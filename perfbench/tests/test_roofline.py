"""The general roofline reader on hand-made inputs, and the harness's mark of
which steps were traced."""

import types

import pytest

import counts
import trace_reduce
from readers import roofline, xplane_scopes

M = {"hidden_size": 4096, "intermediate_size": 14336, "num_hidden_layers": 16,
     "num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 128,
     "vocab_size": 32768}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
BOUNDS = [{"count": "dense_flops", "peak": "bf16_flops_per_s"},
          {"count": "dense_bytes", "peak": "hbm_bytes_per_s"}]


def _ctx(op_s, indices, steps, said):
    return {"trace": {"steps": len(indices or ()), "op_s": op_s,
                      "step_indices": indices},
            "steps": steps, "model": M, "peaks": PEAKS, "say": said.append,
            "family": types.SimpleNamespace(counts=counts)}


def test_each_step_is_held_to_the_larger_of_its_bounds():
    steps = [{"tokens": 9999}, {"tokens": 48, "prefill_tokens": 0},
             {"tokens": 560, "prefill_tokens": 512}]
    said = []
    ctx = _ctx({"fusion": 0.040, "convolution_fusion": 0.030, "copy": 0.5},
               [1, 2], steps, said)
    spec = {"patterns": ["^fusion$", "^convolution"], "bounds": BOUNDS}
    # 48 rows: reading 16 x 416 MiB of weights (8.52 ms) outlasts the MXU's
    # 1.70 ms; 560 rows: 19.84 ms of MXU time outlast it
    elems = 16 * 218_103_808
    least = 2 * elems / 819e9 + 2 * 560 * elems / 197e12
    assert roofline.read(ctx, spec) == pytest.approx(100 * least / 0.070)
    assert least == pytest.approx(0.00852 + 0.01984, rel=1e-3)
    line, = said
    assert "step 1: 48 tokens (0 prefill), dense_bytes" in line
    assert "step 2: 560 tokens (512 prefill), dense_flops" in line


def test_scopes_select_as_the_scope_reader_does(monkeypatch):
    monkeypatch.setattr(xplane_scopes, "seconds",
                        lambda ctx, spec: 0.020 if spec["scopes"] == ["mlp"]
                        else None)
    ctx = _ctx({}, [0], [{"tokens": 512}], [])
    got = roofline.read(ctx, {"scopes": ["mlp"], "bounds": BOUNDS[:1]})
    assert got == pytest.approx(
        100 * counts.dense_flops(M, {"tokens": 512}) / 197e12 / 0.020)
    assert roofline.read(ctx, {"scopes": ["head"], "bounds": BOUNDS}) is None


def test_nothing_to_read_is_none_and_never_zero():
    steps = [{"tokens": 48}]
    spec = {"patterns": ["^fusion$"], "bounds": BOUNDS}
    op_s = {"fusion": 0.04}
    assert roofline.read({"trace": None, "peaks": PEAKS}, spec) is None
    assert roofline.read(_ctx(op_s, None, steps, []), spec) is None
    assert roofline.read(_ctx({"copy": 1.0}, [0], steps, []), spec) is None
    assert roofline.read(dict(_ctx(op_s, [0], steps, []), peaks=None),
                         spec) is None
    # a count that needs a fact the step lacks
    assert roofline.read(_ctx(op_s, [0], [{"sampled": 1}], []), spec) is None
    with pytest.raises(AttributeError):
        roofline.read(_ctx(op_s, [0], steps, []),
                      {"patterns": ["^fusion$"],
                       "bounds": [{"count": "no_such_count",
                                   "peak": "hbm_bytes_per_s"}]})


def test_the_tracer_notes_which_steps_it_wrapped(tmp_path, monkeypatch):
    import jax

    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    tracer = trace_reduce.Tracer(tmp_path / "trace", steps=2, at=0.4)
    for i in range(12):     # 3 fill steps, then a window of 9 steps of 1 s
        if i >= 3:
            tracer.tick(i - 3, 9.0)
        with tracer.around_step():
            pass
    tracer.close()
    assert tracer.traced == [7, 8] and tracer.state == "done"
    # counted from the window's first step, as ctx["steps"] is
    monkeypatch.setattr(tracer, "path", lambda: "x")
    monkeypatch.setattr(trace_reduce, "load", lambda path: [])
    monkeypatch.setattr(trace_reduce.os.path, "getsize", lambda path: 0)
    for seen, want in ((2, [4, 5]), (3, None)):
        monkeypatch.setattr(trace_reduce, "reduce", lambda planes: {
            "steps": seen, "window_s": 1.0, "busy_s": 0.5})
        out = tracer.reduce(say=lambda s: None, first=3)
        assert out["step_indices"] == want
