"""The reader of the device time by named scope: the protobuf fields it
reads itself, the rule that picks an operation's scope, and both on a step
recorded on a v5e with the stats left in (``data/v5e_step_scopes.pbtxt``)."""

import pathlib

import pytest

import trace_reduce
from readers import xplane, xplane_scopes

DATA = pathlib.Path(__file__).resolve().parent / "data"
ATTN = ("^fused_paged_prefill", "^paged_decode_attention")

TINY = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 7000000 duration_ps: 500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%conditional.1 = conditional(...)"
    stats { metadata_id: 7 str_value: "jit(_body)/attn.kv_write/cond:" } } }
  event_metadata { key: 2 value { id: 2 name: "%fused_paged_prefill_l1.1 = custom-call(...)"
    stats { metadata_id: 7 ref_value: 9 } } }
  event_metadata { key: 3 value { id: 3 name: "%copy.5 = copy(...)" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.2 = fusion(...)"
    stats { metadata_id: 8 str_value: "convolution fusion" }
    stats { metadata_id: 7 str_value: "jit(_body)/mlp/dot_general:" } } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
  stat_metadata { key: 8 value { id: 8 name: "hlo_category" } }
  stat_metadata { key: 9 value { id: 9 name: "jit(_body)/attn.kv_write/cond/branch_0_fun/attn.core/jit(fused_paged_prefill)/fused_paged_prefill_l1/pallas_call:" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "main" timestamp_ns: 500
    events { metadata_id: 1 offset_ps: 0 duration_ps: 8000000 } }
  event_metadata { key: 1 value { id: 1 name: "perfbench.step" } }
  event_metadata { key: 2 value { id: 2 name: "%not.a.device = op(...)"
    stats { metadata_id: 7 str_value: "jit(_body)/mlp/x:" } } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
}
"""


def test_scope_is_the_last_known_component():
    assert xplane_scopes.scope_of("jit(_body)/attn.qkv/dot_general") \
        == "attn.qkv"
    assert xplane_scopes.scope_of(
        "jit(_body)/attn.kv_write/cond/branch_0_fun/attn.kv_write/scatter") \
        == "attn.kv_write"
    assert xplane_scopes.scope_of(
        "jit(_body)/attn.kv_write/cond/branch_0_fun/attn.core/"
        "jit(fused_paged_prefill)/reshape") == "attn.core"
    assert xplane_scopes.scope_of("jit(_body)/attn.kv_write/cond") \
        == "attn.kv_write"
    # a scope's name inside a longer component is not the scope
    assert xplane_scopes.scope_of("jit(_body)/jit(mlp_head)/add") \
        == "unscoped"
    assert xplane_scopes.scope_of("jit(convert)/convert") == "unscoped"
    assert xplane_scopes.scope_of(None) == "unscoped"


def test_paths_come_from_the_metadata_stats_of_device_planes(tmp_path):
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(TINY)
    paths = xplane_scopes.op_paths(raw)
    # a string stat, a stat by reference, no stat, a stat after another;
    # the kind after the colon is dropped; the host plane is not read
    assert paths == {
        "%conditional.1 = conditional(...)": "jit(_body)/attn.kv_write/cond",
        "%fused_paged_prefill_l1.1 = custom-call(...)":
            "jit(_body)/attn.kv_write/cond/branch_0_fun/attn.core/"
            "jit(fused_paged_prefill)/fused_paged_prefill_l1/pallas_call",
        "%fusion.2 = fusion(...)": "jit(_body)/mlp/dot_general"}
    f = tmp_path / "tiny.pbtxt"
    f.write_text(TINY)
    dev, spans = xplane_scopes.load(f)
    assert spans == [(500.0, 8500.0)] and len(dev) == 1
    secs = xplane_scopes.scope_seconds(dev, spans, ATTN)
    # the conditional's own 2 us (4 less the kernel inside it) are
    # attn.kv_write; the kernel is excluded; the copy has no path
    assert secs == pytest.approx({"attn.kv_write": 2000e-9,
                                  "excluded": 2000e-9, "unscoped": 1000e-9,
                                  "mlp": 500e-9})
    # with nothing excluded the kernel reads its own innermost scope
    assert xplane_scopes.scope_seconds(dev, spans)["attn.core"] \
        == pytest.approx(2000e-9)


def test_a_program_without_scopes_reads_nothing():
    """PR 23's recording has no stats, and the parent's operations have
    paths with no scope in them (``jit(_body)/dot_general``): nothing to
    read, not a step that is all ``unscoped``, and no raise."""
    dev, spans = xplane_scopes.load(DATA / "v5e_step.pbtxt")
    assert dev and spans
    assert xplane_scopes.scope_seconds(dev, spans, ATTN) is None
    bare = [[(n, s, d, "jit(_body)/dot_general") for n, s, d, _ in dev[0]]]
    assert xplane_scopes.scope_seconds(bare, spans, ATTN) is None
    assert xplane_scopes.scope_seconds([], spans) is None
    assert xplane_scopes.read({"trace": None}, {"scopes": ["mlp"]}) is None


def test_recorded_v5e_step_splits_by_scope():
    f = DATA / "v5e_step_scopes.pbtxt"
    dev, spans = xplane_scopes.load(f)
    secs = xplane_scopes.scope_seconds(dev, spans, ATTN)
    out = trace_reduce.reduce(trace_reduce.load(f))
    # the parts, the kernels and unscoped are the device's busy time
    assert sum(secs.values()) == pytest.approx(out["busy_s"], rel=1e-9)
    ctx = {"trace": out}
    kernels = xplane.read(ctx, {"value": "op_ms_per_step",
                                "patterns": list(ATTN)})
    assert secs["excluded"] * 1e3 == pytest.approx(kernels, rel=1e-9)
    # the three launches of the layer are told apart by name
    by_name = {k: xplane.read(ctx, {"value": "op_ms_per_step",
                                    "patterns": [f"^{k}"]})
               for k in ("fused_paged_prefill_l0", "fused_paged_prefill_l1",
                         "paged_decode_attention")}
    assert by_name["fused_paged_prefill_l0"] == pytest.approx(30.965, abs=0.01)
    assert by_name["fused_paged_prefill_l1"] == pytest.approx(30.954, abs=0.01)
    assert sum(by_name.values()) == pytest.approx(kernels, rel=1e-9)
    # one layer's K/V write: the scatters' pool-sized copies and those of
    # the cond are under attn.kv_write, not unscoped
    assert secs["attn.kv_write"] * 1e3 == pytest.approx(1.143, abs=0.01)
    assert secs["mlp"] * 1e3 == pytest.approx(0.534, abs=0.01)
    assert secs["head"] * 1e3 == pytest.approx(0.360, abs=0.01)
    assert set(secs) == {"excluded", "unscoped", "embed", "attn.qkv",
                         "attn.kv_write", "attn.core", "attn.out", "mlp",
                         "head", "sample"}
    # the idle gap of the step reads an engine phase or JAX's own call
    # inside it, never the bare step
    assert all(label != "step()" for label, _ in out["idle_gaps"])
    assert any("engine." in label for label, _ in out["idle_gaps"])
