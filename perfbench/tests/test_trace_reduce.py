"""The trace reducer on small recordings."""

import pathlib

import pytest

import trace_reduce
from readers import xplane

DATA = pathlib.Path(__file__).resolve().parent / "data"


def test_op_name_is_the_instruction_without_its_number():
    assert trace_reduce.op_name(
        "%fused_paged_prefill.16 = (bf16[8,1152,4,128]{3,2,1,0}, f32[8]) "
        "custom-call(s32[2048]{0} %copy-done.364)") == "fused_paged_prefill"
    assert trace_reduce.op_name("%conditional = (bf16[1]) x") == "conditional"
    assert trace_reduce.op_name("fusion.7") == "fusion"
    assert trace_reduce.op_name("jit__body(317)") == "jit__body"


def test_union_and_self_times():
    assert trace_reduce.union([(5, 7), (1, 3), (2, 4)]) == [[1, 4], [5, 7]]
    evs = [("while", 0.0, 10e9), ("a", 1e9, 2e9), ("b", 4e9, 3e9),
           ("a", 5e9, 1e9), ("c", 12e9, 1e9)]
    t = trace_reduce.self_times(evs)
    # while: 10 - (2 + 3); b: 3 - 1 (the second a sits inside b)
    assert t == pytest.approx({"while": 5.0, "a": 3.0, "b": 2.0, "c": 1.0})


def test_handmade_recording():
    out = trace_reduce.reduce(trace_reduce.load(DATA / "handmade.pbtxt"))
    assert out["steps"] == 2
    assert out["window_s"] == pytest.approx(9500e-9)      # 500 .. 10000
    # busy inside the window: [1000, 6000) and [8000, 9000)
    assert out["busy_s"] == pytest.approx(6000e-9)
    # self times, the layers' numbered copies of one operation added up
    assert out["op_s"] == pytest.approx(
        {"while": 1500e-9, "fusion": 3000e-9, "custom-call": 1500e-9})
    assert out["device_ops"][0] == ["fusion", pytest.approx(3000e-9)]
    gaps = dict(out["idle_gaps"])
    # [500,1000) inside the first step; [6000,8000): its middle, 7000, is
    # the second step's start, under nothing more specific at 7000 ... the
    # Execute span covers 7100-7900; [9000,10000) inside the second step
    assert sum(gaps.values()) == pytest.approx(3500e-9)
    assert gaps["step()"] == pytest.approx(3500e-9)
    ctx = {"trace": out}
    assert xplane.read(ctx, {"value": "idle_share"}) == pytest.approx(
        100 * 3500 / 9500)
    assert xplane.read(ctx, {"value": "op_ms_per_step",
                             "patterns": ["^custom-call"]}) == \
        pytest.approx(1e3 * 1500e-9 / 2)
    assert xplane.read(ctx, {"value": "op_ms_per_step",
                             "patterns": ["nothing"]}) is None
    assert xplane.read({"trace": None}, {"value": "idle_share"}) is None


def test_no_device_plane_reduces_to_nothing():
    planes = [p for p in trace_reduce.load(DATA / "handmade.pbtxt")
              if p["name"] != "/device:TPU:0"]
    assert trace_reduce.reduce(planes) is None


def test_recorded_v5e_step():
    """One real step, cut down (see the file's header): the planes, lines
    and names are what a v5e trace holds."""
    planes = trace_reduce.load(DATA / "v5e_step.pbtxt")
    assert [p["name"] for p in planes] == ["/device:TPU:0", "/host:CPU"]
    out = trace_reduce.reduce(planes)
    assert out["steps"] == 1
    assert out["window_s"] == pytest.approx(1.115677073)
    # only two layers and the tail were kept, so the device looks idle
    # between them: the kept operations' union
    assert out["busy_s"] == pytest.approx(0.135773753)
    top = dict(out["device_ops"])
    # the 16 `conditional` events (fused ingest vs composed attention) hold
    # the kernels; their own self time is next to nothing
    assert top["fused_paged_prefill"] == pytest.approx(0.124395072)
    assert top["paged_decode_attention_split"] == pytest.approx(0.000235499)
    assert out["op_s"]["conditional"] < 1e-4
    assert sum(out["op_s"].values()) == pytest.approx(out["busy_s"], rel=1e-6)
    gaps = dict(out["idle_gaps"])
    # the cut-out layers' time falls under the host's wait for the tokens
    assert gaps["step(): np.asarray(jax.Array)"] == pytest.approx(0.948240629)
    assert gaps["step()"] == pytest.approx(0.031662652)
    attn = xplane.read({"trace": out}, {
        "value": "op_ms_per_step",
        "patterns": ["^fused_paged_prefill", "^paged_decode_attention"]})
    assert attn == pytest.approx(1e3 * (0.124395072 + 0.000235499))
