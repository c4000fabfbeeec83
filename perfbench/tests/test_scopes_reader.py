"""The reader of device time by the scopes a metric's own file names: it
finds the parts of a block ``xplane_scopes`` has no name for, shares the
roofline rule of ``roofline``, and reads nothing from a program that lacks
the parts."""

import types

import pytest

from readers import scopes, xplane_scopes

TRACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 7000000 duration_ps: 500000 }
    events { metadata_id: 5 offset_ps: 7500000 duration_ps: 250000 }
    events { metadata_id: 6 offset_ps: 7750000 duration_ps: 125000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%while.1 = while(...)"
    stats { metadata_id: 7 str_value: "jit(_body)/ssm.scan/while:" } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.9 = fusion(...)"
    stats { metadata_id: 7 str_value: "jit(_body)/ssm.scan/while/body/dot_general:" } } }
  event_metadata { key: 3 value { id: 3 name: "%moe_gmm.3 = custom-call(...)"
    stats { metadata_id: 7 str_value: "jit(_body)/moe.experts/jit(gmm)/moe_gmm/pallas_call:" } } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.2 = fusion(...)"
    stats { metadata_id: 7 str_value: "jit(_body)/attn.qkv/dot_general:" } } }
  event_metadata { key: 5 value { id: 5 name: "%copy.4 = copy(...)" } }
  event_metadata { key: 6 value { id: 6 name: "%fusion.5 = fusion(...)"
    stats { metadata_id: 7 str_value: "jit(_body)/mul:" } } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "main" timestamp_ns: 500
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 } }
  event_metadata { key: 1 value { id: 1 name: "perfbench.step" } }
}
"""


@pytest.fixture
def ctx(tmp_path, monkeypatch):
    from jax.profiler import ProfileData

    d = tmp_path / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    (d / "h.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(TRACE))
    monkeypatch.setattr(xplane_scopes, "TRACE_DIR", tmp_path)
    counts = types.SimpleNamespace(
        flops=lambda m, f: 197e12 * 1e-6 * f["tokens"],
        bytes=lambda m, f: 819e9 * 3e-6)
    said = []
    return {"trace": {"steps": 1, "step_indices": [0]},
            "steps": [{"tokens": 2}], "model": {}, "say": said.append,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "family": types.SimpleNamespace(counts=counts)}


def test_named_parts_are_found_with_their_loops_inside(ctx):
    # the while's 4 us hold the fusion's 2: self times add up to 4
    assert scopes.read(ctx, {"scopes": ["ssm.scan"]}) \
        == pytest.approx(4000e-9 * 1e3)
    assert scopes.read(ctx, {"scopes": ["ssm.scan", "moe.experts"]}) \
        == pytest.approx(5000e-9 * 1e3)
    assert scopes.read(ctx, {"scopes": ["moe.experts"],
                             "exclude": ["^moe_gmm"]}) is None
    # a part of the Llama step is found as xplane_scopes finds it
    assert scopes.read(ctx, {"scopes": ["attn.qkv"]}) \
        == pytest.approx(xplane_scopes.read(ctx, {"scopes": ["attn.qkv"]}))


def test_the_remainder_closes_the_sum(ctx):
    """``unscoped`` is what lies under none of the parts, the block's
    ``known`` ones included: a copy with no path and an operation outside
    every scope; parts and remainder add up to the busy time."""
    block = ["ssm.scan", "moe.experts"]
    rest = scopes.read(ctx, {"scopes": ["unscoped"], "known": block})
    assert rest == pytest.approx(375e-9 * 1e3)
    parts = scopes.read(ctx, {"scopes": block + ["attn.qkv"]})
    assert parts + rest == pytest.approx(5875e-9 * 1e3)
    # without the block's names its parts would be swallowed, and a program
    # that has none of them reads nothing
    assert scopes.read(ctx, {"scopes": ["unscoped"],
                             "known": ["ssm.conv"]}) is None


def test_a_share_is_the_larger_bound_over_the_parts_time(ctx):
    spec = {"scopes": ["ssm.scan"],
            "bounds": [{"count": "flops", "peak": "bf16_flops_per_s"},
                       {"count": "bytes", "peak": "hbm_bytes_per_s"}]}
    assert scopes.read(ctx, spec) == pytest.approx(100 * 3e-6 / 4e-6)
    assert "bytes 0.003 ms" in ctx["say"].__self__[0]
    ctx["steps"][0]["tokens"] = 5          # now the operations bound it
    assert scopes.read(ctx, spec) == pytest.approx(100 * 5e-6 / 4e-6)


def test_a_program_without_the_parts_reads_nothing(ctx):
    assert scopes.read(ctx, {"scopes": ["ssm.conv"]}) is None
    spec = {"scopes": ["ssm.scan"],
            "bounds": [{"count": "absent", "peak": "hbm_bytes_per_s"}]}
    assert scopes.read(ctx, spec) is None          # the family lacks a count
    spec["bounds"][0]["count"] = "flops"
    ctx["steps"][0] = {}
    assert scopes.read(ctx, spec) is None          # the step lacks a fact
    assert scopes.read(dict(ctx, trace=None), {"scopes": ["ssm.scan"]}) \
        is None
