"""The reduction from a profiler trace to per-layer numbers."""
from types import SimpleNamespace as NS

import pytest

import tracing


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def line(name, events):
    return NS(name=name, events=events)


def plane(name, lines):
    return NS(name=name, lines=lines)


HLO = '''
HloModule jit_step

%fused_computation.1 (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  ROOT %multiply.3 = f32[4]{0} multiply(%p0, %p0), metadata={op_name="jit(step)/repro.policy_score/mul"}
}

%fused_computation.2 (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %neg.1 = f32[4]{0} negate(%p0), metadata={op_name="jit(step)/repro.greedy_fill/neg"}
  ROOT %multiply.4 = f32[4]{0} multiply(%neg.1, %p0), metadata={op_name="jit(step)/repro.policy_score/mul"}
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/concatenate"}
  %fusion.2 = f32[4]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
  %while.5 = f32[4]{0} while(%fusion.2), condition=%cond, body=%body, metadata={op_name="jit(step)/repro.greedy_fill/while"}
  ROOT %copy.9 = f32[4]{0} copy(%while.5)
}
'''


def test_hlo_scopes_follow_fused_computations():
    m = tracing.hlo_scopes([HLO])
    assert m["fusion.1"] == {"policy_score"}
    assert m["fusion.2"] == {"policy_score", "greedy_fill"}
    assert m["while.5"] == {"greedy_fill"}
    assert m["copy.9"] == frozenset()
    assert tracing.scopes_of("fusion.1", m) == ("repro.policy_score",)
    assert tracing.scopes_of("%fusion.2 = f32[4]{0} fusion(%fusion.1)",
                             m) == ("repro.greedy_fill",
                                    "repro.policy_score")
    assert tracing.scopes_of("copy.9", m) == ()


def test_leaf_intervals_give_each_instant_to_the_innermost_op():
    a, b, c = "while", "body1", "body2"
    out = tracing._leaf_intervals([(0, 100, a), (10, 30, b), (50, 60, c)])
    total = {}
    for s, e, x in out:
        total[x] = total.get(x, 0) + e - s
    assert total == {a: 70, b: 20, c: 10}


def test_reduce_busy_idle_scopes_and_gaps():
    host = plane("/host:CPU", [line("python", [
        ev("bench.step", 1000, 400), ev("bench.host", 1400, 600),
        ev("bench.step", 2000, 400), ev("bench.host", 2400, 100),
        ev("other", 0, 5000)])])
    dev = plane("/device:TPU:0", [
        line("XLA Ops", [
            ev("fusion.1", 1100, 100), ev("while.5", 1200, 150),
            ev("fusion.2", 1250, 50), ev("copy.9", 2100, 200),
            ev("fusion.1", 900, 150)]),
        line("XLA Modules", [ev("jit_step", 1000, 2000)])])
    pd = NS(planes=[host, dev])
    out = tracing.reduce(pd, tracing.hlo_scopes([HLO]))
    ns = 1e-9
    assert out["window_s"] == pytest.approx(1500 * ns)
    # fusion.1 [1000,1050) (clipped at the window) and [1100,1200),
    # while [1200,1350), copy [2100,2300): 500 ns busy
    assert out["busy_s"] == pytest.approx(500 * ns)
    sc = out["scope_s"]
    assert sc["repro.policy_score"] == pytest.approx(200 * ns)
    assert sc["repro.greedy_fill"] == pytest.approx(150 * ns)
    assert sc["mixed"] == pytest.approx(50 * ns)
    assert sc["unscoped"] == pytest.approx(200 * ns)
    gaps = out["breakdown"]["idle_gaps"]
    assert gaps[0] == ["bench.host", pytest.approx(750 * ns)]
    assert gaps[-1] == ["bench.step", pytest.approx(50 * ns)]
    assert len(out["breakdown"]["device_ops"]) <= 10


def test_reduce_needs_host_spans_and_device_ops():
    host = plane("/host:CPU", [line("python", [ev("bench.step", 0, 10)])])
    with pytest.raises(RuntimeError, match="device"):
        tracing.reduce(NS(planes=[host]), {})
    with pytest.raises(RuntimeError, match="host span"):
        tracing.reduce(NS(planes=[]), {})


def test_recorded_tpu_trace():
    """Three traced slots of serve-paper-steady on one TPU v5e, recorded
    with the harness's spans, and the step's compiled HLO."""
    import gzip
    from pathlib import Path

    from jax.profiler import ProfileData

    data = Path(__file__).parent / "data"
    pd = ProfileData.from_serialized_xspace(gzip.decompress(
        (data / "paper_steady_3slots.xplane.pb.gz").read_bytes()))
    hlo = gzip.decompress((data / "paper_steady_hlo.txt.gz").read_bytes())
    out = tracing.reduce(pd, tracing.hlo_scopes([hlo.decode()]))
    assert 0 < out["busy_s"] < out["window_s"] < 0.1
    assert out["devices"] == 1
    sc = out["scope_s"]
    assert sc["repro.greedy_fill"] > 0 and sc["repro.policy_score"] > 0
    assert sc["unscoped"] > 0
    assert {g[0] for g in out["breakdown"]["idle_gaps"]} <= {
        "bench.step", "bench.host"}
    ops = out["breakdown"]["device_ops"]
    assert ops[0][0].startswith("all ops under ") and len(ops) <= 10
