"""The fleet's slot-update scopes, from the program to the reader, and the
recorded TPU trace's reduction pinned to its values."""
import gzip
from pathlib import Path

import pytest

import tracing
from run import load_module, reader_path

NAME = "slot_update_ns_per_lane_slot.fleet"
UPDATE = ("repro.arrivals", "repro.carbon", "repro.queue_update",
          "repro.emissions")


def _reader():
    return load_module(reader_path(NAME))


def _fleet_rec(scope_s, lane_slots=1000):
    return {"kind": "fleet", "trace": {"scope_s": scope_s,
                                       "lane_slots": lane_slots}}


def test_reader_sums_the_update_scopes_per_lane_slot():
    scope_s = {k: 1e-6 * (i + 1) for i, k in enumerate(UPDATE)}
    scope_s.update({"repro.greedy_fill": 5.0, "unscoped": 1.0,
                    "mixed": 2.0})
    # (1 + 2 + 3 + 4) us over 1000 lane-slots
    assert _reader().read(_fleet_rec(scope_s)) == pytest.approx(10.0)


def test_reader_finds_nothing_without_the_scopes():
    read = _reader().read
    # a program without the scopes (the recorded fleet breakdown's keys)
    assert read(_fleet_rec({"repro.greedy_fill": 0.784, "unscoped": 0.027,
                            "repro.policy_score": 0.00167})) is None
    assert read({"kind": "fleet", "trace": None}) is None
    assert read({"kind": "serve",
                 "trace": {"scope_s": {"repro.carbon": 1.0}, "slots": 1,
                           "lane_slots": 1}}) is None


def test_compiled_fleet_program_carries_the_update_scopes():
    """The scopes reach the operations `tracing.reduce` attributes: those
    of the compiled program's HLO."""
    import jax

    from repro.configs.fleet_scenarios import build_fleet
    from repro.core import CarbonIntensityPolicy, simulate_fleet

    fleet = build_fleet(["diurnal", "multi-region-uk"], per_kind=2, Tc=8,
                        seed=5)
    pol = CarbonIntensityPolicy(V=0.05)
    hlo = jax.jit(lambda fl, k: simulate_fleet(
        pol, fl, 8, k, record="summary")).lower(
        fleet, jax.random.PRNGKey(1)).compile().as_text()
    found = set().union(*tracing.hlo_scopes([hlo]).values())
    for scope in UPDATE:
        assert scope.removeprefix("repro.") in found, scope


def test_cpu_rehearsal_reports_the_metric(checkout):
    res, _ = checkout.run("fleet-paper-whatif", seed=2**33 + 9, trace=1)
    assert res["correct"] is True
    assert res["metrics"][f"cpu.{NAME}"]["value"] > 0


def test_recorded_tpu_trace_reduces_to_its_pinned_values():
    """Three traced slots of serve-paper-steady on one TPU v5e: the
    reduction's window, busy time, scope times and idle gaps, pinned so
    that a change to `tracing` that moves them shows."""
    from jax.profiler import ProfileData

    data = Path(__file__).parent / "data"
    pd = ProfileData.from_serialized_xspace(gzip.decompress(
        (data / "paper_steady_3slots.xplane.pb.gz").read_bytes()))
    hlo = gzip.decompress((data / "paper_steady_hlo.txt.gz").read_bytes())
    out = tracing.reduce(pd, tracing.hlo_scopes([hlo.decode()]))
    ns = 1e-9
    assert out["window_s"] == pytest.approx(12_086_490 * ns, abs=0.5 * ns)
    assert out["busy_s"] == pytest.approx(16_946 * ns, abs=0.5 * ns)
    assert out["scope_s"] == {
        k: pytest.approx(v * ns, abs=0.5 * ns) for k, v in {
            "unscoped": 8_213, "repro.policy_score": 846,
            "repro.greedy_fill": 8_093, "mixed": 206}.items()}
    gaps = [(name, round(g / ns)) for name, g in
            out["breakdown"]["idle_gaps"]]
    assert gaps == [("bench.host", g) for g in (
        4_149_488, 4_046_314, 3_870_602, 747, 745, 715, 706, 48, 48, 4)]
