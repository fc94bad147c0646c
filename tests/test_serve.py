"""Serving-loop tests (repro.serve).

The standing anchors:

* the served trajectory IS the batch trajectory: `make_serve_step`
  reuses `simulate`'s per-slot body and PRNG stream assignment, so
  driving it over t = 0..T-1 matches `simulate` bitwise (per-slot
  backlog, per-slot emissions via the live JSONL events) and exactly
  on the f32 totals;
* latency accounting is deterministic under an injected clock: the
  loop calls it in a fixed pattern (once before the loop, twice per
  slot, once after), percentiles exclude exactly the warmup slots and
  follow `np.percentile` linear interpolation;
* queue-age is FIFO bookkeeping with known answers on hand-built
  arrival/processing sequences;
* the live JSONL/Prometheus export parse-validates and the terminal
  summary event reconciles with the returned ServeReport field for
  field.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CarbonIntensityPolicy,
    NetworkSpec,
    RandomCarbonSource,
    UniformArrivals,
    simulate,
)
from repro.serve import latency_percentiles, serve_loop
from repro.serve.loop import _AgeFifo
from repro.telemetry import validate_jsonl, validate_prometheus

jax.config.update("jax_enable_x64", False)

T = 32
M, N = 6, 3


class FakeClock:
    """Integer-second ticks: every interval is exact in f64, so derived
    latencies are exactly representable and percentile asserts can use
    equality."""

    def __init__(self):
        self.t = 0
        self.calls = 0

    def __call__(self):
        self.calls += 1
        self.t += 1
        return float(self.t)


def _setup():
    rng = np.random.default_rng(3)
    spec = NetworkSpec(
        pe=rng.uniform(1, 8, M).astype(np.float32),
        pc=rng.uniform(2, 100, (M, N)).astype(np.float32),
        Pe=1e4,
        Pc=rng.uniform(1e3, 1e5, N).astype(np.float32),
    )
    return (
        CarbonIntensityPolicy(V=0.05),
        spec,
        RandomCarbonSource(N=N),
        UniformArrivals(M=M, amax=60),
        jax.random.PRNGKey(7),
    )


class TestLatencyAccounting:
    def test_clock_call_pattern_and_exact_percentiles(self):
        clock = FakeClock()
        pol, spec, cs, ar, key = _setup()
        rep = serve_loop(pol, spec, cs, ar, T, key, warmup=2,
                         clock=clock)
        assert clock.calls == 2 * T + 2
        # one tick before + one after each step => 1 s per decision
        np.testing.assert_array_equal(rep.latency_us, np.full(T, 1e6))
        assert rep.p50_us == rep.p95_us == rep.p99_us == 1e6
        assert rep.mean_us == 1e6
        assert rep.wall_s == 2 * T + 1
        assert rep.slots == T and rep.warmup == 2

    def test_tasks_per_sec_over_slots_after_warmup(self):
        """6t tasks arrive in slot t. Slots 2..31 bring 2,970 tasks over
        the 60 ticks from slot 2's first clock call to the loop's last:
        the warm-up slots and their compile stay out."""
        pol, spec, cs, _, key = _setup()
        rep = serve_loop(pol, spec, cs,
                         lambda t, k: jnp.full((M,), t, jnp.float32),
                         T, key, warmup=2, clock=FakeClock())
        assert rep.tasks_arrived == 6 * sum(range(T))
        assert rep.tasks_per_sec == 2970 / 60

    def test_warmup_clamped_on_tiny_runs(self):
        pol, spec, cs, ar, key = _setup()
        rep = serve_loop(pol, spec, cs, ar, 1, key, warmup=5,
                         clock=FakeClock())
        assert rep.warmup == 0 and rep.slots == 1

    def test_percentile_definition(self):
        lat = np.asarray([100.0, 200.0, 300.0, 400.0])
        p50, p95, p99, mean = latency_percentiles(lat)
        assert p50 == np.percentile(lat, 50)
        assert p95 == np.percentile(lat, 95)
        assert p99 == np.percentile(lat, 99)
        assert mean == lat.mean()


class TestBatchParity:
    def test_served_trajectory_matches_simulate(self, tmp_path):
        pol, spec, cs, ar, key = _setup()
        rep = serve_loop(pol, spec, cs, ar, T, key, warmup=2,
                         clock=FakeClock(), outdir=tmp_path,
                         stem="parity", flush_every=8)
        res = simulate(pol, spec, cs, ar, T, key)
        backlog = np.asarray(jax.vmap(
            lambda qe, qc: jnp.sum(qe) + jnp.sum(qc)
        )(res.Qe, res.Qc))
        np.testing.assert_array_equal(rep.backlog, backlog)
        assert rep.tasks_dispatched == float(res.dispatched.sum())
        assert rep.tasks_processed == float(res.processed.sum())
        np.testing.assert_allclose(
            rep.total_emissions, float(res.emissions.sum()), rtol=1e-6
        )
        # per-slot emissions round-trip through the live JSONL bitwise
        events = [
            json.loads(line)
            for line in (tmp_path / "parity.jsonl").read_text()
            .splitlines()
        ]
        slots = [e for e in events if e["event"] == "slot"]
        assert len(slots) == T
        np.testing.assert_array_equal(
            np.float32([e["emissions"] for e in slots]),
            np.asarray(res.emissions),
        )


class TestQueueAge:
    def test_fifo_known_sequence(self):
        fifo = _AgeFifo()
        # t=0: 10 arrive, none processed -> oldest is age 0
        assert fifo.update(0, 10.0, 0.0) == 0
        # t=1: nothing arrives, 4 processed -> 6 of slot-0 left, age 1
        assert fifo.update(1, 0.0, 4.0) == 1
        # t=2: 5 arrive, 6 processed -> slot-0 drained, 5 of slot-2
        assert fifo.update(2, 5.0, 6.0) == 0
        # t=3: nothing arrives, 5 processed -> empty, age 0
        assert fifo.update(3, 0.0, 5.0) == 0
        assert fifo.update(4, 0.0, 3.0) == 0

    def test_overdrain_never_negative(self):
        fifo = _AgeFifo()
        fifo.update(0, 2.0, 0.0)
        assert fifo.update(1, 0.0, 100.0) == 0

    def test_report_max_queue_age(self):
        pol, spec, cs, ar, key = _setup()
        rep = serve_loop(pol, spec, cs, ar, T, key,
                         clock=FakeClock())
        assert rep.max_queue_age == int(np.max(rep.queue_age))
        assert rep.max_queue_age >= 0


class TestLiveExport:
    def test_outputs_validate_and_summary_reconciles(self, tmp_path):
        pol, spec, cs, ar, key = _setup()
        rep = serve_loop(pol, spec, cs, ar, T, key, warmup=2,
                         clock=FakeClock(), outdir=tmp_path,
                         flush_every=8)
        jsonl = (tmp_path / "serve.jsonl").read_text()
        assert validate_jsonl(jsonl) == T + 1
        assert validate_prometheus(
            (tmp_path / "serve.prom").read_text()) > 0
        summary = json.loads(jsonl.splitlines()[-1])
        assert summary["event"] == "summary"
        assert summary["kind"] == "serve"
        for field in ("slots", "warmup", "tasks_arrived",
                      "tasks_dispatched", "tasks_processed",
                      "total_emissions", "wall_s", "tasks_per_sec",
                      "p50_us", "p95_us", "p99_us", "mean_us",
                      "max_queue_age"):
            assert summary[field] == getattr(rep, field), field

    def test_histogram_wire_format(self, tmp_path):
        pol, spec, cs, ar, key = _setup()
        serve_loop(pol, spec, cs, ar, T, key, warmup=2,
                   clock=FakeClock(), outdir=tmp_path)
        prom = (tmp_path / "serve.prom").read_text()
        assert "# TYPE repro_serve_latency_us histogram" in prom
        assert 'repro_serve_latency_us_bucket{le="+Inf"} 30' in prom
        assert "repro_serve_latency_us_count 30" in prom

    def test_live_percentiles_match_summary(self, tmp_path):
        """The last live prom snapshot is computed from the same
        non-warmup latencies as the end-of-run report."""
        pol, spec, cs, ar, key = _setup()
        rep = serve_loop(pol, spec, cs, ar, T, key, warmup=2,
                         clock=FakeClock(), outdir=tmp_path)
        prom = (tmp_path / "serve.prom").read_text()
        for line in prom.splitlines():
            if line.startswith("repro_serve_latency_p50_us "):
                assert float(line.split()[-1]) == rep.p50_us
                break
        else:
            pytest.fail("p50 gauge missing from live snapshot")


class TestSmokeCLI:
    def test_main_smoke(self, tmp_path, monkeypatch, capsys,
                        isolated_compile_cache):
        from repro.serve.loop import main

        monkeypatch.setenv("REPRO_SMOKE", "1")
        rep = main(["--slots", "24", "--outdir", str(tmp_path)])
        assert rep.tasks_arrived >= 1e4
        out = capsys.readouterr().out
        assert "decision latency p50" in out
        assert validate_jsonl(
            (tmp_path / "serve.jsonl").read_text()) == 25
