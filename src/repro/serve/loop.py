"""Serving loop: streaming arrivals through one compiled step.

The batch simulators run T slots inside one `lax.scan`; a serving
deployment sees slots arrive in real time and must DECIDE each one as
it lands. This module promotes `examples/serve_batch.py`'s ad-hoc loop
into the library: `make_serve_step` compiles exactly one donated-buffer
step function (the SAME per-slot program as `core.simulator.simulate`'s
scan body, same PRNG stream splits -- so a served trajectory is bitwise
the batch trajectory), and `serve_loop` drives it from the host,
timing every decision. The step returns its slot's metrics as one f32
vector in the order `SERVE_METRICS` (plus `DEADLINE_METRICS` when
deadline-aware), and the loop reads it to the host once per slot, under
`serve.pull`: one device-to-host read, not one per scalar.

Observability contract (ISSUE 9 / DESIGN.md §Live observability):

* decision latency -- wall time of one step call, device-synced via
  `block_until_ready`, recorded per slot; percentiles (p50/p95/p99,
  `np.percentile` linear interpolation) exclude the first `warmup`
  slots, where the call pays XLA compilation;
* throughput -- tasks/sec over slots[warmup:], from the clock call
  before the first of them to the loop's closing call;
* queue age -- a host-side FIFO of (arrival slot, count) drained
  oldest-first by each slot's processing attempts: the age of the
  oldest unserved task, per slot, plus its max over the run;
* live export -- every `flush_every` slots the JSONL event log grows
  one `slot` event per slot and the Prometheus snapshot (counters,
  gauges, a latency histogram) is rewritten, so the run is watchable
  while it executes. `close` appends the terminal `summary` event --
  computed from the SAME per-slot arrays as the live events, so the
  live series always reconciles with the end-of-run `ServeReport`.

The clock is injectable (`clock=` callable returning seconds) and the
loop calls it in a fixed pattern -- once before the loop, twice per
slot (around the step), once after -- so tests drive it with a fake
and get deterministic histograms.

Under a running profiler each slot's host work shows as the four
`telemetry.profile.HOST_SPANS`: `serve.dispatch` and `serve.sync`
inside the latency bracket, `serve.pull` and `serve.bookkeeping` after
it (`telemetry.profile.trace_to` records one).
"""
from __future__ import annotations

import argparse
import json
import os
import time
from pathlib import Path
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.queueing import (
    Action,
    NetworkSpec,
    emissions,
    init_state,
)
from repro.core.queueing import step as queue_step
from repro.core.simulator import as_data
from repro.telemetry.profile import phase, span

# Latency histogram buckets (microseconds), Prometheus-style with a
# terminal +Inf bucket appended by the exporter.
LATENCY_BUCKETS_US = (
    50.0, 100.0, 200.0, 500.0, 1e3, 2e3, 5e3, 1e4, 2e4, 5e4, 1e5, 1e6,
)

# The entries of the served step's metrics vector, in order; a
# deadline-aware step appends DEADLINE_METRICS.
SERVE_METRICS = ("emissions", "arrived", "dispatched", "processed",
                 "backlog")
DEADLINE_METRICS = ("missed", "shed")


class ServeReport(NamedTuple):
    """End-of-run summary of a `serve_loop` drive. Scalar fields are
    what the terminal JSONL `summary` event carries; the arrays are the
    full per-slot series behind them."""

    slots: int
    warmup: int            # leading slots excluded from percentiles
    tasks_arrived: float
    tasks_dispatched: float
    tasks_processed: float
    total_emissions: float
    wall_s: float
    tasks_per_sec: float   # arrived in slots[warmup:] / their wall time
    p50_us: float          # decision-latency percentiles over
    p95_us: float          #   slots[warmup:]
    p99_us: float
    mean_us: float
    max_queue_age: int     # slots; oldest unserved task over the run
    latency_us: np.ndarray  # [slots] every decision, warmup included
    backlog: np.ndarray     # [slots] post-step Qe+Qc total
    queue_age: np.ndarray   # [slots] oldest unserved task's age
    # deadline-aware serving (zero / 0.0 when `deadlines` is off):
    missed_total: float = 0.0  # tasks expired past their deadline
    shed_total: float = 0.0    # arrivals rejected by admission control
    age_p50: float = 0.0       # queue-age percentiles over all slots --
    age_p95: float = 0.0       #   read against the configured deadline
    age_p99: float = 0.0       #   (the queue-age-vs-deadline export)
    age_over_deadline_frac: float = 0.0  # slots with age > min deadline
    # the step's carried state after the last slot (NetworkState, or
    # (NetworkState, DeadlineState) when deadline-aware) and the
    # per-slot emissions -- what the batch simulator's final Qe/Qc and
    # `emissions` series are compared against
    final_state: object = None
    slot_emissions: np.ndarray = None  # [slots]


def latency_percentiles(lat_us) -> tuple:
    """(p50, p95, p99, mean) of a latency sample, `np.percentile`
    linear interpolation -- the one definition every consumer
    (ServeReport, live export, bench rows, perf_table) shares."""
    lat = np.asarray(lat_us, np.float64)
    p50, p95, p99 = (float(x) for x in
                     np.percentile(lat, [50.0, 95.0, 99.0]))
    return p50, p95, p99, float(lat.mean())


def make_serve_step(policy, spec: NetworkSpec, carbon_source,
                    arrival_source, key, deadlines=None) -> Callable:
    """Compiles the one serving step: `(state, t) -> (state', metrics)`
    with the state buffers DONATED (the loop never reuses the old
    state, so XLA may update queues in place).

    The body is `core.simulator.simulate`'s fault-free scan body with
    the same `jax.random.split(key, 3)` stream assignment, so driving
    it over t = 0..T-1 reproduces the batch trajectory bitwise.
    `metrics` is one f32 vector of shape [5] in the order
    `SERVE_METRICS` (emissions, arrived, dispatched, processed,
    backlog), stacked from the f32 scalars, so each entry is bitwise
    the scalar it holds and the host reads the slot in one transfer.

    With `deadlines` (a DeadlineParams) the carried state becomes the
    pair `(NetworkState, DeadlineState)`, the policy receives the
    slot's `deadline_view`, and metrics grows to [7] with
    `DEADLINE_METRICS` (missed, shed) -- the same deadline slot
    dynamics as the batch simulator, so the deadline-aware served
    trajectory is bitwise the batch one too.
    """
    k_carbon, k_arrive, k_policy = jax.random.split(key, 3)
    if deadlines is not None:
        from repro.deadlines.model import deadline_view, step_deadlines

    def step(state, t):
        if deadlines is not None:
            state, dstate = state
        spec_t, _ = as_data(spec)  # as in simulate
        with phase("carbon"):
            Ce, Cc = carbon_source(t, k_carbon)
        with phase("arrivals"):
            a = arrival_source(t, k_arrive)
        k_t = jax.random.fold_in(k_policy, t)
        if deadlines is None:
            act: Action = policy(state, spec_t, Ce, Cc, a, k_t)
        else:
            act = policy(state, spec_t, Ce, Cc, a, k_t,
                         deadline_view=deadline_view(deadlines, dstate))
        C_t = emissions(spec_t, act, Ce, Cc)
        metrics = (
            C_t,
            jnp.sum(a),
            jnp.sum(act.d),
            jnp.sum(act.w),
        )
        if deadlines is None:
            nxt = queue_step(state, act, a)
            return nxt, jnp.stack(metrics + (
                jnp.sum(nxt.Qe) + jnp.sum(nxt.Qc),
            ))
        d_sum = jnp.sum(act.d, axis=1)
        dstate, admitted, expired, shed = step_deadlines(
            deadlines, dstate, d_sum, a
        )
        with phase("queue_update"):
            nxt = state._replace(
                Qe=jnp.maximum(state.Qe - d_sum, 0.0) + admitted - expired,
                Qc=jnp.maximum(state.Qc - act.w, 0.0) + act.d,
            )
        return (nxt, dstate), jnp.stack(metrics + (
            jnp.sum(nxt.Qe) + jnp.sum(nxt.Qc),
            jnp.sum(expired),
            jnp.sum(shed),
        ))

    return jax.jit(step, donate_argnums=0)


class _AgeFifo:
    """Host-side queue-age bookkeeping: arrivals enqueue (slot, count),
    processing attempts drain oldest-first; `age(t)` is the age of the
    oldest task still waiting. An approximation of per-task sojourn
    (the device queues are per-type/cloud, the FIFO is global) but an
    exact upper-bound gauge for "how stale is the oldest work"."""

    def __init__(self):
        self._fifo: list = []

    def update(self, t: int, arrived: float, processed: float) -> int:
        if arrived > 0:
            self._fifo.append([t, arrived])
        drain = processed
        while drain > 0 and self._fifo:
            head = self._fifo[0]
            take = min(head[1], drain)
            head[1] -= take
            drain -= take
            if head[1] <= 0:
                self._fifo.pop(0)
        return t - self._fifo[0][0] if self._fifo else 0


class ServeExporter:
    """Live Prometheus/JSONL writer for a serving run (the serve-side
    sibling of telemetry.export.FollowedRun). Buffers slot events and
    flushes every `flush_every` slots: appends the events to
    `<stem>.jsonl` and rewrites `<stem>.prom`. `close(report)` appends
    the terminal `summary` event built from the ServeReport, so
    `validate_jsonl` passes and live series reconcile with the summary
    by construction."""

    def __init__(self, outdir, stem: str = "serve",
                 flush_every: int = 16, warmup: int = 2):
        outdir = Path(outdir)
        outdir.mkdir(parents=True, exist_ok=True)
        self.paths = {
            "jsonl": outdir / f"{stem}.jsonl",
            "prometheus": outdir / f"{stem}.prom",
        }
        self.paths["jsonl"].write_text("")
        self.flush_every = flush_every
        self.warmup = warmup
        self._pending: list = []
        self._slots = 0
        self._lat: list = []       # non-warmup latencies so far
        self._totals = {"arrived": 0.0, "dispatched": 0.0,
                        "processed": 0.0, "emissions": 0.0,
                        "missed": 0.0, "shed": 0.0}
        self._last = {"backlog": 0.0, "queue_age": 0}

    def record(self, t: int, latency_us: float, arrived: float,
               dispatched: float, processed: float, backlog: float,
               queue_age: int, emissions_t: float,
               missed: float = 0.0, shed: float = 0.0) -> None:
        self._pending.append(json.dumps({
            "event": "slot", "kind": "serve", "t": t,
            "latency_us": latency_us, "arrived": arrived,
            "dispatched": dispatched, "processed": processed,
            "backlog": backlog, "queue_age": queue_age,
            "emissions": emissions_t, "warmup": t < self.warmup,
            "missed": missed, "shed": shed,
        }))
        self._slots += 1
        if t >= self.warmup:
            self._lat.append(latency_us)
        self._totals["arrived"] += arrived
        self._totals["dispatched"] += dispatched
        self._totals["processed"] += processed
        self._totals["emissions"] += emissions_t
        self._totals["missed"] += missed
        self._totals["shed"] += shed
        self._last = {"backlog": backlog, "queue_age": queue_age}
        if len(self._pending) >= self.flush_every:
            self.flush()

    def flush(self) -> None:
        if self._pending:
            with self.paths["jsonl"].open("a") as fh:
                fh.write("\n".join(self._pending) + "\n")
            self._pending = []
        self.paths["prometheus"].write_text(self._prometheus())

    def _prometheus(self) -> str:
        lines = []

        def emit(name, kind, help_, samples):
            lines.append(f"# HELP {name} {help_}")
            lines.append(f"# TYPE {name} {kind}")
            for labels, value in samples:
                lines.append(f"{name}{labels} {value:.10g}")

        emit("repro_serve_slots", "counter", "slots decided so far",
             [("", self._slots)])
        for k, v in self._totals.items():
            unit = "gCO2" if k == "emissions" else "tasks"
            help_ = {
                "missed": "tasks expired past their deadline (tasks)",
                "shed": "arrivals rejected by admission control (tasks)",
            }.get(k, f"running {k} over served slots ({unit})")
            emit(f"repro_serve_{k}_total", "counter", help_, [("", v)])
        emit("repro_serve_backlog", "gauge",
             "post-step backlog at the newest slot (tasks)",
             [("", self._last["backlog"])])
        emit("repro_serve_queue_age", "gauge",
             "oldest unserved task's age at the newest slot (slots)",
             [("", self._last["queue_age"])])
        if self._lat:
            lat = np.asarray(self._lat)
            p50, p95, p99, mean = latency_percentiles(lat)
            for q, v in (("p50", p50), ("p95", p95), ("p99", p99),
                         ("mean", mean)):
                emit(f"repro_serve_latency_{q}_us", "gauge",
                     f"decision latency {q} over non-warmup slots (us)",
                     [("", v)])
            name = "repro_serve_latency_us"
            lines.append(f"# HELP {name} decision latency (us)")
            lines.append(f"# TYPE {name} histogram")
            cum = 0
            for b in LATENCY_BUCKETS_US:
                cum = int((lat <= b).sum())
                lines.append(f'{name}_bucket{{le="{b:g}"}} {cum}')
            lines.append(f'{name}_bucket{{le="+Inf"}} {lat.size}')
            lines.append(f"{name}_sum {lat.sum():.10g}")
            lines.append(f"{name}_count {lat.size}")
        return "\n".join(lines) + "\n"

    def close(self, report: ServeReport) -> dict:
        self.flush()
        summary = {
            "event": "summary", "kind": "serve",
            "slots": report.slots, "warmup": report.warmup,
            "tasks_arrived": report.tasks_arrived,
            "tasks_dispatched": report.tasks_dispatched,
            "tasks_processed": report.tasks_processed,
            "total_emissions": report.total_emissions,
            "wall_s": report.wall_s,
            "tasks_per_sec": report.tasks_per_sec,
            "p50_us": report.p50_us, "p95_us": report.p95_us,
            "p99_us": report.p99_us, "mean_us": report.mean_us,
            "max_queue_age": report.max_queue_age,
            "missed_total": report.missed_total,
            "shed_total": report.shed_total,
            "age_p50": report.age_p50, "age_p95": report.age_p95,
            "age_p99": report.age_p99,
            "age_over_deadline_frac": report.age_over_deadline_frac,
        }
        with self.paths["jsonl"].open("a") as fh:
            fh.write(json.dumps(summary) + "\n")
        self.paths["prometheus"].write_text(self._prometheus())
        return self.paths


def serve_loop(policy, spec: NetworkSpec, carbon_source, arrival_source,
               T: int, key, *, warmup: int = 2, clock=None,
               outdir=None, stem: str = "serve",
               flush_every: int = 16, deadlines=None) -> ServeReport:
    """Drives `make_serve_step` for T slots from the host, timing every
    decision. `clock` defaults to `time.perf_counter`; inject a fake
    (called 2T + 2 times: loop start, before/after each step, loop end)
    for deterministic latency tests. `outdir` turns on live export via
    ServeExporter. Percentiles cover slots[warmup:] (slot 0 pays XLA
    compilation); `warmup` is clamped to T-1 so tiny runs still report.

    `deadlines` (a DeadlineParams) serves deadline-aware: per-slot
    expiries/sheds accumulate into the report and the live export, and
    the queue-age percentiles are read against the tightest configured
    deadline (`age_over_deadline_frac`).
    """
    if clock is None:
        clock = time.perf_counter
    warmup = max(0, min(warmup, T - 1))
    exporter = None
    if outdir is not None:
        exporter = ServeExporter(outdir, stem=stem,
                                 flush_every=flush_every, warmup=warmup)
    step = make_serve_step(policy, spec, carbon_source, arrival_source,
                           key, deadlines=deadlines)
    state = init_state(spec.M, spec.N)
    if deadlines is not None:
        from repro.deadlines.model import init_deadlines

        state = (state, init_deadlines(spec.M, deadlines.rings.shape[-1]))
    ages = _AgeFifo()
    lat = np.zeros(T)
    backlog = np.zeros(T)
    slot_arrived = np.zeros(T)
    slot_emissions = np.zeros(T)
    queue_age = np.zeros(T, np.int64)
    totals = {"arrived": 0.0, "dispatched": 0.0, "processed": 0.0,
              "emissions": 0.0, "missed": 0.0, "shed": 0.0}

    t_start = t_measured = clock()
    for t in range(T):
        c0 = clock()
        with span("serve.dispatch"):
            state, metrics = step(state, jnp.int32(t))
        with span("serve.sync"):
            jax.block_until_ready(metrics)
        c1 = clock()
        lat[t] = (c1 - c0) * 1e6
        with span("serve.pull"):
            pulled = np.asarray(metrics).tolist()
        with span("serve.bookkeeping"):
            missed_t = shed_t = 0.0
            if deadlines is None:
                em_t, arrived, dispatched, processed, bl = pulled
            else:
                (em_t, arrived, dispatched, processed, bl,
                 missed_t, shed_t) = pulled
            if t == warmup:
                t_measured = c0
            totals["arrived"] += arrived
            totals["dispatched"] += dispatched
            totals["processed"] += processed
            totals["emissions"] += em_t
            totals["missed"] += missed_t
            totals["shed"] += shed_t
            slot_arrived[t] = arrived
            backlog[t] = bl
            slot_emissions[t] = em_t
            # shed arrivals never enter the queue; missed tasks leave it
            # by expiry -- both must flow through the age FIFO or the
            # gauge reads phantom tasks (no-ops when the deadline layer
            # is off)
            queue_age[t] = ages.update(t, arrived - shed_t,
                                       processed + missed_t)
            if exporter is not None:
                exporter.record(t, lat[t], arrived, dispatched, processed,
                                bl, int(queue_age[t]), em_t,
                                missed=missed_t, shed=shed_t)
    t_end = clock()
    wall_s = t_end - t_start

    p50, p95, p99, mean = latency_percentiles(lat[warmup:])
    age_p50, age_p95, age_p99 = (
        float(x) for x in np.percentile(queue_age, [50.0, 95.0, 99.0])
    )
    over_frac = 0.0
    if deadlines is not None:
        d = np.asarray(deadlines.deadline, np.float64)
        finite = d[np.isfinite(d)]
        if finite.size:
            over_frac = float(np.mean(queue_age > finite.min()))
    report = ServeReport(
        slots=T,
        warmup=warmup,
        tasks_arrived=totals["arrived"],
        tasks_dispatched=totals["dispatched"],
        tasks_processed=totals["processed"],
        total_emissions=totals["emissions"],
        wall_s=wall_s,
        tasks_per_sec=float(slot_arrived[warmup:].sum())
        / max(t_end - t_measured, 1e-12),
        p50_us=p50, p95_us=p95, p99_us=p99, mean_us=mean,
        max_queue_age=int(queue_age.max()),
        latency_us=lat,
        backlog=backlog,
        queue_age=queue_age,
        missed_total=totals["missed"],
        shed_total=totals["shed"],
        age_p50=age_p50, age_p95=age_p95, age_p99=age_p99,
        age_over_deadline_frac=over_frac,
        final_state=state,
        slot_emissions=slot_emissions,
    )
    if exporter is not None:
        exporter.close(report)
    return report


def demo_spec(M: int, N: int, seed: int) -> NetworkSpec:
    """The synthetic M-type x N-cloud instance `main` serves."""
    rng = np.random.default_rng(seed)
    return NetworkSpec(
        pe=rng.uniform(1, 8, M).astype(np.float32),
        pc=rng.uniform(2, 100, (M, N)).astype(np.float32),
        Pe=1e4,
        Pc=rng.uniform(1e3, 1e5, N).astype(np.float32),
    )


def main(argv=None) -> ServeReport:
    """CLI: `python -m repro.serve.loop` -- the CI serving-smoke entry.
    Serves a synthetic workload, prints the latency/throughput summary
    and (with `--outdir`) leaves live-exported Prometheus + JSONL
    behind for parse validation. REPRO_SMOKE=1 shrinks the instance;
    even smoke pushes >= 10^4 synthetic tasks through admission."""
    from repro.compile_cache import enable_compile_cache
    from repro.core import (
        CarbonIntensityPolicy,
        UKRegionalTraceSource,
        UniformArrivals,
    )

    enable_compile_cache()
    smoke = os.environ.get("REPRO_SMOKE") == "1"
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=24 if smoke else 64)
    ap.add_argument("--types", type=int, default=16 if smoke else 64,
                    help="task types M")
    ap.add_argument("--clouds", type=int, default=4 if smoke else 8)
    ap.add_argument("--amax", type=int, default=100 if smoke else 300)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--flush-every", type=int, default=8)
    ap.add_argument("--outdir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--deadline", type=float, default=None,
                    help="serve deadline-aware: max extra waiting slots "
                         "per task before it expires (default: off)")
    ap.add_argument("--shed", action="store_true",
                    help="with --deadline: admission control sheds "
                         "arrivals projected capacity cannot clear")
    ap.add_argument("--headroom", type=float, default=0.9,
                    help="admission capacity factor for --shed")
    args = ap.parse_args(argv)

    deadlines = None
    policy = CarbonIntensityPolicy(V=0.05)
    if args.deadline is not None:
        from repro.deadlines import SlackThresholdPolicy, make_deadlines

        deadlines = make_deadlines(
            args.types, deadline=args.deadline,
            shed_on=1.0 if args.shed else 0.0, headroom=args.headroom,
        )
        policy = SlackThresholdPolicy(V=0.05)

    spec = demo_spec(args.types, args.clouds, args.seed)
    report = serve_loop(
        policy,
        spec,
        UKRegionalTraceSource(N=args.clouds),
        UniformArrivals(M=args.types, amax=args.amax),
        args.slots,
        jax.random.PRNGKey(args.seed),
        warmup=args.warmup,
        outdir=args.outdir,
        flush_every=args.flush_every,
        deadlines=deadlines,
    )
    print(f"served {report.slots} slots "
          f"(M={args.types}, N={args.clouds}, amax={args.amax})")
    print(f"tasks arrived {report.tasks_arrived:.0f}, "
          f"processed {report.tasks_processed:.0f}, "
          f"throughput {report.tasks_per_sec:,.0f} tasks/sec")
    print(f"decision latency p50 {report.p50_us:.0f} us, "
          f"p95 {report.p95_us:.0f} us, p99 {report.p99_us:.0f} us "
          f"(warmup={report.warmup} excluded)")
    print(f"max queue age {report.max_queue_age} slots, "
          f"emissions {report.total_emissions:.3g} gCO2-eq")
    if deadlines is not None:
        print(f"queue age p50/p95/p99 {report.age_p50:.0f}/"
              f"{report.age_p95:.0f}/{report.age_p99:.0f} slots vs "
              f"deadline {args.deadline:g} "
              f"(over-deadline {report.age_over_deadline_frac:.1%}); "
              f"missed {report.missed_total:.0f}, "
              f"shed {report.shed_total:.0f}")
    if report.tasks_arrived < 1e4:
        raise SystemExit(
            f"serving smoke must cover >= 10^4 tasks, got "
            f"{report.tasks_arrived:.0f}"
        )
    return report


if __name__ == "__main__":
    main()
