"""Served cells: the window drives `repro.serve.loop.serve_loop`, the
served path, as a closed loop with one slot in flight (slot t+1's
decision reads slot t's queues).

Set-up makes the deployment and the traffic from the seed. The measured
call serves `warmup + ceil(seconds * window_slots_per_s)` slots (plus
`trace_slots` traced ones with a trace) through the harness's own
recording clock; `window_slots_per_s` is the cell's own
(`cells/<cell>.json`), so a cell's window is the same work in every run
and on every commit, about `seconds` long at the rate the program had
when the cell was added. Each run starts from empty queues; the first
`warmup` slots compile or load the step, bring the backlog up to its
operating level, and count as set-up. The loop calls
its clock 2T+2 times (start, before and after each step, end); the run
fails on any other count, so a change to the loop's timing pattern
cannot move the yardstick silently.

Once the window has closed and the peak memory is read, the plain
reference replays every slot of the measured call and the run compares
the final queues (exact task counts), and the per-slot backlog and
emissions series.
"""
import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

import gen
import reference
import tracing

# Limits on the numbers compared, set in PERF.md from the readings of
# the program's sound runs and of the bfloat16 control.
LIMITS = {
    "queue_gap": 0.0,       # max |Q - Q_ref| over final Qe, Qc (tasks)
    "backlog_gap": 1e-3,    # max_t |backlog - ref| / max_t ref
    "emission_gap": 3e-3,   # max_t |emissions - ref| / max_t |ref|
}
REF_CHUNK = 128


class Clock:
    """The loop's injected clock: `perf_counter`, kept at every call.
    Call `mark_at` also runs `mark()`, and with a trace call i drives
    `tracer.at(i)`, each after the reading."""

    def __init__(self, tracer=None, mark_at=None, mark=None):
        self.calls = []
        self.tracer = tracer
        self.mark_at, self.mark = mark_at, mark

    def __call__(self):
        now = time.perf_counter()
        i = len(self.calls)
        if i == self.mark_at:
            self.mark()
        if self.tracer is not None:
            self.tracer.at(i)
        self.calls.append(now)
        return now


class SlotTracer:
    """Starts the profiler after the step of slot `first - 1`, labels
    each traced slot's step (`bench.step`: dispatch to the end of
    `block_until_ready`) and the host work after it (`bench.host`:
    scalar pulls, the age FIFO, totals), and stops at the loop's last
    clock call. Clock call 1 + 2t precedes slot t's step, 2 + 2t
    follows it."""

    def __init__(self, first, T, session):
        self.first, self.T, self.session = first, T, session
        self.span = None

    def _open(self, name):
        self._close()
        self.span = tracing.span(name)

    def _close(self):
        if self.span is not None:
            self.span.__exit__(None, None, None)
            self.span = None

    def at(self, i):
        if i == 2 * self.first:
            self.session.start()
        elif i == 2 * self.T + 1:
            self._close()
            self.session.stop()
        elif i > 2 * self.first:
            self._open("bench.step" if i % 2 else "bench.host")


def _inputs(config, mix, seed):
    from repro.core import CarbonIntensityPolicy

    M, N = int(config["M"]), int(config["N"])
    table = jnp.asarray(gen.carbon_table(N, seed, int(mix["carbon_slots"])))
    base = gen.arrival_key(seed)
    return {
        "spec": gen.network_spec(config),
        "carbon": functools.partial(gen.carbon_at, table),
        "arrivals": functools.partial(gen.arrivals_at, base, M, mix),
        "policy": CarbonIntensityPolicy(V=config["V"]),
        "key": gen.seed_key(seed, 3),
        "table": table,
        "base": base,
    }


def _reference(config, mix, inp, T, dtype):
    """The reference over slots 0 .. T-1, in chunks of REF_CHUNK slots
    through one compiled program, whatever T and the seed (its traffic
    data are arguments)."""
    M = int(config["M"])
    pe, pc, Pe, Pc = gen.spec_arrays(config)

    def chunk(pe, pc, Pe, Pc, V, Q, t0, t_end, table, base):
        return reference.run(
            pe, pc, Pe, Pc, V, functools.partial(gen.carbon_at, table),
            functools.partial(gen.arrivals_at, base, M, mix), REF_CHUNK,
            dtype=getattr(jnp, dtype), Q0=Q, t0=t0, t_end=t_end)

    fn = jax.jit(chunk)
    Q = (np.zeros(pe.shape, np.float32), np.zeros(pc.shape, np.float32))
    series = []
    for t0 in range(0, T, REF_CHUNK):
        Qe, Qc, part = fn(pe, pc, Pe, Pc, np.float32(config["V"]), Q,
                          np.int32(t0), np.int32(T), inp["table"],
                          inp["base"])
        Q = (Qe, Qc)
        series.append(part)
    f64 = lambda x: np.asarray(x, np.float64)  # noqa: E731
    em, backlog = (np.concatenate([f64(p[i]) for p in series])[:T]
                   for i in (0, 1))
    return {"Qe": f64(Q[0]), "Qc": f64(Q[1]), "emissions": em,
            "backlog": backlog}


def compare(out, ref):
    """The numbers compared, each beside its limit, and the count of
    slots whose answer lies outside a limit (the last slot's includes
    the final queues)."""
    def rel(a, b):
        return np.abs(a - b) / max(np.abs(b).max(), 1e-30)

    bl = rel(out["backlog"], ref["backlog"])
    em = rel(out["emissions"], ref["emissions"])
    values = {
        "queue_gap": max(float(np.abs(out["Qe"] - ref["Qe"]).max()),
                         float(np.abs(out["Qc"] - ref["Qc"]).max())),
        "backlog_gap": float(bl.max()),
        "emission_gap": float(em.max()),
    }
    checks = {k: {"value": v, "limit": LIMITS[k]} for k, v in values.items()}
    bad = (bl > LIMITS["backlog_gap"]) | (em > LIMITS["emission_gap"])
    bad[-1] |= values["queue_gap"] > LIMITS["queue_gap"]
    return checks, int(bad.sum())


def run(config, mix, cell, seed, seconds, trace, device, compile_log,
        t_start):
    from repro.serve import serve_loop

    t0 = time.perf_counter()
    inp = _inputs(config, mix, seed)
    w = int(mix["warmup_slots"])
    args = (inp["policy"], inp["spec"], inp["carbon"], inp["arrivals"])
    log0 = compile_log.snapshot()

    n = max(1, math.ceil(seconds * float(cell["window_slots_per_s"])))
    K = int(mix["trace_slots"]) if trace else 0
    T = w + n + K
    session = tracing.Session() if trace else None
    at_start = {}
    clock = Clock(SlotTracer(T - K, T, session) if trace else None,
                  mark_at=1 + 2 * w,
                  mark=lambda: at_start.update(compile_log.snapshot()))
    t1 = time.perf_counter()
    rep = serve_loop(*args, T, inp["key"], warmup=w, clock=clock)
    if len(clock.calls) != 2 * T + 2:
        raise RuntimeError(
            f"serve_loop called its clock {len(clock.calls)} times for "
            f"T={T} slots, not 2T+2: its timing pattern changed")
    c = np.asarray(clock.calls)
    window_start = c[1 + 2 * w]
    log1 = compile_log.snapshot()
    memory_peak = (device.memory_stats() or {}).get("peak_bytes_in_use", 0)

    # slots [w, T-K) are the window; without a trace the host share is
    # read over all of them, with one over those before the slot whose
    # host gap starts the profiler
    lat = rep.latency_us
    tenth = max(1, n // 10)
    drift = [float(np.median(x[i:i + tenth]))
             for x in (lat, rep.backlog) for i in (w, T - K - tenth)]
    h_end = T if not trace else T - K - 1
    out = {"Qe": np.asarray(rep.final_state.Qe, np.float64),
           "Qc": np.asarray(rep.final_state.Qc, np.float64),
           "emissions": np.asarray(rep.slot_emissions, np.float64),
           "backlog": np.asarray(rep.backlog, np.float64)}
    del rep

    t3 = time.perf_counter()
    checks, failed = compare(out, _reference(config, mix, inp, T, "float32"))
    t_ref = time.perf_counter() - t3

    rec = {
        "kind": "serve",
        "M": int(config["M"]), "N": int(config["N"]),
        "setup_s": window_start - t_start,
        "compile_s": log1["compile_s"],
        "latency_us": lat[w:T - K],
        "window_s": c[2 * T + 1] - window_start if not trace else None,
        "window_slots": T - K - w,
        "host_slots": h_end - w,
        "host_window_s": c[1 + 2 * h_end] - window_start,
        "host_latency_us": lat[w:h_end],
        "memory_peak_bytes": int(memory_peak),
        "correct": all(v["value"] <= v["limit"] for v in checks.values()),
        "attempted": T - w,
        "failed": failed,
        "checks": checks,
        "trace": None,
    }
    if trace:
        from repro.core.queueing import init_state
        from repro.serve import make_serve_step

        step = make_serve_step(*args, inp["key"])
        hlo = step.lower(init_state(rec["M"], rec["N"]),
                         jnp.int32(0)).compile().as_text()
        rec["trace"] = session.reduce(hlo_texts=[hlo], slots=K,
                                      lane_slots=K)
    warm = {k: at_start[k] - log0[k] for k in log0}
    rec["anatomy"] = [
        f"setup: {rec['setup_s']:.3f} s from process start to the window; "
        f"{t0 - t_start:.3f} s to JAX and the device, {t1 - t0:.3f} s "
        f"making the inputs, {window_start - t1:.3f} s for the {w} warm-up "
        f"slots (compile or cache load {warm['compile_s']:.3f} s over "
        f"{warm['compiles']} programs, {warm['cache_hits']} cache hits, "
        f"{warm['cache_misses']} misses)",
        f"measured call T={T}: {n} window slots ({seconds} s at the "
        f"cell's {cell['window_slots_per_s']} slots/s), {K} traced; "
        f"window {c[1 + 2 * (T - K)] - window_start:.3f} s; compiles "
        f"inside the window {log1['compiles'] - at_start['compiles']}",
        f"window's first and last tenth: median latency {drift[0]:.1f} "
        f"and {drift[1]:.1f} us, median backlog {drift[2]:.0f} and "
        f"{drift[3]:.0f} tasks",
        f"reference replay of {T} slots: {t_ref:.3f} s",
    ]
    return rec


def control(config, mix, seed, T):
    """The control's readings: the reference in bfloat16 put in the
    program's place, compared as a run compares the program."""
    inp = _inputs(config, mix, seed)
    ref = _reference(config, mix, inp, T, "float32")
    low = _reference(config, mix, inp, T, "bfloat16")
    return compare(low, ref)[0]
