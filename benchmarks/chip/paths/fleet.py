"""Fleet cells: the window drives `repro.core.simulator.simulate_fleet`,
the batch what-if path: one jitted call runs every scenario lane of the
mix for `T` slots.

Set-up makes the lanes from the seed, compiles the one program and runs
`warmup_calls` calls; the last one estimates the time a call takes. The
window then runs whole calls back to back, with keys folded from the
seed and the call index, keeping about `AHEAD_S` seconds of calls
dispatched ahead of the one it waits for, so that the chip stays fed
while the host stands still. Once `seconds` have passed it dispatches
nothing more, waits for every call it sent and closes on the clock
after that wait: every call sent counts, over all of that time. With a
trace, `trace_calls` more calls run under the profiler.

Two calls of the window, drawn from the seed among those the estimate
says the window will hold (the last one where the window held fewer),
keep their results. Once the window has closed and the peak memory is
read, the plain reference replays every lane of those calls and the run
compares the final queues (exact task counts), the per-slot dispatched
and processed counts (exact), and the per-slot emissions.

The reference draws each lane's arrivals as the program's fleet does
(lane f takes `split(key, F)[f]`, arrivals the second of its three
splits, a_m(t) = floor(U(fold_in(k, t)) * (amax_m + 1))): the fleet
takes no arrival source from outside, so that draw is the program's
definition of the mix and the reference has to repeat it.
"""
import math
import time
from collections import deque

import numpy as np

import gen
import reference
import tracing

LIMITS = {
    "queue_gap": 0.0,      # max |Q - Q_ref| over every lane's final Qe, Qc
    "count_gap": 0.0,      # max |dispatched or processed - ref| per slot
    "emission_gap": 3e-3,  # max |emissions - ref| / max |ref|
}
SAMPLED_CALLS = 2
AHEAD_S = 4.0  # seconds of calls kept in flight ahead of the one waited for
RESULT_FIELDS = ("Qe", "Qc", "emissions", "dispatched", "processed")


def _inputs(config, mix, seed):
    from repro.core import CarbonIntensityPolicy

    lanes = gen.fleet_lanes(config, mix, seed)
    return lanes, gen.fleet_scenario(lanes), CarbonIntensityPolicy(
        V=config["V"])


def _program(policy, mix):
    import jax

    from repro.core import simulate_fleet

    T, record = int(mix["T"]), mix["record"]
    return jax.jit(lambda fleet, key: simulate_fleet(
        policy, fleet, T, key, record=record))


def _host(res) -> dict:
    out = {k: np.asarray(getattr(res, k), np.float64) for k in RESULT_FIELDS}
    out["Qe"], out["Qc"] = out["Qe"][:, -1], out["Qc"][:, -1]
    return out


def _reference(config, mix, lanes, key, dtype):
    import jax
    import jax.numpy as jnp

    T = int(mix["T"])
    V = np.float32(config["V"])
    F, M = lanes["amax"].shape

    def lane(pe, pc, Pe, Pc, table, amax, k):
        k_arrive = jax.random.split(k, 3)[1]
        rows = table.shape[0]

        def carbon(t):
            return table[t % rows, 0], table[t % rows, 1:]

        def arrivals(t):
            u = jax.random.uniform(jax.random.fold_in(k_arrive, t), (M,),
                                   jnp.float32)
            return jnp.floor(u * (amax + 1.0))

        return reference.run(pe, pc, Pe, Pc, V, carbon, arrivals, T,
                             dtype=getattr(jnp, dtype))

    Qe, Qc, (em, _, disp, proc) = jax.jit(jax.vmap(lane))(
        lanes["pe"], lanes["pc"], lanes["Pe"], lanes["Pc"],
        lanes["carbon"], lanes["amax"], jax.random.split(key, F))
    f64 = lambda x: np.asarray(x, np.float64)  # noqa: E731
    return {"Qe": f64(Qe), "Qc": f64(Qc), "emissions": f64(em),
            "dispatched": f64(disp), "processed": f64(proc)}


def compare(out, ref):
    """The numbers compared, each beside its limit, and the count of
    lanes outside a limit."""
    q = np.maximum(np.abs(out["Qe"] - ref["Qe"]).max(axis=1),
                   np.abs(out["Qc"] - ref["Qc"]).max(axis=(1, 2)))
    n = np.maximum(np.abs(out["dispatched"] - ref["dispatched"]),
                   np.abs(out["processed"] - ref["processed"])).max(axis=1)
    em = (np.abs(out["emissions"] - ref["emissions"]).max(axis=1)
          / max(np.abs(ref["emissions"]).max(), 1e-30))
    values = {"queue_gap": float(q.max()), "count_gap": float(n.max()),
              "emission_gap": float(em.max())}
    bad = ((q > LIMITS["queue_gap"]) | (n > LIMITS["count_gap"])
           | (em > LIMITS["emission_gap"]))
    return {k: {"value": v, "limit": LIMITS[k]}
            for k, v in values.items()}, int(bad.sum())


def _merge(parts):
    """Worst reading over several compared calls."""
    checks = {}
    for part, _ in parts:
        for k, c in part.items():
            if k not in checks or c["value"] > checks[k]["value"]:
                checks[k] = c
    return checks, sum(f for _, f in parts)


def run(config, mix, cell, seed, seconds, trace, device, compile_log,
        t_start):
    import jax

    t0 = time.perf_counter()
    lanes, fleet, policy = _inputs(config, mix, seed)
    fn = _program(policy, mix)
    base = gen.seed_key(seed, 4)
    t1 = time.perf_counter()
    w = int(mix["warmup_calls"])
    for i in range(w):
        tc = time.perf_counter()
        jax.block_until_ready(fn(fleet, jax.random.fold_in(base, 2**31 + i)))
    est = time.perf_counter() - tc
    log0 = compile_log.snapshot()

    n_est = max(1, int(seconds / est))
    rng = gen.seed_rng(seed, 5)
    sample = set(int(i) for i in rng.choice(
        n_est, size=min(SAMPLED_CALLS, n_est), replace=False))
    ahead = max(1, min(math.ceil(AHEAD_S / est), n_est))
    kept = {}
    inflight = deque()
    start = end = time.perf_counter()
    call_s = []
    sent = 0
    closing = False
    while True:
        while not closing and len(inflight) < ahead:
            key = jax.random.fold_in(base, sent)
            inflight.append((sent, key, fn(fleet, key)))
            sent += 1
        i, key, res = inflight.popleft()
        jax.block_until_ready(res)
        if i in sample:
            kept[i] = (key, res)
        now = time.perf_counter()
        call_s.append(now - end)
        end = now
        closing = closing or end - start >= seconds
        if closing and not inflight:
            break
    calls = len(call_s)
    if len(kept) < len(sample):
        kept[calls - 1] = (key, res)
    log1 = compile_log.snapshot()

    K = int(mix["trace_calls"]) if trace else 0
    session = tracing.Session() if trace else None
    if trace:
        session.start()
        for i in range(K):
            sp = tracing.span("bench.step")
            jax.block_until_ready(fn(fleet, jax.random.fold_in(base, 2**31
                                                                + w + i)))
            sp.__exit__(None, None, None)
        session.stop()
    memory_peak = (device.memory_stats() or {}).get("peak_bytes_in_use", 0)
    outs = {i: (key, _host(r)) for i, (key, r) in kept.items()}
    del kept, res

    t3 = time.perf_counter()
    checks, failed = _merge(
        [compare(out, _reference(config, mix, lanes, key, "float32"))
         for key, out in outs.values()])
    t_ref = time.perf_counter() - t3

    F, T = fleet.F, int(mix["T"])
    rec = {
        "kind": "fleet",
        "F": F, "T": T, "M": int(config["M"]), "N": int(config["N"]),
        "setup_s": start - t_start,
        "compile_s": log1["compile_s"],
        "calls": calls,
        "call_s": call_s,
        "window_s": end - start,
        "memory_peak_bytes": int(memory_peak),
        "correct": all(v["value"] <= v["limit"] for v in checks.values()),
        "attempted": calls * F,
        "failed": failed,
        "checks": checks,
        "trace": None,
    }
    if trace:
        hlo = fn.lower(fleet, base).compile().as_text()
        rec["trace"] = session.reduce(hlo_texts=[hlo], slots=K * T,
                                      lane_slots=K * F * T)
    rec["anatomy"] = [
        f"setup: {rec['setup_s']:.3f} s from process start to the window; "
        f"{t0 - t_start:.3f} s to JAX and the device, {t1 - t0:.3f} s "
        f"making {F} lanes, {start - t1:.3f} s for {w} warm-up calls "
        f"(compile or cache load {log0['compile_s']:.3f} s over "
        f"{log0['compiles']} programs, {log0['cache_hits']} cache hits, "
        f"{log0['cache_misses']} misses)",
        f"call estimate {est:.6f} s, {ahead} calls in flight; window "
        f"{calls} calls of {F} lanes x {T} slots, completions "
        f"{min(call_s):.6f} s to {max(call_s):.6f} s apart "
        f"(median {float(np.median(call_s)):.6f} s, first "
        f"{call_s[0]:.6f} s, {sum(c > 1.05 * est for c in call_s)} over "
        f"1.05 x the estimate); compiles inside the window "
        f"{log1['compiles'] - log0['compiles']}",
        f"reference replay of calls {sorted(outs)}: {t_ref:.3f} s",
    ]
    return rec


def control(config, mix, seed, calls=SAMPLED_CALLS):
    """The control's readings: the reference in bfloat16 put in the
    program's place, compared as a run compares the program."""
    import jax

    lanes, _, _ = _inputs(config, mix, seed)
    base = gen.seed_key(seed, 4)
    parts = []
    for i in range(calls):
        key = jax.random.fold_in(base, i)
        parts.append(compare(
            _reference(config, mix, lanes, key, "bfloat16"),
            _reference(config, mix, lanes, key, "float32")))
    return _merge(parts)[0]
