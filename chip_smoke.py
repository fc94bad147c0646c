#!/usr/bin/env python3
"""Smoke run of the scheduler's served and batch paths on one TPU chip.

    python chip_smoke.py

Runs in this one process and starts no other. Phases, in order; every
gate of a phase is checked and printed, a failed phase is reported with
its traceback while the later phases still run, and then the script
exits nonzero without printing the result line:

0. device check: JAX's first device must be a TPU. There is no CPU
   fallback, so `JAX_PLATFORMS=cpu` makes the script fail at once.
1. served path at deployment scale: `serve_loop` (what
   `python -m repro.serve` runs) serves M=4096 task types x N=256 clouds
   (UniformArrivals(amax=300), about 6e5 tasks per slot) for 32 slots,
   once per score backend, and `simulate(record="summary")` runs the
   same seed in batch. Gate: the final Qe [M] and Qc [M, N] are
   elementwise equal across the two backends and between served and
   batch, and the pallas step's compiled program holds the Mosaic
   kernel (`tpu_custom_call`).
2. batch fleet: `simulate_fleet` on 256 diurnal lanes for T=192 slots,
   once per backend (the pallas kernel runs under vmap). Gate: the final
   queues are equal lane by lane and the pallas program holds
   `tpu_custom_call`.
3. WAN path: `simulate_fleet` with NetworkAwareDPPPolicy on 64
   congested-uplink lanes for T=192 slots, once per backend. Gate: the
   final Qe/Qc/Qt are equal lane by lane and the pallas program holds
   `tpu_custom_call`.

Per-slot scalar series (emissions, backlog) are compared exactly too;
a difference is printed with its size but does not fail the run: the
gates are the final queues.
Latency and tasks/sec lines are smoke readings, not benchmark numbers.
Compile seconds and persistent-cache hits are printed per phase; the
cache lives where `repro.compile_cache` says.

The last line of stdout is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}`.
"""
from __future__ import annotations

import importlib.metadata
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

# phase 1: the served deployment
SERVE_M, SERVE_N, SERVE_AMAX, SERVE_SLOTS, SERVE_WARMUP = 4096, 256, 300, 32, 2
# phase 2: the fleet_summary/F256 bench instance
FLEET_F, FLEET_T, FLEET_TC = 256, 192, 96
# phase 3: the congested-uplink WAN fleet
WAN_F, WAN_T, WAN_TC = 64, 192, 96
SEED = 0

KERNEL_MARK = "tpu_custom_call"


def _import_repo():
    if not (SRC / "repro").is_dir():
        raise SystemExit(
            f"chip_smoke: {SRC / 'repro'} not found; run the script from "
            f"a checkout of the repository"
        )
    sys.path.insert(0, str(SRC))


class CompileLog:
    """Backend compile seconds (cache reads included) and persistent
    cache hits/writes, from JAX's monitoring events."""

    def __init__(self, monitoring):
        self.secs = 0.0
        self.hits = 0
        self.writes = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    def since(self, mark):
        s, h, w = mark
        return (f"compile {self.secs - s:.3f} s, cache hits "
                f"{self.hits - h}, cache writes {self.writes - w}")

    def mark(self):
        return (self.secs, self.hits, self.writes)


def check_device():
    """Phase 0. Returns jax.devices()[0]; exits nonzero off the TPU."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX's first device is {dev.platform!r} "
            f"({dev.device_kind}), not a TPU; this script has no CPU "
            f"fallback"
        )
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    print(f"device: {dev.device_kind}, count {len(jax.devices())}, "
          f"jax {jax.__version__}, libtpu {libtpu}", flush=True)
    return dev


class Gates:
    """One phase's gates. Every gate is checked and printed; `close`
    raises if any failed, naming each failure with its size."""

    def __init__(self):
        self.failed = []

    def _report(self, name, ok, detail):
        print(f"  gate {name}: {'ok' if ok else 'FAILED'} ({detail})",
              flush=True)
        if not ok:
            self.failed.append(f"{name} ({detail})")

    def equal(self, name, a, b):
        """Elementwise equality of two arrays."""
        import numpy as np

        a, b = np.asarray(a), np.asarray(b)
        if a.shape != b.shape:
            self._report(name, False, f"shapes {a.shape} vs {b.shape}")
        elif np.array_equal(a, b):
            self._report(name, True, f"{a.size} entries equal")
        else:
            d = np.abs(a.astype(np.float64) - b.astype(np.float64))
            self._report(name, False,
                         f"{int((d > 0).sum())} of {a.size} entries "
                         f"differ, max |diff| {d.max()!r}")

    def kernel(self, name, compiled_text):
        """The compiled program holds the Mosaic kernel."""
        ok = KERNEL_MARK in compiled_text
        self._report(name, ok,
                     f"{KERNEL_MARK} {'present' if ok else 'absent'}")

    def close(self):
        if self.failed:
            raise AssertionError("; ".join(self.failed))


def compare_series(name, a, b):
    """Report-only exact comparison of a per-slot series ([T] or, for a
    fleet, [F, T]); prints the count of differing entries and the worst
    one with its index, and never raises."""
    import numpy as np

    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if a.shape != b.shape:
        print(f"  series {name}: DIFFERS in shape, {a.shape} vs {b.shape}",
              flush=True)
        return
    d = np.abs(a - b)
    if not d.any():
        print(f"  series {name}: equal ({a.size} entries)", flush=True)
        return
    worst = np.unravel_index(int(d.argmax()), d.shape)
    print(f"  series {name}: DIFFERS at {int((d > 0).sum())} of {a.size} "
          f"entries, max |diff| {float(d[worst])!r} at index "
          f"{tuple(int(i) for i in worst)} (values {float(a[worst])!r} vs "
          f"{float(b[worst])!r})", flush=True)


def backends(policy_cls, **kw):
    """The two score backends of one policy; the pallas one is left on
    auto dispatch (score_interpret=None), which must pick the compiled
    kernel on the TPU."""
    return {
        "reference": policy_cls(score_backend="reference", **kw),
        "pallas": policy_cls(score_backend="pallas",
                             score_interpret=None, **kw),
    }


def served_path(M=SERVE_M, N=SERVE_N, amax=SERVE_AMAX, slots=SERVE_SLOTS,
                warmup=SERVE_WARMUP, seed=SEED):
    """Phase 1: serve_loop on both backends + simulate, gated on the
    final queue state."""
    import jax
    import jax.numpy as jnp

    from repro.core import (
        CarbonIntensityPolicy,
        UKRegionalTraceSource,
        UniformArrivals,
        simulate,
    )
    from repro.core.queueing import init_state
    from repro.serve import make_serve_step, serve_loop
    from repro.serve.loop import demo_spec

    spec = demo_spec(M, N, seed)
    carbon = UKRegionalTraceSource(N=N)
    arrivals = UniformArrivals(M=M, amax=amax)
    key = jax.random.PRNGKey(seed)
    pols = backends(CarbonIntensityPolicy, V=0.05)

    gates = Gates()
    step = make_serve_step(pols["pallas"], spec, carbon, arrivals, key)
    gates.kernel("pallas serve step",
                 step.lower(init_state(M, N), jnp.int32(0)).compile()
                 .as_text())

    reports = {}
    for name, pol in pols.items():
        rep = serve_loop(pol, spec, carbon, arrivals, slots, key,
                         warmup=warmup)
        reports[name] = rep
        print(f"  smoke reading, not a benchmark number: served {name} "
              f"M={M} N={N}: p50 {rep.p50_us:.1f} us, p99 "
              f"{rep.p99_us:.1f} us over {slots - rep.warmup} slots, "
              f"{rep.tasks_per_sec:.1f} tasks/s, "
              f"{rep.tasks_arrived:.0f} tasks arrived", flush=True)
    batch = simulate(pols["reference"], spec, carbon, arrivals, slots, key,
                     record="summary")
    jax.block_until_ready(batch.Qc)

    ref, pal = reports["reference"], reports["pallas"]
    gates.equal("served Qe reference==pallas",
                ref.final_state.Qe, pal.final_state.Qe)
    gates.equal("served Qc reference==pallas",
                ref.final_state.Qc, pal.final_state.Qc)
    gates.equal("Qe served==simulate", ref.final_state.Qe, batch.Qe[-1])
    gates.equal("Qc served==simulate", ref.final_state.Qc, batch.Qc[-1])
    compare_series("served emissions reference vs pallas",
                   ref.slot_emissions, pal.slot_emissions)
    compare_series("served backlog reference vs pallas",
                   ref.backlog, pal.backlog)
    compare_series("emissions served vs simulate",
                   ref.slot_emissions, batch.emissions)
    gates.close()


def _run_fleet(gates, pols, fleet, T, key):
    """Compiles and runs simulate_fleet once per backend, gating on the
    kernel in the pallas program; returns {backend: result}."""
    import jax

    from repro.core import simulate_fleet

    results = {}
    for name, pol in pols.items():
        fn = jax.jit(lambda k, pol=pol: simulate_fleet(
            pol, fleet, T, k, record="summary"))
        compiled = fn.lower(key).compile()
        if name == "pallas":
            gates.kernel("pallas fleet program", compiled.as_text())
        results[name] = jax.block_until_ready(compiled(key))
    return results


def batch_fleet(F=FLEET_F, T=FLEET_T, Tc=FLEET_TC, seed=SEED):
    """Phase 2: simulate_fleet on the diurnal F-lane fleet."""
    import jax

    from repro.configs.fleet_scenarios import build_fleet
    from repro.core import CarbonIntensityPolicy

    fleet = build_fleet(["diurnal"], per_kind=F, Tc=Tc, seed=seed)
    gates = Gates()
    res = _run_fleet(gates, backends(CarbonIntensityPolicy, V=0.05), fleet,
                     T, jax.random.PRNGKey(seed))
    ref, pal = res["reference"], res["pallas"]
    gates.equal("fleet Qe reference==pallas", ref.Qe[:, -1], pal.Qe[:, -1])
    gates.equal("fleet Qc reference==pallas", ref.Qc[:, -1], pal.Qc[:, -1])
    compare_series("fleet emissions reference vs pallas",
                   ref.emissions, pal.emissions)
    gates.close()


def wan_path(F=WAN_F, T=WAN_T, Tc=WAN_TC, seed=SEED):
    """Phase 3: simulate_fleet through the WAN transfer layer."""
    import jax

    from repro.configs.fleet_scenarios import build_network_fleet
    from repro.network import NetworkAwareDPPPolicy

    fleet = build_network_fleet(["congested-uplink"], per_kind=F, Tc=Tc,
                                seed=seed)
    gates = Gates()
    res = _run_fleet(gates, backends(NetworkAwareDPPPolicy, V=0.1), fleet,
                     T, jax.random.PRNGKey(seed))
    ref, pal = res["reference"], res["pallas"]
    gates.equal("wan Qe reference==pallas", ref.Qe[:, -1], pal.Qe[:, -1])
    gates.equal("wan Qc reference==pallas", ref.Qc[:, -1], pal.Qc[:, -1])
    gates.equal("wan Qt reference==pallas", ref.Qt[:, -1], pal.Qt[:, -1])
    compare_series("wan emissions reference vs pallas",
                   ref.emissions, pal.emissions)
    gates.close()


def main():
    _import_repo()
    import jax

    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    dev = check_device()
    print(f"compile cache: {cache_dir}", flush=True)
    log = CompileLog(jax.monitoring)
    phases = (
        ("1 served path", served_path),
        ("2 batch fleet", batch_fleet),
        ("3 WAN path", wan_path),
    )
    t_all = time.perf_counter()
    failed = []
    for name, phase in phases:
        print(f"phase {name}", flush=True)
        mark, t0 = log.mark(), time.perf_counter()
        # a failed phase is reported and the later ones still run, so
        # one chip run shows every failure; the exit is nonzero below
        try:
            phase()
        except Exception:
            traceback.print_exc()
            failed.append(name)
            verdict = "FAILED"
        else:
            verdict = "passed"
        print(f"phase {name}: {verdict} in {time.perf_counter() - t0:.3f}"
              f" s ({log.since(mark)})", flush=True)
    print(f"all phases done in {time.perf_counter() - t_all:.3f} s "
          f"({log.since((0.0, 0, 0))})", flush=True)
    if failed:
        raise SystemExit(f"chip_smoke: failed phase(s): {', '.join(failed)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
