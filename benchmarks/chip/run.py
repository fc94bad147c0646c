#!/usr/bin/env python3
"""Chip benchmark of the carbon-aware scheduler: one cell, one run.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

Runs from the root of a checkout, in this one process, on JAX's first
device, which must be a TPU whose `device_kind` is in `peaks.json`;
there is no CPU fallback. `BENCHMARK.json` names the cell's
configuration (`configs/<config>.json`) and traffic mix
(`traffic/<mix>.json`); the mix names the entry path that drives the
window (`paths/<path>.py`), a cell may carry parameters of its own
(`cells/<cell>.json`), and every metric is read by its own reader
(`metrics/<metric>.py`, or that of the quantity it splits). A new cell,
mix or metric is a new file and a new entry, with no edit here.

With `--trace 0` the result carries the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics, read from a profiler trace of a short
sub-window after the measured one. Every run checks what the window
produced against the plain reference (`reference.py`) and prints each
number compared beside its limit, as the last lines of stderr and as the
last key (`checks`) of the result, which is the last line of stdout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]


class BenchError(SystemExit):
    """A run that cannot give a result: exits nonzero, prints none."""

    def __init__(self, msg):
        super().__init__(f"benchmark: {msg}")


def load_module(path: Path):
    """Imports one file of the benchmark by path (metric readers are
    named after their metric, which may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    try:
        with open(path) as fh:
            return json.load(fh)
    except FileNotFoundError:
        raise BenchError(f"{path} not found") from None


def cell_plan(bench: dict, workload: str, trace: bool) -> dict:
    """What one run of a cell needs, found by name: the workload entry,
    its configuration and traffic files, its path module file and the
    metric entries it reports (end-to-end ones without a trace,
    per-layer ones with)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = read_json(ROOT / configs[cell["config"]]["file"])
    mix = read_json(BENCH / "traffic" / f"{cell['traffic']}.json")

    def applies(m, reported):
        if "workloads" in m:
            return workload in m["workloads"]
        return reported is None or m["moves"] in reported

    e2e = [m for m in bench["end_to_end"] if applies(m, None)]
    if trace:
        reported = {m["name"] for m in e2e}
        metrics = [m for m in bench["per_layer"] if applies(m, reported)]
    else:
        metrics = e2e
    params = BENCH / "cells" / f"{workload}.json"
    return {"cell": cell, "config": cfg, "mix": mix,
            "params": read_json(params) if params.exists() else {},
            "path": BENCH / "paths" / f"{mix['path']}.py",
            "metrics": metrics}


def reader_path(name: str) -> Path:
    """A metric's reader: `metrics/<name>.py`, or else the reader of
    the quantity it splits (`device_idle_share.fleet` falls back to
    `metrics/device_idle_share.py`); the entry's `workloads` say where
    it is read."""
    own = BENCH / "metrics" / f"{name}.py"
    if own.exists() or "." not in name:
        return own
    return BENCH / "metrics" / f"{name.split('.')[0]}.py"


def read_metrics(entries, rec: dict, required: bool) -> dict:
    """Runs each metric's reader on the run's records. A reader that
    finds nothing returns None and the metric is left out; an
    end-to-end metric (`required`) that reads nothing is an error."""
    out = {}
    for m in entries:
        value = load_module(reader_path(m["name"])).read(rec)
        if value is None:
            if required:
                raise BenchError(f"metric {m['name']} read nothing")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def check_device(chips: int, peaks: dict):
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise BenchError(f"JAX's first device is {dev.platform!r} "
                         f"({dev.device_kind}), not a TPU; there is no "
                         f"CPU fallback")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
    if dev.device_kind not in peaks:
        raise BenchError(f"device kind {dev.device_kind!r} is not in "
                         f"peaks.json (have {sorted(peaks)})")
    return dev


class CompileLog:
    """Backend compile seconds (cache reads included) and persistent
    cache hits and misses, from JAX's monitoring events."""

    def __init__(self, monitoring):
        self.secs = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.secs, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}


def format_checks(checks: dict) -> list:
    return [f"check {name}: {c['value']!r} (limit {c['limit']!r}, "
            f"{'ok' if c['value'] <= c['limit'] else 'OUT'})"
            for name, c in checks.items()]


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    if args.seconds <= 0:
        ap.error("--seconds must be > 0")
    return args


def main(argv=None, device_check=check_device):
    args = parse_args(argv)
    bench = read_json(ROOT / "BENCHMARK.json")
    plan = cell_plan(bench, args.workload, bool(args.trace))
    if not (ROOT / "src" / "repro").is_dir():
        raise BenchError(f"{ROOT / 'src' / 'repro'} not found: run from a "
                         f"checkout of the repository")
    # The persistent compilation cache lives at a fixed path inside the
    # checkout, whatever the environment says, so two checkouts never
    # share one; the program's enable_compile_cache takes it from here.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))

    t_import = time.perf_counter()
    import jax

    t_jax = time.perf_counter()
    peaks = read_json(BENCH / "peaks.json")["devices"]
    dev = device_check(plan["cell"]["chips"], peaks)
    t_dev = time.perf_counter()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    log = CompileLog(jax.monitoring)
    path = load_module(plan["path"])
    rec = path.run(
        config=plan["config"], mix=plan["mix"], cell=plan["params"],
        seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), device=dev,
        compile_log=log, t_start=T_START,
    )
    rec["peaks"] = peaks.get(dev.device_kind)
    print(f"start: {t_import - T_START:.3f} s to read the cell, "
          f"{t_jax - t_import:.3f} s importing JAX, {t_dev - t_jax:.3f} s "
          f"reaching the {dev.device_kind}", flush=True)
    for line in rec["anatomy"]:
        print(line, flush=True)
    metrics = read_metrics(plan["metrics"], rec, required=not args.trace)
    if dev.platform != "tpu":
        # a rehearsal off the chip (its look for a chip replaced): no
        # number of it goes under a device metric's name
        metrics = {f"{dev.platform}.{k}": v for k, v in metrics.items()}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    result = {"correct": rec["correct"], "attempted": rec["attempted"],
              "failed": rec["failed"], "metrics": metrics,
              "device": device}
    if args.trace:
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
        result["breakdown"] = rec["trace"]["breakdown"]
    result["checks"] = rec["checks"]
    for line in format_checks(rec["checks"]):
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
