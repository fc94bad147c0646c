"""Benchmark harness: one function per paper table/figure + roofline.
Prints ``name,us_per_call,derived`` CSV and writes artifacts/bench/.

``--only SUBSTR`` (repeatable) selects benches whose function name
contains SUBSTR; a filtered run merges its rows into the existing
results.json instead of clobbering the full set. ``--smoke`` shrinks
bench instances to CI size (every code path compiles and runs; the
numbers are not representative) and prefixes row names with ``smoke/``
so a smoke run can never clobber committed full-size results.
``--compare`` diffs every fresh row's us_per_call against the committed
results.json BEFORE merging and exits nonzero when any row regresses by
more than ``--compare-tol`` (default 25%); rows faster than
``--compare-floor`` microseconds in the baseline are skipped as timer
noise. CI's bench-smoke job runs ``--smoke --compare`` against the
committed ``smoke/*`` baseline rows.

Benches that also run their workload with the telemetry taps on
(bench_fault_robustness, bench_telemetry_overhead) deposit a
``repro.telemetry.manifest`` dict per row in paper_benches.MANIFESTS;
it is stamped onto the matching results.json row under ``telemetry``.
Benches deposit further columns (serve latency percentiles, XLA
cost_analysis numbers) in paper_benches.EXTRAS, merged the same way.
Both are informational provenance: ``--compare`` gates us_per_call
ONLY, so a manifest-only diff (alert counts moving, peak backlog
shifting) never fails the gate.

Every invocation also appends ONE entry (git sha + dirty flag, env,
this run's fresh rows) to the append-only perf-trend ledger
``artifacts/bench/history.jsonl`` -- see benchmarks/trend.py. The
append happens even when ``--compare`` fails: the ledger records what
WAS measured; only the results.json baseline is protected from
regressed numbers. ``--trend`` renders the newest entry's per-row
deltas against the prior ledger entries after the run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ART = Path(__file__).resolve().parents[1] / "artifacts" / "bench"


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import paper_benches
    from benchmarks.paper_benches import ALL_BENCHES

    ap = argparse.ArgumentParser()
    ap.add_argument("--only", action="append", default=[])
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--compare", action="store_true",
                    help="fail on >tol us_per_call regression vs the "
                         "committed results.json")
    ap.add_argument("--compare-tol", type=float, default=0.25)
    ap.add_argument("--compare-floor", type=float, default=100.0,
                    help="skip baseline rows faster than this many "
                         "microseconds (timer noise)")
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed for bench instances; stamped into "
                         "every results.json row so any committed "
                         "number can be re-derived exactly")
    ap.add_argument("--trend", action="store_true",
                    help="after the run, render this entry's per-row "
                         "deltas against the perf-trend ledger "
                         "(artifacts/bench/history.jsonl)")
    ap.add_argument("--trend-last", type=int, default=5,
                    help="how many prior ledger entries --trend diffs "
                         "against")
    args = ap.parse_args()
    paper_benches.SMOKE = args.smoke
    paper_benches.SEED = args.seed
    benches = [
        b for b in ALL_BENCHES
        if not args.only or any(s in b.__name__ for s in args.only)
    ]

    # provenance stamped on every row so the perf trajectory in
    # results.json stays comparable across PRs / machines; git sha +
    # dirty flag tie each row to the code that produced it
    import jax

    from benchmarks import trend

    env = {
        "jax_version": jax.__version__,
        "platform": jax.default_backend(),
        "seed": args.seed,
        **trend.git_provenance(),
    }

    ART.mkdir(parents=True, exist_ok=True)
    print("name,us_per_call,derived")
    all_rows = []
    for bench in benches:
        t0 = time.perf_counter()
        rows = bench()
        wall_s = time.perf_counter() - t0
        if args.smoke:
            rows = [(f"smoke/{n}", u, d) for n, u, d in rows]
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived:.4f}")
        # bench_wall_s = total wall time of the bench FUNCTION that
        # produced the row (shared by its rows) -- compare like-named
        # benches across PRs, not rows within one bench. Telemetry
        # manifests are keyed by the unprefixed name (deposited before
        # the smoke/ prefix lands).
        for n, u, d in rows:
            bare = n[len("smoke/"):] if n.startswith("smoke/") else n
            row = {"name": n, "us_per_call": float(u),
                   "derived": float(d),
                   "bench_wall_s": round(wall_s, 3), **env}
            if bare in paper_benches.MANIFESTS:
                row["telemetry"] = paper_benches.MANIFESTS[bare]
            for k, v in paper_benches.EXTRAS.get(bare, {}).items():
                row.setdefault(k, v)
            all_rows.append(row)

    # roofline rows come from dry-run artifacts when present
    try:
        from benchmarks.roofline import bench_roofline

        t0 = time.perf_counter()
        roof = bench_roofline()
        wall_s = time.perf_counter() - t0
        for name, us, derived in roof:
            print(f"{name},{us:.1f},{derived:.4f}")
            all_rows.append(
                {"name": name, "us_per_call": us, "derived": derived,
                 "bench_wall_s": round(wall_s, 3), **env}
            )
    except Exception as e:  # dry-run not executed yet
        print(f"# roofline skipped: {e}", file=sys.stderr)

    out = ART / "results.json"
    committed = json.loads(out.read_text()) if out.exists() else []

    # ledger first, unconditionally: history.jsonl records what was
    # measured, including runs --compare is about to reject
    trend.append_history(all_rows, env)

    # --compare: diff fresh rows against the committed baseline BEFORE
    # merging, so the gate always sees the pre-run numbers.
    regressions = []
    if args.compare:
        base = {r["name"]: r["us_per_call"] for r in committed}
        for r in all_rows:
            old = base.get(r["name"])
            if old is None or old < args.compare_floor:
                continue
            if r["us_per_call"] > old * (1.0 + args.compare_tol):
                regressions.append((r["name"], old, r["us_per_call"]))
        for name, old, new in regressions:
            print(
                f"# REGRESSION {name}: {old:.1f} -> {new:.1f} us "
                f"(+{100.0 * (new / old - 1):.0f}% > "
                f"{100.0 * args.compare_tol:.0f}% tolerance)",
                file=sys.stderr,
            )

    # smoke rows are smoke/-prefixed (disjoint names), so a smoke run
    # must also merge -- never clobber committed full-size rows.
    if (args.only or args.smoke) and committed:
        kept = [
            r for r in committed
            if r["name"] not in {x["name"] for x in all_rows}
        ]
        all_rows = kept + all_rows
    if args.trend:
        print(trend.render_trend(trend.load_history(),
                                 last=args.trend_last, only=args.only))
    if regressions:
        # Leave results.json untouched: writing the regressed numbers
        # would install them as the next run's baseline and launder the
        # regression away on re-run.
        print(
            f"# results.json NOT updated ({len(regressions)} regression"
            f"{'s' if len(regressions) != 1 else ''})", file=sys.stderr,
        )
        sys.exit(1)
    out.write_text(json.dumps(all_rows, indent=2))


if __name__ == "__main__":
    main()
