"""Perf-trend ledger: an append-only history of bench rows.

Every `benchmarks/run.py` invocation appends ONE line to
``artifacts/bench/history.jsonl`` -- timestamp, git sha (+ dirty flag),
jax/platform/seed provenance, and this run's fresh ``(name,
us_per_call, derived)`` rows. Unlike ``results.json`` (a snapshot that
merge-updates in place), the ledger only ever grows, so the perf
trajectory across PRs stays inspectable after the snapshot moves on;
CI uploads it as a build artifact next to results.json.

``python -m benchmarks.trend`` (or ``run.py --trend``) renders the
per-row deltas of the newest entry against the previous K entries:

    row                          us now     vs prev    vs window     n
    policy_fast/M2048xN256       1234.5       -2.1%        +0.4%     5

Also home to ``cost_columns``: the small normalizer that turns an XLA
``compiled.cost_analysis()`` dict plus a measured lower+compile wall
time (persistent compilation cache off, so it is a compile) into
the flat ``{"compile_ms", "flops", "bytes_accessed"}`` dict benches
stamp onto their rows via ``paper_benches.EXTRAS``.
"""
from __future__ import annotations

import argparse
import json
import subprocess
import time
from pathlib import Path

ART = Path(__file__).resolve().parents[1] / "artifacts" / "bench"
HISTORY = ART / "history.jsonl"


def git_provenance(root: Path | None = None) -> dict:
    """{"git_sha": <12 hex or "unknown">, "git_dirty": bool} for the
    repo at `root`. Never raises: outside a checkout (or without a git
    binary) the sha is "unknown" and dirty is False -- bench rows are
    still writable, just unattributed."""
    root = Path(root) if root is not None else ART.parents[1]
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short=12", "HEAD"],
            cwd=root, capture_output=True, text=True, timeout=10,
        )
        status = subprocess.run(
            ["git", "status", "--porcelain"],
            cwd=root, capture_output=True, text=True, timeout=10,
        )
        if sha.returncode != 0:
            return {"git_sha": "unknown", "git_dirty": False}
        return {
            "git_sha": sha.stdout.strip(),
            "git_dirty": bool(status.stdout.strip())
            if status.returncode == 0 else False,
        }
    except (OSError, subprocess.SubprocessError):
        return {"git_sha": "unknown", "git_dirty": False}


def append_history(rows, env: dict, path: Path = HISTORY,
                   timestamp: float | None = None) -> dict:
    """Appends one ledger entry holding this run's fresh rows (name /
    us_per_call / derived only -- manifests and cost columns live in
    results.json). Returns the entry."""
    entry = {
        "ts": round(time.time() if timestamp is None else timestamp, 3),
        **env,
        "rows": [
            {"name": r["name"], "us_per_call": r["us_per_call"],
             "derived": r["derived"]}
            for r in rows
        ],
    }
    path.parent.mkdir(parents=True, exist_ok=True)
    with path.open("a") as fh:
        fh.write(json.dumps(entry) + "\n")
    return entry


def load_history(path: Path = HISTORY) -> list:
    """All ledger entries, oldest first. Malformed lines are skipped
    (the ledger is append-only across PRs; one bad merge line must not
    brick the trend view)."""
    if not path.exists():
        return []
    entries = []
    for line in path.read_text().splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            entry = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(entry, dict) and isinstance(entry.get("rows"), list):
            entries.append(entry)
    return entries


def render_trend(history: list, last: int = 5, only=()) -> str:
    """Markdown-ish delta table: newest entry's rows vs the previous
    `last` entries. "vs prev" is the % change against the most recent
    older entry carrying the row; "vs window" against the OLDEST entry
    in the window carrying it; n counts entries (window + newest) that
    have the row. `only` filters row names by substring."""
    if not history:
        return "# trend: ledger is empty (run benchmarks/run.py first)"
    newest = history[-1]
    window = history[max(0, len(history) - 1 - last):-1]
    head = (
        f"# trend: {newest.get('git_sha', '?')}"
        f"{'+dirty' if newest.get('git_dirty') else ''}"
        f" vs {len(window)} prior entr"
        f"{'y' if len(window) == 1 else 'ies'}"
        f" ({len(history)} in ledger)"
    )
    if not window:
        return head + "\n# (need >= 2 entries for deltas)"

    def series(name):
        return [
            r["us_per_call"]
            for e in window for r in e["rows"] if r["name"] == name
        ]

    lines = [
        head,
        f"{'row':<44} {'us now':>12} {'vs prev':>9} "
        f"{'vs window':>10} {'n':>3}",
    ]
    for row in newest["rows"]:
        name = row["name"]
        if only and not any(s in name for s in only):
            continue
        hist = series(name)
        now = row["us_per_call"]
        if not hist:
            prev_s = wind_s = "new"
            n = 1
        else:
            prev_s = f"{100.0 * (now / hist[-1] - 1.0):+.1f}%"
            wind_s = f"{100.0 * (now / hist[0] - 1.0):+.1f}%"
            n = len(hist) + 1
        lines.append(
            f"{name:<44} {now:>12.1f} {prev_s:>9} {wind_s:>10} {n:>3}"
        )
    return "\n".join(lines)


def cost_columns(fn, *args) -> dict:
    """Lower+compile `fn(*args)` and normalize XLA's cost analysis into
    flat row columns: compile_ms (measured lower->compile wall),
    flops, bytes_accessed (0.0 when the backend reports neither).
    The persistent compilation cache is off around the compile, so
    compile_ms never times a cache read."""
    import jax

    from repro.compile_cache import compile_cache_off

    with compile_cache_off():
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        compile_ms = (time.perf_counter() - t0) * 1e3
    ca = compiled.cost_analysis() or {}
    return {
        "compile_ms": round(compile_ms, 3),
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--last", type=int, default=5,
                    help="window of prior ledger entries to diff against")
    ap.add_argument("--only", action="append", default=[],
                    help="substring filter on row names (repeatable)")
    args = ap.parse_args()
    print(render_trend(load_history(), last=args.last, only=args.only))


if __name__ == "__main__":
    main()
