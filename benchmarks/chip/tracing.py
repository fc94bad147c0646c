"""The profiler trace of a run, and its reduction to per-layer numbers.

A run with `--trace 1` records one short sub-window after its measured
one (`Session`), with the harness's host spans (`span`) around each
step and each stretch of host work. `reduce` turns the recorded
`.xplane.pb` into:

- the traced window: from the start of the first `bench.*` host span to
  the end of the last;
- device busy time: the union of the intervals in which an operation
  ran on the device, inside the window, averaged over the devices that
  ran any;
- device time per `repro.<phase>` scope (the program's named scopes,
  `repro.telemetry.profile.PHASES`) and the time of operations under no
  such scope. An operation's scope is read from the program's compiled
  HLO: the `op_name` metadata of the operation and of every operation
  fused into it. An operation whose fused parts carry more than one
  scope counts under `mixed`;
- the longest idle gaps, each named by the host span around it, and the
  operations that took most device time.

Operations nest on the device timeline (a loop and the operations of its
body): each interval counts once, to the innermost operation running.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
import shutil
import tempfile

SPAN_PREFIX = "bench."
SCOPE_RE = re.compile(r"repro\.(\w+)")
OPS_LINES = ("XLA Ops",)


def span(name):
    """Opens a host span in the profiler's trace; the caller closes it
    with `__exit__`."""
    import jax

    ann = jax.profiler.TraceAnnotation(name)
    ann.__enter__()
    return ann


class Session:
    """One profiler session into a temporary directory (under TMPDIR),
    read and removed by `reduce`."""

    def __init__(self):
        self.dir = None

    def start(self):
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(self.dir)

    def stop(self):
        import jax

        jax.profiler.stop_trace()

    def reduce(self, hlo_texts=(), **norm):
        from jax.profiler import ProfileData

        try:
            found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not found:
                raise RuntimeError(f"no .xplane.pb under {self.dir}")
            out = reduce(ProfileData.from_file(found[0]),
                         hlo_scopes(hlo_texts))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        out.update(norm)
        return out


# ---------------------------------------------------------------------------
# HLO: which repro scopes each operation of a compiled program carries

_COMP_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')


def hlo_scopes(texts) -> dict:
    """{operation name: frozenset of scopes} over the compiled programs'
    HLO texts: an operation's scopes are those in its own `op_name` and
    in the `op_name`s inside every computation it fuses (`calls=`)."""
    comps = {}   # computation -> [(instr, scopes, called)]
    for text in texts:
        cur = None
        for line in text.splitlines():
            m = _INSTR_RE.match(line)
            if m and cur is not None:
                op = _OPNAME_RE.search(line)
                scopes = set(SCOPE_RE.findall(op.group(1))) if op else set()
                called = _CALLS_RE.findall(line)
                comps[cur].append((m.group(1), scopes, called))
                continue
            m = _COMP_RE.match(line)
            if m:
                cur = m.group(1)
                comps.setdefault(cur, [])

    memo = {}

    def of_comp(name, seen=()):
        if name in memo:
            return memo[name]
        out = set()
        for _, scopes, called in comps.get(name, ()):
            out |= scopes
            for c in called:
                if c not in seen:
                    out |= of_comp(c, seen + (name,))
        memo[name] = out
        return out

    result = {}
    for instrs in comps.values():
        for name, scopes, called in instrs:
            s = set(scopes)
            for c in called:
                s |= of_comp(c)
            result[name] = frozenset(s)
    return result


# ---------------------------------------------------------------------------
# the trace


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def _leaf_intervals(events):
    """[(start, end, event)] of one timeline with nested events: each
    instant belongs to the innermost event covering it."""
    evs = sorted(events, key=lambda e: (e[0], -e[1]))
    out = []
    stack = []  # (end, event), outer to inner
    cursor = None

    def emit(a, b, ev):
        if b > a:
            out.append((a, b, ev))

    for s, e, ev in evs:
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            emit(cursor, end, top)
            cursor = end
        if stack:
            emit(cursor, s, stack[-1][1])
        stack.append((e, ev))
        cursor = s
    while stack:
        end, top = stack.pop()
        emit(cursor, end, top)
        cursor = end
    return out


def _union(intervals):
    total, cur_s, cur_e, merged = 0, None, None, []
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                merged.append((cur_s, cur_e))
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        merged.append((cur_s, cur_e))
    for s, e in merged:
        total += e - s
    return total, merged


def _is_ops_line(plane_name, line) -> bool:
    """A device's operations line; off the chip (a rehearsal on the CPU)
    the host threads whose events name an HLO operation."""
    if plane_name.startswith("/device:"):
        return line.name in OPS_LINES
    if plane_name.startswith("/host:CPU"):
        return any(_stat(ev, "hlo_op") is not None
                   for _, ev in zip(range(8), line.events))
    return False


_EVENT_OP_RE = re.compile(r"^%?([\w.\-]+)(?: = |$)")


def op_of(event_name: str) -> str:
    """The HLO operation an event ran: TPU traces name it by the whole
    instruction (`%fusion.35 = f32[...] fusion(...), ...`), the CPU by
    its name alone."""
    m = _EVENT_OP_RE.match(event_name)
    return m.group(1) if m else event_name


def scopes_of(name, scopes_map) -> tuple:
    """The `repro.<phase>` scopes an event's operation carries, itself or
    fused into it (sorted; empty when none)."""
    return tuple(sorted("repro." + s
                        for s in scopes_map.get(op_of(name), ())))


def reduce(pd, scopes_map, top=10) -> dict:
    """The reduction of one ProfileData (see the module doc). Times are
    seconds."""
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    if not spans:
        raise RuntimeError("the trace holds no bench.* host span")
    spans.sort()
    starts = [s for s, _, _ in spans]
    w0 = spans[0][0]
    w1 = max(e for _, e, _ in spans)

    busy_per_device = []
    by_scope = collections.Counter()
    by_op = collections.Counter()
    gaps = []
    n_devices = 0
    for plane in pd.planes:
        evs = []
        for line in plane.lines:
            if not _is_ops_line(plane.name, line):
                continue
            line_evs = []
            for ev in line.events:
                s = max(ev.start_ns, w0)
                e = min(ev.start_ns + ev.duration_ns, w1)
                if e > s:
                    line_evs.append((s, e, ev))
            evs += line_evs
            for s, e, ev in _leaf_intervals(line_evs):
                scopes = scopes_of(ev.name, scopes_map)
                for sc in scopes or ("unscoped",):
                    by_scope[sc] += e - s
                if len(scopes) > 1:
                    by_scope["mixed"] += e - s
                name = "+".join(scopes) or "unscoped"
                by_op[f"{name}/{op_of(ev.name)}"] += e - s
        if not evs:
            continue
        n_devices += 1
        busy, merged = _union([(s, e) for s, e, _ in evs])
        busy_per_device.append(busy)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                i = bisect.bisect_right(starts, (a + b) / 2) - 1
                inside = i >= 0 and (a + b) / 2 < spans[i][1]
                gaps.append((spans[i][2] if inside else "no host span",
                             b - a))
    if not n_devices:
        raise RuntimeError("no operation ran on the device in the trace")
    ns = 1e-9
    scope_s = {k: v * ns for k, v in by_scope.items()}
    window_s = (w1 - w0) * ns
    busy_s = sum(busy_per_device) / n_devices * ns
    totals = sorted(scope_s.items(), key=lambda kv: -kv[1])
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])
    gaps.sort(key=lambda g: -g[1])
    n_tot = min(len(totals), 4)
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "devices": n_devices,
        "scope_s": scope_s,
        "breakdown": {
            "device_ops": [[f"all ops under {k}", v] for k, v in
                           totals[:n_tot]]
            + [[k, v * ns] for k, v in ops[:top - n_tot]],
            "idle_gaps": [[n, g * ns] for n, g in gaps[:top]],
        },
    }
