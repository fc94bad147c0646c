"""Trace instrumentation: the served loop's host spans and the slot's
device scopes (`repro.telemetry.profile`).

* under a profiler session `serve_loop` records exactly one of each
  `HOST_SPANS` entry per slot, in order, without touching its clock
  pattern (2T + 2 calls);
* the compiled served step and fleet program carry every slot phase's
  `repro.<phase>` scope in their HLO `op_name` metadata;
* every scope and span placed under `src/repro` is a canonical name,
  and every canonical name is placed;
* fills of at most `LANE_WIDTH` items compile without a gather or
  scatter under `repro.greedy_fill`, and longer fills keep theirs.
"""
import glob
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.fleet_scenarios import build_fleet
from repro.core import CarbonIntensityPolicy, simulate_fleet
from repro.core.policies import LANE_WIDTH, greedy_fill
from repro.core.queueing import init_state
from repro.serve import make_serve_step, serve_loop
from repro.telemetry.profile import HOST_SPANS, PHASES, trace_to
from test_serve import FakeClock, _setup

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"


def test_serve_loop_spans_one_of_each_per_slot_in_order(tmp_path):
    T = 6
    pol, spec, cs, ar, key = _setup()
    clock = FakeClock()
    with trace_to(str(tmp_path)):
        serve_loop(pol, spec, cs, ar, T, key, warmup=2, clock=clock)
    assert clock.calls == 2 * T + 2
    found = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(found) == 1
    spans = sorted(
        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
        for plane in ProfileData.from_file(found[0]).planes
        if plane.name.startswith("/host")
        for line in plane.lines
        for ev in line.events
        if ev.name.startswith("serve.")
    )
    assert [name for _, _, name in spans] == list(HOST_SPANS) * T
    # one after another on the host, never overlapping
    for (_, end, _), (start, _, _) in zip(spans, spans[1:]):
        assert end <= start


def _serve_hlo(deadlines=None):
    pol, spec, cs, ar, key = _setup()
    step = make_serve_step(pol, spec, cs, ar, key, deadlines=deadlines)
    state = init_state(spec.M, spec.N)
    if deadlines is not None:
        from repro.deadlines.model import init_deadlines

        state = (state, init_deadlines(spec.M, deadlines.rings.shape[-1]))
    return step.lower(state, jnp.int32(0)).compile().as_text()


def _fleet_hlo():
    fleet = build_fleet(["diurnal", "bursty"], per_kind=2, Tc=12, seed=3)
    pol = CarbonIntensityPolicy(V=0.05)
    fn = jax.jit(lambda fl, k: simulate_fleet(pol, fl, 12, k,
                                              record="summary"))
    return fn.lower(fleet, jax.random.PRNGKey(0)).compile().as_text()


def _deadline_serve_hlo():
    from repro.deadlines import make_deadlines

    return _serve_hlo(make_deadlines(_setup()[1].M, deadline=3.0,
                                     shed_on=1.0))


SLOT = ("arrivals", "carbon", "queue_update", "emissions",
        "policy_score", "greedy_fill", "fill_dense")
PROGRAMS = {
    "serve_step": (_serve_hlo, SLOT),
    "simulate_fleet": (_fleet_hlo, SLOT),
    "serve_step_deadlines": (_deadline_serve_hlo,
                             SLOT + ("deadline_step",)),
}


@pytest.fixture(scope="module")
def hlos():
    """{program: its compiled HLO text}, compiled once."""
    return {name: build() for name, (build, _) in PROGRAMS.items()}


@pytest.fixture(scope="module")
def op_names(hlos):
    """{program: every op_name in its compiled HLO}."""
    return {name: set(re.findall(r'op_name="([^"]*)"', text))
            for name, text in hlos.items()}


@pytest.mark.parametrize("program,scope", [
    (p, s) for p, (_, scopes) in PROGRAMS.items() for s in scopes])
def test_compiled_program_carries_phase_scope(op_names, program, scope):
    assert any(f"repro.{scope}/" in n for n in op_names[program]), (
        program, scope)


_INDEX_OP = re.compile(r' (gather|scatter)\(.*?op_name="([^"]*)"')


def _fill_index_ops(hlo):
    """The opcodes of a compiled program's gathers and scatters that
    carry `repro.greedy_fill`."""
    return [op for op, name in _INDEX_OP.findall(hlo)
            if "repro.greedy_fill/" in name]


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_short_fill_rows_compile_without_gather_or_scatter(hlos, program):
    """M = 5 rows take the dense one-hot path: the fill's permutation is
    selects and sums, with no index gather or scatter left."""
    assert _fill_index_ops(hlos[program]) == []


def test_long_fill_rows_keep_their_gathers_and_scatter():
    M = 200
    assert M > LANE_WIDTH
    row = jax.ShapeDtypeStruct((6, M), jnp.float32)
    fn = jax.jit(lambda s, e, c, p: greedy_fill(s, e, c, p, chunk=3))
    hlo = fn.lower(row, row, row,
                   jax.ShapeDtypeStruct((6,), jnp.float32)).compile()
    ops = _fill_index_ops(hlo.as_text())
    assert "gather" in ops and "scatter" in ops, ops
    assert "repro.fill_dense" not in hlo.as_text()


def _placed(fn_name):
    """{name: [files]} of every `fn_name("<name>")` under src/repro,
    outside the module that defines them."""
    out = {}
    pat = re.compile(rf'\b{fn_name}\("([^"]+)"\)')
    for path in SRC.rglob("*.py"):
        if path == SRC / "telemetry" / "profile.py":
            continue
        for name in pat.findall(path.read_text()):
            out.setdefault(name, []).append(path.relative_to(SRC))
    return out


def test_every_placed_phase_is_canonical_and_every_phase_placed():
    placed = _placed("phase")
    assert set(placed) - set(PHASES) == set(), placed
    assert set(PHASES) - set(placed) == set()


def test_every_placed_span_is_canonical_and_every_span_placed():
    placed = _placed("span")
    assert set(placed) == set(HOST_SPANS), placed
    assert all(files == [Path("serve/loop.py")]
               for files in placed.values())


def test_traced_run_returns_the_untraced_results(tmp_path):
    """The spans wrap the loop's work without changing it."""
    pol, spec, cs, ar, key = _setup()
    plain = serve_loop(pol, spec, cs, ar, 6, key, clock=FakeClock())
    with trace_to(str(tmp_path)):
        traced = serve_loop(pol, spec, cs, ar, 6, key, clock=FakeClock())
    for field in ("latency_us", "backlog", "queue_age", "slot_emissions"):
        np.testing.assert_array_equal(getattr(plain, field),
                                      getattr(traced, field))
    assert plain.tasks_per_sec == traced.tasks_per_sec


_SCOPED = ("repro.core.queueing", "repro.core.simulator",
           "repro.core.policies", "repro.serve.loop",
           "repro.deadlines.model")
_TABLES = re.compile(
    r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n.*?\n\n",
    re.M | re.S)


def _bare(hlo):
    """A compiled program's text without its metadata, its source tables
    and its instruction names (a second trace in one process names them
    on from the first): opcodes, shapes and attributes remain."""
    hlo = re.sub(r", metadata=\{[^}]*\}", "", _TABLES.sub("", hlo))
    hlo = re.sub(r", stack_frame_id=\d+", "", hlo)
    return re.sub(r"%[\w.\-]+", "%", hlo)


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_scopes_leave_the_compiled_program_unchanged(monkeypatch,
                                                     program):
    """Scopes are metadata only: with every `phase` a no-op the compiled
    program is the same, apart from its metadata."""
    import contextlib
    import importlib

    build = PROGRAMS[program][0]
    scoped = build()
    for mod in _SCOPED:
        monkeypatch.setattr(importlib.import_module(mod), "phase",
                            lambda name: contextlib.nullcontext())
    plain = build()
    assert "repro." in scoped and "repro." not in _bare(scoped)
    assert "repro.arrivals" not in plain
    assert _bare(scoped) == _bare(plain)
