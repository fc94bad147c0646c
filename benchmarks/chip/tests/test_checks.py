"""What decides `correct`: the plain reference agrees with the program
to the task on the CPU, its bfloat16 control fails the limits, and a run
whose timed path is broken underneath comes out not correct, once for
each fault its cell can have."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TINY_MIX, WIDE, load_cfg

import gen
from paths import fleet as fleet_path
from paths import serve as serve_path


def _tiny(cfg_name, mix_name):
    cfg = load_cfg(cfg_name)
    mix = dict(gen.load_json(gen.bench_dir() / "traffic"
                             / f"{mix_name}.json"),
               **TINY_MIX.get(mix_name, {}))
    return cfg, mix


def _out_of_limits(checks):
    return [k for k, c in checks.items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("wide", [False, True])
def test_serve_control_fails(wide):
    cfg, mix = _tiny("paper-table1", "steady")
    if wide:
        cfg.update(WIDE)
    for seed in (3, 2**33 + 1, 77):
        assert _out_of_limits(serve_path.control(cfg, mix, seed, 120))


def test_fleet_control_fails():
    cfg, mix = _tiny("paper-table1", "whatif")
    for seed in (3, 2**33 + 1, 77):
        assert _out_of_limits(fleet_path.control(cfg, mix, seed, calls=1))


# -- faults planted under a whole run --------------------------------------


def _policy_fault(monkeypatch, alter):
    """Wraps the policy's decision where it is produced."""
    from repro.core import policies

    orig = policies.CarbonIntensityPolicy.__call__

    def call(self, state, *a, **kw):
        return alter(orig(self, state, *a, **kw), state)

    monkeypatch.setattr(policies.CarbonIntensityPolicy, "__call__", call)


def answer_altered(monkeypatch):
    """One more task processed at (type 0, cloud 0) where any waits."""
    def alter(act, state):
        extra = jnp.zeros_like(act.w).at[0, 0].set(
            jnp.where(state.Qc[0, 0] > act.w[0, 0], 1.0, 0.0))
        return act._replace(w=act.w + extra)

    _policy_fault(monkeypatch, alter)


def half_the_types(monkeypatch):
    """Decisions made for the first half of the task types only."""
    def alter(act, state):
        M = act.d.shape[0]
        keep = (jnp.arange(M) < M // 2)[:, None]
        return act._replace(d=jnp.where(keep, act.d, 0.0),
                            w=jnp.where(keep, act.w, 0.0))

    _policy_fault(monkeypatch, alter)


def served_state_unchanged(monkeypatch):
    import repro.serve.loop as loop

    monkeypatch.setattr(loop, "queue_step", lambda state, act, a: state)


def fleet_state_unchanged(monkeypatch):
    import repro.core.simulator as sim

    monkeypatch.setattr(sim, "step", lambda state, act, a: state)


def half_the_lanes(monkeypatch):
    """The fleet runs half of its lanes and repeats them for the rest."""
    import repro.core as core

    orig = core.simulate_fleet

    def half(policy, fleet, T, key, **kw):
        F = fleet.F
        part = jax.tree.map(lambda x: x[: F // 2], fleet)
        res = orig(policy, part, T, key, **kw)
        return jax.tree.map(
            lambda x: jnp.concatenate([x, x])[:F] if x is not None else x,
            res)

    monkeypatch.setattr(core, "simulate_fleet", half)


@pytest.mark.parametrize("cell,fault", [
    ("serve-paper-steady", served_state_unchanged),
    ("serve-paper-steady", answer_altered),
    ("serve-paper-steady", half_the_types),
    ("fleet-paper-whatif", fleet_state_unchanged),
    ("fleet-paper-whatif", answer_altered),
    ("fleet-paper-whatif", half_the_lanes),
])
def test_broken_path_is_not_correct(checkout, monkeypatch, cell, fault):
    res, _ = checkout.run(cell, seed=5)
    assert res["correct"] is True
    fault(monkeypatch)
    jax.clear_caches()
    res, _ = checkout.run(cell, seed=5)
    assert res["correct"] is False, res["checks"]
    assert res["failed"] > 0


def test_reference_matches_program_exactly():
    """At a small size on the CPU the reference and the served program
    agree on every queue, backlog and emission of every slot."""
    from repro.serve import serve_loop

    cfg, mix = _tiny("paper-table1", "steady")
    cfg.update(WIDE)
    inp = serve_path._inputs(cfg, mix, 9)
    T = 150
    rep = serve_loop(inp["policy"], inp["spec"], inp["carbon"],
                     inp["arrivals"], T, inp["key"])
    ref = serve_path._reference(cfg, mix, inp, T, "float32")
    np.testing.assert_array_equal(ref["Qc"], np.asarray(rep.final_state.Qc))
    np.testing.assert_array_equal(ref["backlog"], rep.backlog)
    np.testing.assert_allclose(ref["emissions"], rep.slot_emissions,
                               rtol=1e-6)
