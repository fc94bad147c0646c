"""Fleet-simulation engine: simulate_fleet == per-instance simulate,
scenario registry shapes, and the one-compiled-call acceptance check."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.fleet_scenarios import SCENARIOS, build_fleet
from repro.core import (
    CarbonIntensityPolicy,
    QueueLengthPolicy,
    simulate,
    simulate_fleet,
)
from repro.core.queueing import NetworkSpec


def test_fleet_matches_per_instance_simulate():
    """Each lane of the vmapped fleet reproduces a standalone simulate()
    run with the same spec/table/arrivals/key."""
    fleet = build_fleet(["diurnal", "heterogeneous-fleet"], per_kind=2,
                        Tc=48, seed=3)
    T = 25
    key = jax.random.PRNGKey(7)
    pol = CarbonIntensityPolicy(V=0.05)
    res = simulate_fleet(pol, fleet, T, key)
    keys = jax.random.split(key, fleet.F)
    M = fleet.arrival_amax.shape[1]
    for f in range(fleet.F):
        spec = NetworkSpec(
            pe=fleet.spec.pe[f], pc=fleet.spec.pc[f],
            Pe=fleet.spec.Pe[f], Pc=fleet.spec.Pc[f],
        )
        ctab = fleet.carbon[f]
        amax = fleet.arrival_amax[f]

        def carbon_source(t, kk, ctab=ctab):
            del kk
            row = ctab[t % ctab.shape[0]]
            return row[0], row[1:]

        def arrival_source(t, kk, amax=amax):
            u = jax.random.uniform(jax.random.fold_in(kk, t), (M,))
            return jnp.floor(u * (amax + 1.0))

        one = simulate(pol, spec, carbon_source, arrival_source, T, keys[f])
        np.testing.assert_allclose(
            np.asarray(res.cum_emissions[f]), np.asarray(one.cum_emissions),
            rtol=1e-6,
        )
        np.testing.assert_array_equal(
            np.asarray(res.Qe[f]), np.asarray(one.Qe)
        )


def test_fleet_64_instances_one_jitted_call():
    """Acceptance: >= 64 scenario instances sweep in ONE jitted call."""
    fleet = build_fleet(per_kind=16)  # 4 registered kinds x 16 = 64
    assert fleet.F >= 64
    T = 20
    f = jax.jit(lambda k: simulate_fleet(
        CarbonIntensityPolicy(V=0.05), fleet, T, k
    ))
    res = f(jax.random.PRNGKey(0))
    assert res.cum_emissions.shape == (fleet.F, T)
    assert res.Qe.shape == (fleet.F, T, fleet.arrival_amax.shape[1])
    assert bool(jnp.isfinite(res.cum_emissions).all())
    # per-instance cumulative emissions are nondecreasing
    assert bool((jnp.diff(res.cum_emissions, axis=1) >= -1e-3).all())
    # distinct scenarios produce distinct trajectories
    assert len(np.unique(np.asarray(res.cum_emissions[:, -1]))) > 1


def test_registry_names_and_shapes():
    assert set(SCENARIOS) == {
        "diurnal", "diurnal-slack", "bursty", "heterogeneous-fleet",
        "multi-region-uk", "overload",
    }
    fleet = build_fleet(["bursty", "multi-region-uk"], per_kind=3,
                        M=7, N=4, Tc=30, seed=1)
    assert fleet.F == 6
    assert fleet.spec.pe.shape == (6, 7)
    assert fleet.spec.pc.shape == (6, 7, 4)
    assert fleet.spec.Pc.shape == (6, 4)
    assert fleet.carbon.shape == (6, 30, 5)
    assert fleet.arrival_amax.shape == (6, 7)
    # tables are valid intensities
    assert float(fleet.carbon.min()) >= 0.0
    assert float(fleet.carbon.max()) <= 700.0


def test_build_fleet_unknown_name():
    with pytest.raises(KeyError, match="registered"):
        build_fleet(["no-such-scenario"], per_kind=1)


def test_fleet_record_summary_matches_full():
    """One compiled call, F lanes, record="summary": scalar series
    bitwise equal to full recording, Qe/Qc collapse to [F, 1, ...]."""
    fleet = build_fleet(["diurnal", "bursty"], per_kind=3, Tc=48, seed=5)
    T, key = 40, jax.random.PRNGKey(11)
    pol = CarbonIntensityPolicy(V=0.05)
    full = simulate_fleet(pol, fleet, T, key)
    summ = jax.jit(lambda k: simulate_fleet(
        pol, fleet, T, k, record="summary"
    ))(key)
    for name in ("emissions", "cum_emissions", "dispatched", "processed",
                 "energy_edge", "energy_cloud"):
        np.testing.assert_array_equal(
            np.asarray(getattr(full, name)), np.asarray(getattr(summ, name)),
            err_msg=name,
        )
    M = fleet.arrival_amax.shape[1]
    assert summ.Qe.shape == (fleet.F, 1, M)
    np.testing.assert_array_equal(
        np.asarray(full.Qe[:, -1]), np.asarray(summ.Qe[:, 0])
    )
    np.testing.assert_array_equal(
        np.asarray(full.Qc[:, -1]), np.asarray(summ.Qc[:, 0])
    )


def test_fleet_carbon_policy_beats_queue_policy_on_average():
    """The paper's headline holds across a heterogeneous fleet: averaged
    over scenarios, the carbon-aware policy emits less than the
    queue-length baseline."""
    fleet = build_fleet(per_kind=4, Tc=48, seed=9)  # F=16
    T = 60
    key = jax.random.PRNGKey(2)
    carb = simulate_fleet(CarbonIntensityPolicy(V=0.05), fleet, T, key)
    base = simulate_fleet(QueueLengthPolicy(), fleet, T, key)
    mean_carb = float(carb.cum_emissions[:, -1].mean())
    mean_base = float(base.cum_emissions[:, -1].mean())
    assert mean_carb < mean_base


@pytest.mark.parametrize("kind,F,T", [("diurnal", 16, 96),
                                      ("congested-uplink", 8, 48)])
def test_fleet_run_does_not_depend_on_how_the_fleet_is_passed(kind, F, T):
    """Closed over under jit (XLA constants it could fold on the host)
    or passed as an argument, the fleet gives the same run bitwise."""
    from repro.configs.fleet_scenarios import build_network_fleet
    from repro.network import NetworkAwareDPPPolicy

    if kind == "diurnal":
        fleet = build_fleet([kind], per_kind=F, Tc=96, seed=0)
        pol = CarbonIntensityPolicy(V=0.05)
    else:
        fleet = build_network_fleet([kind], per_kind=F, Tc=96, seed=0)
        pol = NetworkAwareDPPPolicy(V=0.1)
    key = jax.random.PRNGKey(0)
    closed = jax.jit(lambda k: simulate_fleet(
        pol, fleet, T, k, record="summary"))(key)
    passed = jax.jit(lambda fl, k: simulate_fleet(
        pol, fl, T, k, record="summary"))(fleet, key)
    for field in ("Qe", "Qc", "emissions"):
        np.testing.assert_array_equal(
            np.asarray(getattr(closed, field)),
            np.asarray(getattr(passed, field)), err_msg=field,
        )
