"""Simulator integration tests: stability, emissions accounting, repro of
paper's headline comparisons at reduced horizon."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CarbonIntensityPolicy,
    QueueLengthPolicy,
    RandomCarbonSource,
    UKRegionalTraceSource,
    UniformArrivals,
    simulate,
    simulate_vsweep,
)
from repro.configs.paper_workloads import V_PAPER, paper_spec


@pytest.fixture(scope="module")
def results():
    spec = paper_spec()
    key = jax.random.PRNGKey(0)
    T = 800
    carbon = RandomCarbonSource(N=5)
    arrive = UniformArrivals(M=5, amax=400)
    rc = jax.jit(
        lambda: simulate(
            CarbonIntensityPolicy(V=V_PAPER), spec, carbon, arrive, T, key
        )
    )()
    rq = jax.jit(
        lambda: simulate(QueueLengthPolicy(), spec, carbon, arrive, T, key)
    )()
    return rc, rq, T


def test_emission_accounting_consistent(results):
    rc, _, _ = results
    np.testing.assert_allclose(
        np.asarray(rc.cum_emissions),
        np.cumsum(np.asarray(rc.emissions)),
        rtol=1e-5,
    )
    assert np.all(np.asarray(rc.emissions) >= 0)


def test_energy_constraints_never_violated(results):
    rc, rq, _ = results
    spec = paper_spec()
    for r in (rc, rq):
        assert np.all(np.asarray(r.energy_edge) <= spec.Pe + 1e-2)
        assert np.all(
            np.asarray(r.energy_cloud) <= np.asarray(spec.Pc)[None, :] + 1e-2
        )


def test_carbon_policy_beats_queue_policy(results):
    rc, rq, _ = results
    red = 1 - float(rc.cum_emissions[-1]) / float(rq.cum_emissions[-1])
    # paper reports 63% at T~2000; at T=800 with our seed it's > 50%
    assert red > 0.45, f"only {red:.2%} reduction"


def test_mean_rate_stability(results):
    rc, _, T = results
    # backlog grows sublinearly: Q(T)/T small and decreasing in T
    backlog_frac = float(rc.final_backlog) / T
    assert backlog_frac < 60.0
    # stronger: windowed averages of Qe flatten out (no linear blowup)
    qe = np.asarray(rc.Qe).sum(1)
    first, last = qe[: T // 4].mean(), qe[-T // 4 :].mean()
    assert last < 50 * max(first, 1.0)


def test_realworld_trace_reduction():
    spec = paper_spec()
    key = jax.random.PRNGKey(0)
    T = 600
    carbon = UKRegionalTraceSource(N=5)
    arrive = UniformArrivals(M=5, amax=400)
    rc = simulate(CarbonIntensityPolicy(V=V_PAPER), spec, carbon, arrive, T, key)
    rq = simulate(QueueLengthPolicy(), spec, carbon, arrive, T, key)
    red = 1 - float(rc.cum_emissions[-1]) / float(rq.cum_emissions[-1])
    assert red > 0.35  # paper: 54% at T~2000


def test_vsweep_tradeoff_monotone():
    """Theorem 1: larger V -> lower emissions, larger queues (Fig 2+4)."""
    spec = paper_spec()
    Vs = jnp.array([0.005, 0.05, 0.5])
    res = simulate_vsweep(
        lambda V: CarbonIntensityPolicy(V=V),
        Vs,
        spec,
        RandomCarbonSource(N=5),
        UniformArrivals(M=5, amax=400),
        500,
        jax.random.PRNGKey(1),
    )
    cum = np.asarray(res.cum_emissions[:, -1])
    qe_mean = np.asarray(res.Qe).mean((1, 2))
    assert cum[0] > cum[1] > cum[2]
    assert qe_mean[0] < qe_mean[2]


def test_record_summary_matches_full_bitwise():
    """record="summary" keeps the per-slot scalar series bitwise equal
    to full recording and returns the final state as a length-1
    trajectory (so Qe[-1]/final_backlog work unchanged)."""
    spec = paper_spec()
    key = jax.random.PRNGKey(3)
    args = (
        CarbonIntensityPolicy(V=0.05), spec, RandomCarbonSource(N=5),
        UniformArrivals(M=5, amax=400), 120, key,
    )
    full = simulate(*args)
    summ = simulate(*args, record="summary")
    for name in ("emissions", "cum_emissions", "dispatched", "processed",
                 "energy_edge", "energy_cloud"):
        np.testing.assert_array_equal(
            np.asarray(getattr(full, name)), np.asarray(getattr(summ, name)),
            err_msg=name,
        )
    assert summ.Qe.shape == (1, 5)
    assert summ.Qc.shape == (1, 5, 5)
    np.testing.assert_array_equal(np.asarray(full.Qe[-1]),
                                  np.asarray(summ.Qe[0]))
    np.testing.assert_array_equal(np.asarray(full.Qc[-1]),
                                  np.asarray(summ.Qc[0]))
    np.testing.assert_array_equal(np.asarray(full.final_backlog),
                                  np.asarray(summ.final_backlog))


def test_record_stride_snapshots_every_k_slots():
    """record=k snapshots the post-step state at slots k-1, 2k-1, ...
    (exactly the rows full recording stacks there) and keeps the scalar
    series identical."""
    spec = paper_spec()
    key = jax.random.PRNGKey(4)
    args = (
        CarbonIntensityPolicy(V=0.05), spec, RandomCarbonSource(N=5),
        UniformArrivals(M=5, amax=400), 120, key,
    )
    full = simulate(*args)
    k = 8
    strided = simulate(*args, record=k)
    assert strided.Qe.shape == (120 // k, 5)
    np.testing.assert_array_equal(
        np.asarray(full.Qe[k - 1 :: k]), np.asarray(strided.Qe)
    )
    np.testing.assert_array_equal(
        np.asarray(full.Qc[k - 1 :: k]), np.asarray(strided.Qc)
    )
    np.testing.assert_array_equal(
        np.asarray(full.emissions), np.asarray(strided.emissions)
    )


def test_record_rejects_bad_stride():
    spec = paper_spec()
    args = (
        CarbonIntensityPolicy(V=0.05), spec, RandomCarbonSource(N=5),
        UniformArrivals(M=5, amax=400), 100, jax.random.PRNGKey(0),
    )
    with pytest.raises(ValueError, match="record"):
        simulate(*args, record=7)  # 7 does not divide 100
    with pytest.raises(ValueError, match="record"):
        simulate(*args, record=0)


def test_simulation_deterministic_given_key():
    spec = paper_spec()
    args = (
        CarbonIntensityPolicy(V=0.05),
        spec,
        RandomCarbonSource(N=5),
        UniformArrivals(M=5, amax=400),
        100,
        jax.random.PRNGKey(7),
    )
    r1, r2 = simulate(*args), simulate(*args)
    np.testing.assert_array_equal(
        np.asarray(r1.cum_emissions), np.asarray(r2.cum_emissions)
    )


def _lane(fleet, f):
    """Lane f of a stacked network fleet as (spec, graph, carbon, arrivals)."""
    from repro.core import TableCarbonSource
    from repro.core.queueing import NetworkSpec

    spec = NetworkSpec(pe=fleet.spec.pe[f], pc=fleet.spec.pc[f],
                       Pe=fleet.spec.Pe[f], Pc=fleet.spec.Pc[f])
    graph = jax.tree.map(lambda x: x[f], fleet.graph)
    return (spec, graph, TableCarbonSource(table=fleet.carbon[f]),
            UniformArrivals(M=spec.M, amax=300))


@pytest.mark.parametrize("entry", ["simulate-wan", "serve-step"])
def test_run_does_not_depend_on_how_spec_and_graph_are_passed(entry):
    """Closed over under jit (XLA constants it could rewrite) or passed
    as arguments, the spec and route graph give the same run bitwise:
    through `simulate` (a WAN lane, whose closed-over graph changed the
    final queues before `as_data`) and through the served step, which
    closes over its spec by construction."""
    from repro.configs.fleet_scenarios import build_network_fleet
    from repro.core.queueing import NetworkSpec, init_state
    from repro.network import NetworkAwareDPPPolicy
    from repro.serve import make_serve_step

    key = jax.random.PRNGKey(0)
    T = 48

    def passed(pol, spec, carbon, arrivals, graph=None):
        def run(pe, pc, Pe, Pc, g, k):
            return simulate(pol, NetworkSpec(pe=pe, pc=pc, Pe=Pe, Pc=Pc),
                            carbon, arrivals, T, k, graph=g,
                            record="summary")
        return jax.jit(run)(spec.pe, spec.pc, spec.Pe, spec.Pc, graph, key)

    if entry == "simulate-wan":
        fleet = build_network_fleet(["congested-uplink"], per_kind=4,
                                    Tc=96, seed=0)
        pol = NetworkAwareDPPPolicy(V=0.1)
        for f in range(fleet.F):
            spec, graph, carbon, arrivals = _lane(fleet, f)
            closed = jax.jit(lambda k: simulate(
                pol, spec, carbon, arrivals, T, k, graph=graph,
                record="summary"))(key)
            ref = passed(pol, spec, carbon, arrivals, graph)
            for field in ("Qe", "Qc", "Qt", "emissions"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(closed, field)),
                    np.asarray(getattr(ref, field)),
                    err_msg=f"lane {f} {field}")
    else:
        spec = paper_spec()
        pol = CarbonIntensityPolicy(V=0.05)
        carbon = UKRegionalTraceSource(N=spec.N)
        arrivals = UniformArrivals(M=spec.M, amax=400)
        step = make_serve_step(pol, spec, carbon, arrivals, key)
        state = init_state(spec.M, spec.N)
        for t in range(T):
            state, _ = step(state, jnp.int32(t))
        # the decisions, i.e. the queues; the per-slot emission sums
        # of the two programs may round apart by an ulp on the CPU
        ref = passed(pol, spec, carbon, arrivals)
        np.testing.assert_array_equal(np.asarray(state.Qe),
                                      np.asarray(ref.Qe[-1]))
        np.testing.assert_array_equal(np.asarray(state.Qc),
                                      np.asarray(ref.Qc[-1]))
