"""WAN transfer subsystem tests: degenerate-graph parity (the
regression anchor), route-kernel backend equivalence, Qt conservation,
bandwidth-cap saturation, ceil(size/bw) latency, and vmap shape/dtype
contracts across stacked topologies."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.fleet_scenarios import (
    NETWORK_SCENARIOS,
    build_network_fleet,
)
from repro.core import (
    CarbonIntensityPolicy,
    RandomCarbonSource,
    UniformArrivals,
    simulate,
    simulate_fleet,
)
from repro.core.queueing import NetworkSpec, NetworkState
from repro.network import (
    NetworkAwareDPPPolicy,
    StaticRoutePolicy,
    direct_graph,
    init_links,
    make_graph,
    step_links,
)
from repro.network.transfer import land_in_clouds, sum_rows_in_order

jax.config.update("jax_enable_x64", False)


def _random_instance(rng, M, N):
    spec = NetworkSpec(
        pe=rng.uniform(1, 8, M).astype(np.float32),
        pc=rng.uniform(2, 100, (M, N)).astype(np.float32),
        Pe=float(rng.uniform(100, 2000)),
        Pc=rng.uniform(100, 5000, N).astype(np.float32),
    )
    state = NetworkState(
        Qe=jnp.asarray(rng.integers(0, 1000, M).astype(np.float32)),
        Qc=jnp.asarray(rng.integers(0, 1000, (M, N)).astype(np.float32)),
    )
    Ce = jnp.float32(rng.uniform(0, 700))
    Cc = jnp.asarray(rng.uniform(0, 700, N).astype(np.float32))
    return spec, state, Ce, Cc


# ------------------------------------------------- degenerate-graph parity


@pytest.mark.parametrize("backend", ["reference", "pallas"])
@pytest.mark.parametrize("chunk", [8, 512])
def test_degenerate_graph_policy_bit_parity(backend, chunk):
    """On direct_graph (one infinite-bandwidth, zero-transfer-carbon
    link per cloud) NetworkAwareDPPPolicy's actions are BIT-IDENTICAL
    to CarbonIntensityPolicy's on both score backends -- the
    subsystem's regression anchor."""
    rng = np.random.default_rng(7)
    for M, N in [(5, 5), (23, 9), (64, 16)]:
        spec, state, Ce, Cc = _random_instance(rng, M, N)
        g = direct_graph(M, N)
        Qt0 = jnp.zeros((M, N), jnp.float32)
        # score_interpret=True pins the pallas backend to the real
        # (emulated) kernels on CPU; the reference backend ignores it.
        interp = True if backend == "pallas" else None
        base = CarbonIntensityPolicy(
            V=0.05, fill_chunk=chunk, score_backend=backend,
            score_interpret=interp,
        )
        net = NetworkAwareDPPPolicy(
            V=0.05, fill_chunk=chunk, score_backend=backend,
            score_interpret=interp,
        )
        a = jax.jit(lambda s: base(s, spec, Ce, Cc, None, None))(state)
        b = jax.jit(
            lambda s: net(s, spec, Ce, Cc, None, None, graph=g, Qt=Qt0)
        )(state)
        np.testing.assert_array_equal(np.asarray(a.d), np.asarray(b.dt))
        np.testing.assert_array_equal(np.asarray(a.w), np.asarray(b.w))


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_degenerate_graph_simulation_parity(backend):
    """Full trajectories through the WAN simulator on direct_graph
    match the link-free simulator: queue trajectories bitwise, Qt pinned
    at zero, emissions to float tolerance (the two scan bodies fuse
    reductions differently, as in test_fleet's per-instance check)."""
    rng = np.random.default_rng(3)
    M, N = 11, 6
    spec, _, _, _ = _random_instance(rng, M, N)
    carbon = RandomCarbonSource(N=N)
    arrive = UniformArrivals(M=M, amax=80)
    key = jax.random.PRNGKey(5)
    g = direct_graph(M, N)
    interp = True if backend == "pallas" else None
    r0 = simulate(
        CarbonIntensityPolicy(V=0.05, score_backend=backend,
                              score_interpret=interp),
        spec, carbon, arrive, 40, key,
    )
    r1 = simulate(
        NetworkAwareDPPPolicy(V=0.05, score_backend=backend,
                              score_interpret=interp),
        spec, carbon, arrive, 40, key, graph=g,
    )
    np.testing.assert_array_equal(np.asarray(r0.Qe), np.asarray(r1.Qe))
    np.testing.assert_array_equal(np.asarray(r0.Qc), np.asarray(r1.Qc))
    assert float(jnp.abs(r1.Qt).max()) == 0.0
    assert float(r1.energy_transfer.sum()) == 0.0
    np.testing.assert_allclose(
        np.asarray(r0.cum_emissions), np.asarray(r1.cum_emissions),
        rtol=1e-6,
    )


# ------------------------------------------------------- kernel equivalence


@pytest.mark.parametrize(
    "M,L,bm,bl",
    [
        (5, 5, 256, 256),      # tiny, blocks larger than the array
        (128, 128, 128, 128),  # exact block fit
        (100, 37, 64, 16),     # non-multiple of block in both dims
        (257, 129, 128, 128),  # one row/col past the block boundary
    ],
)
def test_route_kernel_bit_identical(M, L, bm, bl):
    from repro.kernels import ops

    rng = np.random.default_rng(M * 100 + L)
    for _ in range(3):
        Qt = jnp.asarray(rng.integers(0, 500, (M, L)).astype(np.float32))
        pt = jnp.asarray(rng.uniform(0, 5, (M, L)).astype(np.float32))
        Qcr = jnp.asarray(rng.integers(0, 900, (M, L)).astype(np.float32))
        extra = jnp.asarray(rng.uniform(0, 50, (M, L)).astype(np.float32))
        Qe = jnp.asarray(rng.integers(0, 900, M).astype(np.float32))
        pe = jnp.asarray(rng.uniform(1, 8, M).astype(np.float32))
        VCt = jnp.asarray(rng.uniform(0, 40, L).astype(np.float32))
        V_Ce = jnp.float32(rng.uniform(0, 40))
        ref = jax.jit(ops.route_scores_ref)(
            Qt, pt, Qcr, extra, Qe, pe, VCt, V_Ce
        )
        # interpret=True forces the emulated Pallas kernel (auto-dispatch
        # would lower to the reference off-TPU, making this vacuous)
        pal = ops.route_scores(
            Qt, pt, Qcr, extra, Qe, pe, VCt, V_Ce,
            block_m=bm, block_l=bl, interpret=True,
        )
        np.testing.assert_array_equal(np.asarray(ref[0]), np.asarray(pal[0]))
        np.testing.assert_array_equal(np.asarray(ref[1]), np.asarray(pal[1]))
        np.testing.assert_array_equal(np.asarray(ref[2]), np.asarray(pal[2]))


def test_network_policy_unknown_backend_raises():
    rng = np.random.default_rng(0)
    spec, state, Ce, Cc = _random_instance(rng, 5, 5)
    g = direct_graph(5, 5)
    pol = NetworkAwareDPPPolicy(score_backend="nope")
    with pytest.raises(ValueError, match="score_backend"):
        pol(state, spec, Ce, Cc, None, None,
            graph=g, Qt=jnp.zeros((5, 5)))


# -------------------------------------------------------- link dynamics


def _two_link_graph(size, bw):
    M = len(size)
    return make_graph(
        dest=[0, 1], bw=bw, pt=np.ones((M, 2), np.float32),
        region=[1, 2], size=size, primary=[0, 1],
    )


def test_qt_conservation_no_task_lost_or_duplicated():
    """Over a random dispatch stream, per (type, route):
    total injected == total delivered + still in flight, exactly."""
    rng = np.random.default_rng(11)
    M = 4
    g = _two_link_graph(
        size=rng.uniform(0.5, 6.0, M).astype(np.float32),
        bw=[7.0, 2.5],
    )
    ls = init_links(M, 2)
    injected = np.zeros((M, 2))
    delivered = np.zeros((M, 2))
    for t in range(60):
        dt = rng.integers(0, 5, (M, 2)).astype(np.float32)
        if t > 40:
            dt = np.zeros_like(dt)  # drain phase
        ls, dl = step_links(ls, g, jnp.asarray(dt))
        injected += dt
        delivered += np.asarray(dl)
        assert (np.asarray(dl) >= 0).all()
        assert (np.asarray(dl) == np.round(np.asarray(dl))).all()
    np.testing.assert_array_equal(injected, delivered + np.asarray(ls.Qt))
    # residual progress is always less than one task's worth of work
    assert (np.asarray(ls.prog) < np.asarray(g.size)[:, None] + 1e-5).all()


def test_bandwidth_cap_saturation():
    """A flooded route delivers at most bw size-units per slot, and
    keeps delivering at (near) line rate while backlogged."""
    M, bw = 3, 12.0
    size = np.array([1.0, 2.0, 4.0], np.float32)
    g = make_graph(
        dest=[0], bw=[bw], pt=np.ones((M, 1), np.float32),
        region=[1], size=size, primary=[0],
    )
    ls = init_links(M, 1)
    cum_work = 0.0
    for t in range(30):
        dt = jnp.full((M, 1), 10.0)  # 70 size-units/slot offered
        ls, dl = step_links(ls, g, dt)
        cum_work += float((np.asarray(dl)[:, 0] * size).sum())
        # the pipe can never have moved more than bw per elapsed slot
        # (a single slot may burst above bw when multi-slot progress
        # completes, but the running total is capped at line rate)
        assert cum_work <= bw * (t + 1) + 1e-3
    # ... and a backlogged pipe runs AT line rate, minus the partial
    # progress still parked on incomplete tasks
    assert cum_work >= bw * 30 - float((size * M).sum())
    assert float(np.asarray(ls.Qt).sum()) > 0  # genuinely congested


@pytest.mark.parametrize("size,bw", [(5.0, 2.0), (1.0, 1.0), (7.0, 3.0),
                                     (2.0, 8.0)])
def test_transfer_latency_is_ceil_size_over_bw(size, bw):
    g = make_graph(
        dest=[0], bw=[bw], pt=[[1.0]], region=[1], size=[size],
        primary=[0],
    )
    ls = init_links(1, 1)
    ls, dl = step_links(ls, g, jnp.ones((1, 1)))
    slots = 1
    while float(dl[0, 0]) == 0.0:
        ls, dl = step_links(ls, g, jnp.zeros((1, 1)))
        slots += 1
        assert slots < 50
    assert slots == int(np.ceil(size / bw))


def test_infinite_bandwidth_delivers_same_slot():
    g = direct_graph(3, 2)
    ls = init_links(3, 2)
    dt = jnp.asarray(np.random.default_rng(0).integers(0, 9, (3, 2)),
                     jnp.float32)
    ls, dl = step_links(ls, g, dt)
    np.testing.assert_array_equal(np.asarray(dl), np.asarray(dt))
    assert float(np.abs(np.asarray(ls.Qt)).max()) == 0.0
    assert float(np.abs(np.asarray(ls.prog)).max()) == 0.0


def test_full_simulation_conserves_tasks():
    """In the full WAN simulation: dispatched == delivered + in flight,
    and cloud queues only ever receive delivered tasks."""
    fleet = build_network_fleet(["congested-uplink"], per_kind=2, Tc=48)
    res = simulate_fleet(
        NetworkAwareDPPPolicy(V=0.1), fleet, 60,
        jax.random.PRNGKey(1),
    )
    disp = np.asarray(res.dispatched).sum(axis=1)
    deliv = np.asarray(res.delivered).sum(axis=1)
    qt_end = np.asarray(res.Qt)[:, -1].sum(axis=(1, 2))
    np.testing.assert_allclose(disp, deliv + qt_end, rtol=0, atol=1e-3)


def test_full_simulation_cloud_queues_conserve_exactly():
    """Every slot, each lane's cloud backlog changes by exactly
    delivered - processed: the landing matmul loses and makes no task."""
    fleet = build_network_fleet(["congested-uplink"], per_kind=2, Tc=48)
    res = simulate_fleet(
        NetworkAwareDPPPolicy(V=0.1), fleet, 60, jax.random.PRNGKey(1),
    )
    qc = np.asarray(res.Qc, np.float64).sum(axis=(2, 3))   # [F, T]
    prev = np.concatenate([np.zeros_like(qc[:, :1]), qc[:, :-1]], axis=1)
    change = np.asarray(res.delivered, np.float64) - np.asarray(
        res.processed, np.float64)
    np.testing.assert_array_equal(qc - prev, change)


def _dot_precisions(fn, *args):
    return [
        eqn.params["precision"]
        for eqn in jax.make_jaxpr(fn)(*args).jaxpr.eqns
        if eqn.primitive.name == "dot_general"
    ]


@pytest.mark.parametrize("site", ["land_in_clouds", "static_route"])
def test_count_matmuls_ask_for_highest_precision(site):
    """Task counts go through one-hot matmuls; a TPU runs a DEFAULT
    precision f32 matmul as one bfloat16 pass, which rounds counts above
    256. Both sites must ask for HIGHEST (exact for integral counts)."""
    rng = np.random.default_rng(3)
    M, N = 4, 3
    spec, state, Ce, Cc = _random_instance(rng, M, N)
    g = direct_graph(M, N)
    if site == "land_in_clouds":
        fn = lambda d: land_in_clouds(d, g, N)  # noqa: E731
        args = (jnp.zeros((M, g.L)),)
    else:
        pol = StaticRoutePolicy(CarbonIntensityPolicy(V=0.05))
        fn = lambda Qt: pol(state, spec, Ce, Cc, None, None,  # noqa: E731
                            graph=g, Qt=Qt).dt
        args = (jnp.zeros((M, g.L)),)
    highest = jax.lax.Precision.HIGHEST
    assert _dot_precisions(fn, *args) == [(highest, highest)]


def test_sum_rows_in_order_adds_in_index_order():
    """((x0 + x1) + x2) + x3, whatever the compiler would pick for a
    reduce: the pairwise order gives 0 on these rows, index order 1."""
    x = jnp.asarray([[1e8], [1.0], [-1e8], [1.0]], jnp.float32)
    assert float(jax.jit(sum_rows_in_order)(x)[0]) == 1.0
    lanes = jnp.stack([x, x[::-1]])
    np.testing.assert_array_equal(
        np.asarray(jax.jit(jax.vmap(sum_rows_in_order))(lanes))[:, 0],
        [1.0, 0.0],
    )


# ------------------------------------------------- stacked-topology fleet


def test_registry_names():
    assert set(NETWORK_SCENARIOS) == {
        "star", "congested-uplink", "multi-region-uk-wan",
    }
    with pytest.raises(KeyError, match="registered"):
        build_network_fleet(["no-such-topology"], per_kind=1)
    # the advertised default kinds must actually stack (same L)
    assert build_network_fleet(per_kind=1, Tc=24).F == 2


def test_fleet_vmap_shape_dtype_contracts():
    """Stacked same-L topologies simulate in ONE jitted call with the
    documented shapes/dtypes on every NetSimResult field."""
    fleet = build_network_fleet(
        ["congested-uplink", "multi-region-uk-wan"], per_kind=3,
        M=4, N=3, Tc=24, seed=2,
    )
    F, M, N, T = fleet.F, 4, 3, 20
    L = fleet.graph.dest.shape[-1]
    assert F == 6 and L == 2 * N
    assert fleet.graph.pt.shape == (F, M, L)
    assert fleet.graph.dest.dtype == jnp.int32
    res = jax.jit(lambda k: simulate_fleet(
        NetworkAwareDPPPolicy(V=0.05), fleet, T, k
    ))(jax.random.PRNGKey(0))
    assert res.cum_emissions.shape == (F, T)
    assert res.Qe.shape == (F, T, M)
    assert res.Qc.shape == (F, T, M, N)
    assert res.Qt.shape == (F, T, M, L)
    assert res.energy_transfer.shape == (F, T)
    for field in res:
        if field is None:  # telemetry is off by default
            continue
        assert field.dtype == jnp.float32
        assert bool(jnp.isfinite(field).all())
    # cumulative emissions nondecreasing, distinct lanes distinct
    assert bool((jnp.diff(res.cum_emissions, axis=1) >= -1e-3).all())
    assert len(np.unique(np.asarray(res.cum_emissions[:, -1]))) > 1


def test_network_record_summary_matches_full():
    """record="summary" through the WAN simulator: scalar series
    bitwise, Qt/Qe/Qc collapse to length-1 final-state trajectories."""
    fleet = build_network_fleet(["congested-uplink"], per_kind=2, Tc=48)
    T, key = 30, jax.random.PRNGKey(4)
    pol = NetworkAwareDPPPolicy(V=0.1)
    full = simulate_fleet(pol, fleet, T, key)
    summ = simulate_fleet(pol, fleet, T, key, record="summary")
    for name in ("emissions", "cum_emissions", "dispatched", "delivered",
                 "processed", "energy_edge", "energy_transfer",
                 "energy_cloud"):
        np.testing.assert_array_equal(
            np.asarray(getattr(full, name)), np.asarray(getattr(summ, name)),
            err_msg=name,
        )
    assert summ.Qt.shape[1] == 1
    np.testing.assert_array_equal(
        np.asarray(full.Qt[:, -1]), np.asarray(summ.Qt[:, 0])
    )
    np.testing.assert_array_equal(
        np.asarray(full.Qc[:, -1]), np.asarray(summ.Qc[:, 0])
    )


def test_star_topology_runs():
    fleet = build_network_fleet(["star"], per_kind=2, Tc=24)
    res = simulate_fleet(
        NetworkAwareDPPPolicy(V=0.05), fleet, 15, jax.random.PRNGKey(0)
    )
    assert res.Qt.shape[-1] == 5  # one route per cloud
    assert bool(jnp.isfinite(res.cum_emissions).all())


def test_static_route_policy_uses_primary_routes():
    rng = np.random.default_rng(2)
    M, N = 6, 4
    spec, state, Ce, Cc = _random_instance(rng, M, N)
    g = make_graph(
        dest=np.repeat(np.arange(N), 2),
        bw=np.full(2 * N, 50.0),
        pt=np.ones((M, 2 * N), np.float32),
        region=np.repeat(np.arange(1, N + 1), 2),
        size=np.ones(M, np.float32),
        primary=2 * np.arange(N) + 1,  # the odd links
    )
    base = CarbonIntensityPolicy(V=0.05)
    pol = StaticRoutePolicy(base)
    act = pol(state, spec, Ce, Cc, None, None,
              graph=g, Qt=jnp.zeros((M, 2 * N)))
    d = np.asarray(base(state, spec, Ce, Cc, None, None).d)
    dt = np.asarray(act.dt)
    np.testing.assert_array_equal(dt[:, 1::2], d)   # primaries carry d
    assert (dt[:, 0::2] == 0).all()                 # alternates unused


def test_route_aware_beats_transfer_blind_on_congested_uplink():
    """The subsystem's acceptance property, test-sized: on the
    congested-uplink topology the route-aware policy emits less than
    the transfer-blind baseline while doing comparable work."""
    fleet = build_network_fleet(["congested-uplink"], per_kind=4, Tc=96,
                                seed=0)
    T, key = 120, jax.random.PRNGKey(0)
    blind = simulate_fleet(
        StaticRoutePolicy(CarbonIntensityPolicy(V=0.1)),
        fleet, T, key,
    )
    aware = simulate_fleet(
        NetworkAwareDPPPolicy(V=0.1), fleet, T, key,
    )
    em_blind = float(blind.cum_emissions[:, -1].mean())
    em_aware = float(aware.cum_emissions[:, -1].mean())
    assert em_aware < 0.95 * em_blind, (em_aware, em_blind)
    # comparable throughput: within 10% of the blind policy's work
    assert (float(aware.processed.sum()) >
            0.9 * float(blind.processed.sum()))


def test_stack_graphs_rejects_mixed_shapes():
    from repro.network import stack_graphs

    with pytest.raises(ValueError, match="share"):
        stack_graphs([direct_graph(3, 2), direct_graph(3, 4)])


def test_make_graph_rejects_degenerate_sizes_and_bandwidth():
    """size=0 would turn floor(prog/size) into NaN deep inside the
    scan; the validating constructor must refuse it up front."""
    ok = dict(dest=[0], bw=[1.0], pt=[[1.0]], region=[1], size=[1.0],
              primary=[0])
    make_graph(**ok)  # sanity
    with pytest.raises(ValueError, match="size"):
        make_graph(**{**ok, "size": [0.0]})
    with pytest.raises(ValueError, match="bw"):
        make_graph(**{**ok, "bw": [-1.0]})
