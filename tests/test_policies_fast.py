"""Exactness tests for the chunked top_k greedy fill (§Perf-policy).

`greedy_fill` is the repo's ONE fill engine, so these tests pin it to a
float32 numpy transcription of the sequential Algorithm-1 walk across
every variant (stop_at_first_unfit x literal_edge_budget x sort_key),
chunk sizes that force multi-trip chunking, batched-lane stacking, and
the degenerate corners (zero budget, all-nonnegative scores, single
type, zero caps). Rows of at most `LANE_WIDTH` items take the dense
one-hot path, longer rows the gather path: both are pinned to the
oracle and to each other, and `place_dispatch` to the scatter it
replaces."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # optional test dep: only the @given property test needs it
    import hypothesis.strategies as st
    from hypothesis import given, settings

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised on lean containers
    HAVE_HYPOTHESIS = False

from repro.core import policies
from repro.core.policies import (
    LANE_WIDTH,
    CarbonIntensityPolicy,
    QueueLengthPolicy,
    greedy_fill,
    literal_algorithm1,
    place_dispatch,
)
from repro.core.queueing import (
    NetworkSpec,
    NetworkState,
    init_state,
    is_feasible,
)
from repro.serve import make_serve_step

f32 = np.float32


def seq_fill(scores, e, caps, budget, stop=True, literal=False,
             sort_key=None):
    """float32 numpy transcription of the sequential scan fill the
    engine replaced -- the bit-parity oracle (same op order, so exact
    equality is the contract, not a tolerance)."""
    key = sort_key if sort_key is not None else scores / e
    order = np.argsort(key, kind="stable")
    P = f32(budget)
    stopped = False
    take = np.zeros_like(scores)
    for m in order:
        fits = f32(np.floor(P / e[m]))
        can = (fits > 0) and (scores[m] < 0) and (not stopped)
        t = f32(min(caps[m], fits)) if can else f32(0.0)
        take[m] = t
        if literal:
            if can:
                P = f32(P - f32(fits * e[m]))
            stopped = stopped or fits <= 0
        else:
            P = f32(P - f32(t * e[m]))
            if stop:
                stopped = stopped or fits <= 0
    return take


def _instance(rng, M):
    scores = rng.uniform(-100, 50, M).astype(f32)
    e = rng.uniform(0.5, 10, M).astype(f32)
    caps = rng.integers(0, 50, M).astype(f32)
    budget = f32(rng.uniform(1, 500))
    return scores, e, caps, budget


VARIANTS = [
    ("stop", dict(stop_at_first_unfit=True)),
    ("nostop", dict(stop_at_first_unfit=False)),
    ("literal", dict(literal_edge_budget=True)),
]


@pytest.mark.parametrize("chunk", [3, 64])
@pytest.mark.parametrize("variant", [v for v, _ in VARIANTS],
                         ids=[v for v, _ in VARIANTS])
@pytest.mark.parametrize("seed", range(10))
def test_fill_matches_sequential_oracle(seed, variant, chunk):
    kwargs = dict(VARIANTS)[variant]
    rng = np.random.default_rng(seed)
    M = int(rng.integers(2, 128))
    scores, e, caps, budget = _instance(rng, M)
    want = seq_fill(
        scores, e, caps, budget,
        stop=kwargs.get("stop_at_first_unfit", True),
        literal=kwargs.get("literal_edge_budget", False),
    )
    got = np.asarray(greedy_fill(
        jnp.asarray(scores), jnp.asarray(e), jnp.asarray(caps),
        jnp.asarray(budget), chunk=chunk, **kwargs,
    ))
    np.testing.assert_array_equal(want, got)


# Row lengths past LANE_WIDTH: the fill keeps its gathers and scatter.
GATHER_SIZES = (129, 200)


@pytest.mark.parametrize("chunk", [3, 64])
@pytest.mark.parametrize("variant", [v for v, _ in VARIANTS],
                         ids=[v for v, _ in VARIANTS])
@pytest.mark.parametrize("M", GATHER_SIZES)
def test_fill_gather_path_matches_sequential_oracle(M, variant, chunk):
    """The oracle test on rows too long for the dense path, with budgets
    large enough that chunk 3 takes several trips."""
    assert M > LANE_WIDTH
    kwargs = dict(VARIANTS)[variant]
    rng = np.random.default_rng(M)
    scores, e, caps, budget = _instance(rng, M)
    budget = f32(budget * 40)
    want = seq_fill(
        scores, e, caps, budget,
        stop=kwargs.get("stop_at_first_unfit", True),
        literal=kwargs.get("literal_edge_budget", False),
    )
    got = np.asarray(greedy_fill(
        jnp.asarray(scores), jnp.asarray(e), jnp.asarray(caps),
        jnp.asarray(budget), chunk=chunk, **kwargs,
    ))
    np.testing.assert_array_equal(want, got)


def _fill_property_case(M, budget, seed, variant, chunk, degenerate):
    kwargs = dict(VARIANTS)[variant]
    rng = np.random.default_rng(seed)
    scores = rng.uniform(-200, 50, M).astype(f32)
    e = rng.uniform(0.5, 20, M).astype(f32)
    caps = rng.integers(0, 100, M).astype(f32)
    budget = f32(budget)
    if degenerate == "zero-budget":
        budget = f32(0.0)
    elif degenerate == "nonneg-scores":
        scores = np.abs(scores)
    elif degenerate == "zero-caps":
        caps = np.zeros_like(caps)
    want = seq_fill(
        scores, e, caps, budget,
        stop=kwargs.get("stop_at_first_unfit", True),
        literal=kwargs.get("literal_edge_budget", False),
    )
    got = np.asarray(greedy_fill(
        jnp.asarray(scores), jnp.asarray(e), jnp.asarray(caps),
        jnp.asarray(budget), chunk=chunk, **kwargs,
    ))
    np.testing.assert_array_equal(want, got)


DEGENERATES = [None, "zero-budget", "nonneg-scores", "zero-caps"]


@pytest.mark.parametrize("degenerate", DEGENERATES,
                         ids=["plain"] + DEGENERATES[1:])
@pytest.mark.parametrize("variant", [v for v, _ in VARIANTS],
                         ids=[v for v, _ in VARIANTS])
def test_fill_degenerate_corners(variant, degenerate):
    """Deterministic slice of the property test (runs without
    hypothesis): each variant on each degenerate corner, with a chunk
    small enough to force multiple trips and M=1 single-type cases."""
    for seed, M, chunk in [(0, 1, 5), (1, 7, 2), (2, 33, 5), (3, 64, 64)]:
        _fill_property_case(M, 250.0, seed, variant, chunk, degenerate)


@pytest.mark.parametrize("degenerate", DEGENERATES,
                         ids=["plain"] + DEGENERATES[1:])
@pytest.mark.parametrize("variant", [v for v, _ in VARIANTS],
                         ids=[v for v, _ in VARIANTS])
def test_fill_degenerate_corners_gather_path(variant, degenerate):
    """The degenerate corners on rows past LANE_WIDTH (gather path)."""
    for seed, M, chunk in [(4, GATHER_SIZES[0], 3),
                           (5, GATHER_SIZES[1], 64)]:
        _fill_property_case(M, 2500.0, seed, variant, chunk, degenerate)


if HAVE_HYPOTHESIS:

    @given(
        M=st.integers(1, 40),
        budget=st.floats(0.0, 1e4),
        seed=st.integers(0, 2**31 - 1),
        variant=st.sampled_from([v for v, _ in VARIANTS]),
        chunk=st.sampled_from([1, 5, 64]),
        degenerate=st.sampled_from(DEGENERATES),
    )
    @settings(max_examples=120, deadline=None)
    def test_fill_property_all_variants(M, budget, seed, variant, chunk,
                                        degenerate):
        _fill_property_case(M, budget, seed, variant, chunk, degenerate)


def test_fill_sort_key_orders_the_walk():
    """QueueLengthPolicy's ordering contract: sort_key overrides the
    score/energy ratio (ties resolve by index, like the stable sort)."""
    rng = np.random.default_rng(17)
    for _ in range(20):
        M = int(rng.integers(1, 80))
        Q = rng.integers(0, 40, M).astype(f32)
        scores = np.where(Q > 0, -Q, f32(1.0)).astype(f32)
        e = rng.uniform(0.5, 10, M).astype(f32)
        budget = f32(rng.uniform(0, 400))
        want = seq_fill(scores, e, Q, budget, stop=False, sort_key=scores)
        got = np.asarray(greedy_fill(
            jnp.asarray(scores), jnp.asarray(e), jnp.asarray(Q),
            jnp.asarray(budget), stop_at_first_unfit=False,
            sort_key=jnp.asarray(scores), chunk=8,
        ))
        np.testing.assert_array_equal(want, got)


def test_fill_batched_lanes_match_per_lane():
    """The stacked [B, M] call (how policies fill edge + N clouds in one
    shot) equals B independent single-lane calls."""
    rng = np.random.default_rng(5)
    B, M = 9, 120
    S = rng.uniform(-100, 50, (B, M)).astype(f32)
    E = rng.uniform(0.5, 10, (B, M)).astype(f32)
    C = rng.integers(0, 50, (B, M)).astype(f32)
    P = rng.uniform(1, 500, B).astype(f32)
    full = np.asarray(greedy_fill(
        jnp.asarray(S), jnp.asarray(E), jnp.asarray(C), jnp.asarray(P),
        chunk=16,
    ))
    for b in range(B):
        one = np.asarray(greedy_fill(
            jnp.asarray(S[b]), jnp.asarray(E[b]), jnp.asarray(C[b]),
            jnp.asarray(P[b]), chunk=16,
        ))
        np.testing.assert_array_equal(full[b], one)


def test_fill_jits_and_vmaps():
    """The engine composes with jit and vmap (fleet lanes vmap whole
    simulations over it)."""
    rng = np.random.default_rng(2)
    M, B = 50, 6
    S = rng.uniform(-100, 50, (B, M)).astype(f32)
    E = rng.uniform(0.5, 10, (B, M)).astype(f32)
    C = rng.integers(0, 50, (B, M)).astype(f32)
    P = rng.uniform(1, 500, B).astype(f32)
    direct = np.asarray(greedy_fill(
        jnp.asarray(S), jnp.asarray(E), jnp.asarray(C), jnp.asarray(P),
        chunk=16,
    ))
    vmapped = np.asarray(jax.jit(jax.vmap(
        lambda s, e, c, p: greedy_fill(s, e, c, p, chunk=16)
    ))(jnp.asarray(S), jnp.asarray(E), jnp.asarray(C), jnp.asarray(P)))
    np.testing.assert_array_equal(direct, vmapped)


def _bits(x):
    """float32 bit patterns: equal only where the bits are (+0.0 differs
    from -0.0)."""
    return np.asarray(x, np.float32).view(np.int32)


def _tied_rows(rng, F, B, M):
    """[F, B, M] fill inputs with tied keys (score/energy takes a few
    integer values), zero caps, whole rows of non-negative scores and
    zero budgets."""
    e = rng.choice(np.array([0.5, 1.0, 2.5, 4.0], f32), (F, B, M))
    scores = (-rng.integers(-1, 4, (F, B, M)) * e).astype(f32)
    scores[:, 0] = np.abs(scores[:, 0])
    caps = rng.integers(0, 30, (F, B, M)).astype(f32)
    caps[rng.uniform(size=caps.shape) < 0.3] = 0.0
    budget = rng.uniform(0, 300, (F, B)).astype(f32)
    budget[:, 1] = 0.0
    return scores, e, caps, budget


@pytest.mark.parametrize("chunk", [3, 64])
@pytest.mark.parametrize("variant", ["stop", "nostop", "literal",
                                     "sort_key"])
def test_fill_dense_and_gather_paths_bit_identical(monkeypatch, variant,
                                                   chunk):
    """The dense one-hot path and the gather path return the same bits,
    jitted and vmapped over lanes as the fleet runs them, with the while
    loop (chunk < M) and without. Equal takes walked in the same order
    leave equal budgets, so the counts pin the whole walk."""
    kwargs = dict(VARIANTS, sort_key=dict(stop_at_first_unfit=False))[variant]
    rng = np.random.default_rng(chunk)
    F, B, M = 4, 6, 17
    assert M <= LANE_WIDTH
    args = [jnp.asarray(a) for a in _tied_rows(rng, F, B, M)]

    def fill():
        def one(s, e, c, p):
            key = s if variant == "sort_key" else None
            return greedy_fill(s, e, c, p, sort_key=key, chunk=chunk,
                               **kwargs)

        return np.asarray(jax.jit(jax.vmap(one))(*args))

    dense = fill()
    monkeypatch.setattr(policies, "LANE_WIDTH", 0)  # every row gathers
    gather = fill()
    np.testing.assert_array_equal(_bits(dense), _bits(gather))
    assert dense.any()


@pytest.mark.parametrize("W", [1, 5, LANE_WIDTH, *GATHER_SIZES])
def test_place_dispatch_equals_the_scatter(W):
    """The dispatch row's one-hot select equals
    `zeros.at[arange(M), cols].set(values)` bit for bit, a -0.0 value
    included, eagerly and jitted under vmap, at row widths up to and
    past one lane tile."""
    rng = np.random.default_rng(W)
    F, M = 3, 7
    like = jnp.zeros((F, M, W), jnp.float32)
    cols = jnp.asarray(rng.integers(0, W, (F, M)), jnp.int32)
    vals = rng.integers(0, 400, (F, M)).astype(f32)
    vals[:, 0] = -0.0
    vals = jnp.asarray(vals)
    want = jax.vmap(
        lambda z, c, v: z.at[jnp.arange(M), c].set(v))(like, cols, vals)
    got = jax.jit(jax.vmap(place_dispatch))(like, cols, vals)
    np.testing.assert_array_equal(_bits(got), _bits(want))
    np.testing.assert_array_equal(
        _bits(place_dispatch(like[0], cols[0], vals[0])), _bits(want[0]))


def _scatter_dispatch(like, cols, values):
    return jnp.zeros_like(like).at[jnp.arange(like.shape[0]), cols].set(
        values)


def test_served_step_dispatch_bits_equal_the_scatter(monkeypatch):
    """Inside the compiled served step, slot after slot, the dispatch row
    and the queues and task counts that follow from it carry the bits of
    the gather fill with a scattered dispatch row. Only the emissions, a
    sum whose order follows the fused layout, may differ, in the last
    bits."""
    from test_serve import _setup

    def serve(slots=12):
        pol, spec, cs, ar, key = _setup()
        rows, exact, emitted = [], [], []

        def spy(*args, **kw):  # the policy, its dispatch row sent out
            act = pol(*args, **kw)
            jax.debug.callback(lambda d: rows.append(np.array(d)), act.d)
            return act

        step = make_serve_step(spy, spec, cs, ar, key)
        state = init_state(spec.M, spec.N)
        for t in range(slots):
            state, metrics = step(state, jnp.int32(t))
            exact.append([_bits(np.array(x)) for x in
                          (*jax.tree.leaves(state), *metrics[1:])])
            emitted.append(float(metrics[0]))
        jax.effects_barrier()
        assert len(rows) == slots
        return rows, exact, emitted

    rows, exact, emitted = serve()
    monkeypatch.setattr(policies, "LANE_WIDTH", 0)  # every row gathers
    monkeypatch.setattr(policies, "place_dispatch", _scatter_dispatch)
    want_rows, want_exact, want_emitted = serve()
    for got, want in zip(rows, want_rows):
        np.testing.assert_array_equal(_bits(got), _bits(want))
    for got, want in zip(exact, want_exact):
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
    np.testing.assert_allclose(emitted, want_emitted, rtol=1e-6)
    assert sum(r.sum() for r in rows) > 0


@pytest.mark.parametrize("variant", [v for v, _ in VARIANTS],
                         ids=[v for v, _ in VARIANTS])
@pytest.mark.parametrize("seed", range(6))
def test_policy_matches_literal_algorithm1_all_variants(seed, variant):
    """Full-policy semantics against the pure-Python Algorithm 1
    transcription, for every fill variant (small instances keep the
    float64 oracle and the float32 engine in exact agreement)."""
    rng = np.random.default_rng(seed + 50)
    M, N = int(rng.integers(1, 8)), int(rng.integers(1, 6))
    spec = NetworkSpec(
        pe=rng.uniform(1.0, 8.0, M).astype(f32),
        pc=rng.uniform(2.0, 100.0, (M, N)).astype(f32),
        Pe=float(rng.uniform(20, 200)),
        Pc=rng.uniform(50, 500, N).astype(f32),
    )
    state = NetworkState(
        Qe=jnp.asarray(rng.integers(0, 200, M).astype(f32)),
        Qc=jnp.asarray(rng.integers(0, 200, (M, N)).astype(f32)),
    )
    Ce = jnp.float32(rng.uniform(0, 700))
    Cc = jnp.asarray(rng.uniform(0, 700, N).astype(f32))
    V = 0.05
    stop = variant != "nostop"
    literal = variant == "literal"
    pol = CarbonIntensityPolicy(
        V=V, stop_at_first_unfit=stop, literal_edge_budget=literal,
        fill_chunk=4,
    )
    got = pol(state, spec, Ce, Cc, None, None)
    want = literal_algorithm1(
        state, spec, Ce, Cc, V,
        stop_at_first_unfit=stop, literal_edge_budget=literal,
    )
    np.testing.assert_array_equal(np.asarray(got.d), np.asarray(want.d))
    np.testing.assert_array_equal(np.asarray(got.w), np.asarray(want.w))


@pytest.mark.parametrize("chunk", [8, 64])
def test_policy_parity_across_chunk_sizes(chunk):
    """fill_chunk is a pure performance knob: actions are identical
    whatever the chunking (multi-trip vs single-trip)."""
    rng = np.random.default_rng(3)
    M, N = 256, 32
    spec = NetworkSpec(
        pe=rng.uniform(1, 8, M).astype(f32),
        pc=rng.uniform(2, 100, (M, N)).astype(f32),
        Pe=5e3,
        Pc=rng.uniform(1e3, 5e4, N).astype(f32),
    )
    state = NetworkState(
        Qe=jnp.asarray(rng.integers(0, 500, M).astype(f32)),
        Qc=jnp.asarray(rng.integers(0, 500, (M, N)).astype(f32)),
    )
    Ce = jnp.float32(300.0)
    Cc = jnp.asarray(rng.uniform(0, 700, N).astype(f32))
    a = CarbonIntensityPolicy(V=0.05, fill_chunk=512)(
        state, spec, Ce, Cc, None, None
    )
    b = CarbonIntensityPolicy(V=0.05, fill_chunk=chunk)(
        state, spec, Ce, Cc, None, None
    )
    np.testing.assert_array_equal(np.asarray(a.d), np.asarray(b.d))
    np.testing.assert_array_equal(np.asarray(a.w), np.asarray(b.w))
    assert bool(is_feasible(spec, b))


def test_queue_length_policy_feasible_and_chunk_invariant():
    rng = np.random.default_rng(9)
    M, N = 64, 8
    spec = NetworkSpec(
        pe=rng.uniform(1, 8, M).astype(f32),
        pc=rng.uniform(2, 100, (M, N)).astype(f32),
        Pe=2e3,
        Pc=rng.uniform(5e2, 1e4, N).astype(f32),
    )
    state = NetworkState(
        Qe=jnp.asarray(rng.integers(0, 500, M).astype(f32)),
        Qc=jnp.asarray(rng.integers(0, 500, (M, N)).astype(f32)),
    )
    a = QueueLengthPolicy(fill_chunk=7)(
        state, spec, jnp.float32(0.0), jnp.zeros(N), None, None
    )
    b = QueueLengthPolicy(fill_chunk=64)(
        state, spec, jnp.float32(0.0), jnp.zeros(N), None, None
    )
    np.testing.assert_array_equal(np.asarray(a.d), np.asarray(b.d))
    np.testing.assert_array_equal(np.asarray(a.w), np.asarray(b.w))
    assert bool(is_feasible(spec, a))


def test_policy_feasible_on_extreme_budgets():
    """Huge budgets used to hit f32 cumsum rounding in the old prefix
    formulation; the chunked engine replays the sequential op order, so
    exact parity with the oracle holds even here -- and feasibility and
    surrogate quality must hold regardless."""
    from repro.core import dpp

    rng = np.random.default_rng(4)
    M, N = 512, 16
    spec = NetworkSpec(
        pe=rng.uniform(1, 8, M).astype(f32),
        pc=rng.uniform(2, 100, (M, N)).astype(f32),
        Pe=5e7,
        Pc=np.full(N, 5e7, f32),
    )
    state = NetworkState(
        Qe=jnp.asarray(rng.integers(0, 1000, M).astype(f32)),
        Qc=jnp.asarray(rng.integers(0, 1000, (M, N)).astype(f32)),
    )
    Ce = jnp.float32(300.0)
    Cc = jnp.asarray(rng.uniform(0, 700, N).astype(f32))
    act = CarbonIntensityPolicy(V=0.05)(state, spec, Ce, Cc, None, None)
    assert bool(is_feasible(spec, act))
    v = float(dpp.surrogate_value(state, spec, act, Ce, Cc, 0.05))
    assert np.isfinite(v)
