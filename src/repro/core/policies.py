"""Scheduling policies.

* CarbonIntensityPolicy -- the paper's Algorithm 1 (drift-plus-penalty
  greedy). Faithful semantics, expressed through the chunked top_k
  greedy fill so it jits / vmaps / scans at any M.
* QueueLengthPolicy -- the paper's baseline: longest edge queue -> shortest
  cloud queue; clouds always process their longest queues; carbon-blind.
* ExactDPPPolicy -- beyond-paper: solves the per-slot surrogate (19)
  exactly with the unbounded-knapsack DP (small instances; used to
  measure the greedy's optimality gap).
* RandomPolicy -- feasible random actions (stress/property tests).

All policies share the signature:
    policy(state, spec, Ce, Cc, arrivals, key) -> Action
`arrivals` is observed *before* acting (Algorithm 1 line "Observe ...
a_m(t)"): the paper's queue update (7) applies d to the pre-arrival queue;
policies only clip d by the current Qe, matching the pseudocode.

Every policy also accepts a `fault_view=` kwarg (a repro.faults
FaultView, passed by the faulted simulators) and deliberately ignores
it: base policies model the fair-weather scheduler, and all graceful
degradation lives in repro.faults.guard.StalenessGuardPolicy. The same
convention covers `deadline_view=` (a repro.deadlines DeadlineView,
passed by deadline-threaded simulators): base policies ignore it, and
urgency/deferral behavior lives in repro.deadlines.policy.

Notes vs. the paper's pseudocode (documented in DESIGN.md):
  * The edge branch of Algorithm 1 prints `P <- P - floor(P/pe)*pe` while
    the cloud branch subtracts the *scheduled* energy `w*pc`. We treat the
    edge line as a typo (it would burn budget that was never used when
    Qe < floor(P/pe)) and subtract d*pe. Set `literal_edge_budget=True`
    to reproduce the printed text exactly.
  * `stop_at_first_unfit=True` reproduces the pseudocode's `break` when
    the current type no longer fits the remaining budget. The improved
    variant (False) keeps scanning cheaper types -- a strictly better
    knapsack fill (see DESIGN.md §Perf-policy).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import dpp
from repro.core.queueing import Action, NetworkSpec, NetworkState
from repro.telemetry.profile import phase

Array = jax.Array

# Fill rows of at most this many items (one vreg's lane width) take the
# dense one-hot path. On a TPU v5e it ran the policy step 1.7-38x faster
# than the gathers at M = 5 to 200, and 8% slower at M = 4096 (PERF.md):
# the crossover lies between, unmeasured, so the rule stays at one tile.
LANE_WIDTH = 128


def greedy_fill(
    scores: Array,       # [M] or [B, M] per-item score (negative == take)
    unit_energy: Array,  # [M] or [B, M] energy per item
    max_items: Array,    # [M] or [B, M] cap per item (queue lengths)
    budget: Array,       # scalar or [B] energy budget per lane
    *,
    stop_at_first_unfit: bool = True,
    literal_edge_budget: bool = False,
    sort_key: Array | None = None,
    chunk: int = 64,
) -> Array:
    """The repo's one greedy knapsack fill (Algorithm 1, both halves).

    Semantics (per lane, items visited in increasing `sort_key` order,
    ties broken by index -- `sort_key` defaults to scores/unit_energy):
      fits = floor(P / e); take min(cap, fits) of every item whose score
      is negative, decrementing P by take*e. `stop_at_first_unfit`
      reproduces the pseudocode's `break` at the first fits == 0;
      `literal_edge_budget` reproduces the printed edge line verbatim
      (P -= fits*e, always stopping at the first unfit -- the variant
      ignores `stop_at_first_unfit`, like the pseudocode it mirrors).

    Implementation (§Perf-policy): only items with score < 0 can ever
    take or stop the walk before the takes end -- with the default
    ratio key they sort strictly before every non-negative item, so the
    walk over non-negative items is a no-op tail. Each while_loop trip
    pulls the `chunk` cheapest unprocessed negative-score items with
    lax.top_k (ties resolve to the lowest index == the stable order)
    and walks them with a lax.scan whose body is the sequential
    reference op-for-op, so counts are bit-identical to a full
    sequential pass by construction. The loop exits when a lane stops,
    runs out of negative items, or P drops below the cheapest remaining
    energy (nothing downstream can fit). One trip almost always
    suffices: taking `chunk` items costs >= chunk * min_e energy.

    Batched: stack lanes on a leading axis ([B, M] inputs, [B] budget)
    and every trip issues ONE top_k / ONE scan for all lanes -- that is
    how the policies fill the edge row and all N clouds per slot in a
    single call. Callers passing `sort_key` must keep the contract that
    negative-score items sort before non-negative ones (any key does
    when negative items get negative keys, like -queue-length).

    Caps are treated as integer-valued (queue lengths); the budget walk
    takes cap items whenever floor(P/e) >= cap.

    Rows of at most `LANE_WIDTH` (128) items apply each trip's top_k
    permutation as a dense one-hot select (scope `repro.fill_dense`):
    `onehot[b, j, m] = idx[b, j] == m`, each gathered value a sum over
    m of `where(onehot, x, 0)`, each take added back as a sum over j.
    On a TPU a short row is padded to a lane tile anyway, and an index
    gather or scatter on it costs several ns per element, while the
    compares, selects and sums of a [B, k, M] one-hot ride the vector
    units. Longer rows keep the gathers and the flattened scatter: the
    one-hot grows as k * M, and at M = 4096 the dense path measured
    slower (see `LANE_WIDTH`). Both paths return the same counts, bit
    for bit: every output element is one selected term plus +0.0
    terms, and adding +0.0 is exact (it could only turn a -0.0 into
    +0.0, and takes, caps and energies are never -0.0, nor does the
    sign of a zero score change `score < 0`).
    """
    # The phase scope is profiler metadata only (repro.telemetry
    # §profiling): it labels the fill ops in xprof/Perfetto traces and
    # never changes the computation.
    with phase("greedy_fill"):
        return _greedy_fill(
            scores, unit_energy, max_items, budget,
            stop_at_first_unfit=stop_at_first_unfit,
            literal_edge_budget=literal_edge_budget,
            sort_key=sort_key, chunk=chunk,
        )


def _greedy_fill(
    scores, unit_energy, max_items, budget, *,
    stop_at_first_unfit, literal_edge_budget, sort_key, chunk,
):
    scores = jnp.asarray(scores)
    single = scores.ndim == 1
    if single:
        scores = scores[None]
        unit_energy = jnp.asarray(unit_energy)[None]
        max_items = jnp.asarray(max_items)[None]
        budget = jnp.reshape(jnp.asarray(budget), (1,))
        if sort_key is not None:
            sort_key = jnp.asarray(sort_key)[None]
    B, M = scores.shape
    if int(chunk) < 1:
        raise ValueError(
            f"chunk={chunk!r} must be >= 1 (a zero-size chunk would "
            "loop forever processing nothing)"
        )
    k = min(int(chunk), M)
    stops = stop_at_first_unfit or literal_edge_budget

    key = sort_key if sort_key is not None else scores / unit_energy
    mkey0 = jnp.where(scores < 0, key, jnp.inf)
    P0 = jnp.broadcast_to(jnp.asarray(budget, jnp.float32), (B,))

    def active(P, stopped, mkey):
        alive = jnp.isfinite(mkey)
        min_e = jnp.min(
            jnp.where(alive, unit_energy, jnp.inf), axis=-1
        )
        return (~stopped) & jnp.any(alive, axis=-1) & (P >= min_e)

    def step(carry, item):
        P, stopped = carry
        e_j, s_j, cap_j, live_j = item
        fits = jnp.floor(P / e_j)
        live = live_j & (~stopped)
        can = live & (fits > 0.0) & (s_j < 0)
        t_j = jnp.where(can, jnp.minimum(cap_j, fits), 0.0)
        if literal_edge_budget:
            P = jnp.where(can, P - fits * e_j, P)
        else:
            P = P - t_j * e_j  # t_j == 0 is an exact no-op
        if stops:
            stopped = stopped | (live & (fits <= 0.0))
        return (P, stopped), t_j

    if M <= LANE_WIDTH:
        # Dense one-hot permute (see the docstring).
        def onehot(idx):  # [B, k, M]: walk position j holds item idx[j]
            return idx[..., :, None] == jnp.arange(M, dtype=idx.dtype)

        def gather(x, idx):
            with phase("fill_dense"):
                return jnp.sum(jnp.where(onehot(idx), x[..., None, :], 0),
                               axis=-1)

        def scatter_add(t, idx, v):
            with phase("fill_dense"):
                return t + jnp.sum(jnp.where(onehot(idx), v[..., :, None], 0),
                                   axis=-2)

        def mark_done(mkey, idx):
            with phase("fill_dense"):
                return jnp.where(jnp.any(onehot(idx), axis=-2), jnp.inf, mkey)
    else:
        # Per-lane scatters flattened into ONE row-major scatter on
        # [B*M]: bit-identical to the per-row vmap formulation (indices
        # stay unique), one scatter instead of a batched one, and --
        # because an unbatched scatter is all checkify's OOB rule can
        # instrument -- the only formulation `analysis.sanitize` can
        # lift with index_checks enabled.
        def _rows(i):
            return (i + M * jnp.arange(B, dtype=i.dtype)[:, None]).ravel()

        def gather(x, idx):
            return jnp.take_along_axis(x, idx, axis=-1)

        def scatter_add(t, idx, v):
            return t.ravel().at[_rows(idx)].add(v.ravel()).reshape(B, M)

        def mark_done(mkey, idx):
            return mkey.ravel().at[_rows(idx)].set(jnp.inf).reshape(B, M)

    def walk_chunk(P, stopped, mkey, gate):
        neg, idx = jax.lax.top_k(-mkey, k)  # k smallest keys, stable
        valid = jnp.isfinite(neg) & gate
        e_s = gather(unit_energy, idx)
        s_s = gather(scores, idx)
        cap_s = gather(max_items, idx)
        (P, stopped), takes = jax.lax.scan(
            step, (P, stopped), (e_s.T, s_s.T, cap_s.T, valid.T)
        )
        return P, stopped, idx, takes.T

    stopped0 = jnp.zeros((B,), bool)
    if k == M:
        # One trip provably covers every item: skip the while_loop and
        # its exit bookkeeping entirely (the common small-M / fleet-lane
        # case; per-slot cost matches the old argsort+scan fill).
        _, _, idx, takes = walk_chunk(P0, stopped0, mkey0, True)
        counts = scatter_add(jnp.zeros_like(scores), idx, takes)
        return counts[0] if single else counts

    def trip(carry):
        P, stopped, take, mkey, act = carry
        P, stopped, idx, takes = walk_chunk(P, stopped, mkey, act[:, None])
        take = scatter_add(take, idx, takes)
        mkey = jnp.where(act[:, None], mark_done(mkey, idx), mkey)
        return P, stopped, take, mkey, active(P, stopped, mkey)

    carry = jax.lax.while_loop(
        lambda c: jnp.any(c[4]),
        trip,
        (P0, stopped0, jnp.zeros_like(scores), mkey0,
         active(P0, stopped0, mkey0)),
    )
    counts = carry[2]
    return counts[0] if single else counts


def place_dispatch(like: Array, cols: Array, values: Array) -> Array:
    """`zeros_like(like).at[arange(M), cols].set(values)`: row m of the
    [M, W] result holds values[m] at column cols[m] and zeros elsewhere
    (each type's dispatch at its chosen cloud or route). Built as a
    dense one-hot select, which writes the same [M, W] bytes as the
    zeros the scatter would start from and which a TPU runs on its
    vector units instead of as a scatter; the result is the same, bit
    for bit."""
    hit = cols[:, None] == jnp.arange(like.shape[1], dtype=cols.dtype)
    return jnp.where(hit, values[:, None], 0).astype(like.dtype)


@dataclasses.dataclass(frozen=True)
class CarbonIntensityPolicy:
    """Paper Algorithm 1: carbon-intensity based drift-plus-penalty greedy.

    The edge dispatch row and all N cloud processing rows go through ONE
    stacked `greedy_fill` call per slot (chunked top_k engine, see
    DESIGN.md §Perf-policy); `fill_chunk` sizes the per-trip top_k.

    score_backend selects how the per-slot score pass (n1, b, c) is
    computed:
      * "reference" -- plain jnp (default; works everywhere, vmaps).
      * "pallas"    -- the fused kernels.carbon_score.carbon_scores
        kernel: one HBM sweep of Qc/pc produces the c-matrix and the
        per-row (min, argmin) reduction. With score_interpret=None
        (auto) it is the compiled kernel on a TPU and the jnp reference
        elsewhere (kernels/ops.py); it pads internally, so any M/N
        works. Under jit both backends produce bit-identical
        scores, hence bit-identical actions (tests/test_score_backend).
    """

    V: float = 0.05
    stop_at_first_unfit: bool = True
    literal_edge_budget: bool = False
    fill_chunk: int = 64
    score_backend: str = "reference"
    score_block_m: int = 256
    score_block_n: int = 256
    score_interpret: bool | None = None

    def _fill_all(self, b, c, pe, pc, Qe, Qc, Pe, Pc):
        """Edge dispatch + N cloud fills as one stacked [N+1, M] greedy
        fill (shared with NetworkAwareDPPPolicy, whose dispatch scores
        differ but whose fill semantics are exactly Algorithm 1's).
        Returns (d_counts [M], w [M, N])."""
        if self.literal_edge_budget:
            # The literal pseudocode variant only exists for the edge
            # branch; clouds keep the corrected budget accounting.
            d_counts = greedy_fill(
                b, pe, Qe, Pe,
                literal_edge_budget=True, chunk=self.fill_chunk,
            )
            w = greedy_fill(
                c.T, pc.T, Qc.T, Pc,
                stop_at_first_unfit=self.stop_at_first_unfit,
                chunk=self.fill_chunk,
            ).T
            return d_counts, w
        counts = greedy_fill(
            jnp.concatenate([b[None, :], c.T], axis=0),
            jnp.concatenate([pe[None, :], pc.T], axis=0),
            jnp.concatenate([Qe[None, :], Qc.T], axis=0),
            jnp.concatenate([jnp.reshape(Pe, (1,)), Pc], axis=0),
            stop_at_first_unfit=self.stop_at_first_unfit,
            chunk=self.fill_chunk,
        )
        return counts[0], counts[1:].T

    def _scores(self, state, pe, pc, Ce, Cc, V):
        """Score pass: (c [M,N], n1 [M], b [M]) via the selected backend.
        The phase scope labels it in profiler traces (metadata only)."""
        with phase("policy_score"):
            if self.score_backend == "pallas":
                from repro.kernels import ops

                # The kernel contract takes pre-scaled intensities:
                # V*Cc for the c-matrix and V*Ce for the b-vector (same
                # op order as the reference, so results agree bitwise
                # under jit).
                return ops.carbon_scores(
                    state.Qc, pc, state.Qe, pe, V * Cc, V * Ce,
                    block_m=self.score_block_m,
                    block_n=self.score_block_n,
                    interpret=self.score_interpret,
                )
            if self.score_backend != "reference":
                raise ValueError(
                    f"unknown score_backend {self.score_backend!r}"
                )
            from repro.kernels import ref

            return ref.carbon_scores_ref(
                state.Qc, pc, state.Qe, pe, V * Cc, V * Ce
            )

    def __call__(
        self,
        state: NetworkState,
        spec: NetworkSpec,
        Ce: Array,
        Cc: Array,
        arrivals: Array,
        key: Array | None = None,
        fault_view=None,
        deadline_view=None,
    ) -> Action:
        del arrivals, key, fault_view, deadline_view
        pe, pc, Pe, Pc = spec.as_arrays()
        V = jnp.asarray(self.V, jnp.float32)

        c, n1, b = self._scores(state, pe, pc, Ce, Cc, V)
        d_counts, w = self._fill_all(
            b, c, pe, pc, state.Qe, state.Qc, Pe, Pc
        )
        d = place_dispatch(state.Qc, n1, d_counts)
        return Action(d=d, w=w)


@dataclasses.dataclass(frozen=True)
class LookaheadDPPPolicy(CarbonIntensityPolicy):
    """Receding-horizon drift-plus-penalty (beyond-paper, forecast
    subsystem). Plans against an [H, N+1] intensity forecast and
    executes only the first slot: the myopic scores are recomputed with
    *deferral-penalized* intensities

        C_eff = C_now + defer_weight * max(0, C_now - Cmin)
        Cmin  = min_h forecast[h] / discount**h         (h = 0..H-1)

    so a trough h slots ahead must beat the present by 1/discount**h
    before it raises the bar for acting now -- the discounting absorbs
    forecast-error growth and the queue-holding cost of waiting. Row 0
    of the forecast is overwritten with the observed (Ce, Cc), hence
    H=1 gives Cmin = C_now, zero penalty, and *bit-identical* actions
    to CarbonIntensityPolicy on either score backend (the modified
    intensities feed the identical score/fill pipeline). See DESIGN.md
    §Receding-horizon lookahead.

    With no forecast supplied (forecast=None) the policy degrades to
    the myopic parent -- simulate() only threads forecasts when a
    forecaster is given.
    """

    H: int = 8
    discount: float = 0.98
    defer_weight: float = 2.0

    def effective_intensities(
        self, Ce: Array, Cc: Array, forecast: Array | None
    ) -> Tuple[Array, Array]:
        if forecast is None or self.H <= 0:
            return Ce, Cc
        if forecast.shape[0] < self.H:
            raise ValueError(
                f"forecast covers {forecast.shape[0]} slots but the policy "
                f"plans over H={self.H}: configure the forecaster with "
                f"H >= {self.H} (silently planning short would mislabel "
                "every lookahead result)"
            )
        f = forecast[: self.H].astype(jnp.float32)
        f = f.at[0].set(jnp.concatenate([Ce[None], Cc]))
        g = jnp.asarray(self.discount, jnp.float32) ** jnp.arange(
            f.shape[0], dtype=jnp.float32
        )
        cmin = jnp.min(f / g[:, None], axis=0)  # [N+1]
        w = jnp.asarray(self.defer_weight, jnp.float32)
        Ce_eff = Ce + w * jnp.maximum(0.0, Ce - cmin[0])
        Cc_eff = Cc + w * jnp.maximum(0.0, Cc - cmin[1:])
        return Ce_eff, Cc_eff

    def __call__(
        self,
        state: NetworkState,
        spec: NetworkSpec,
        Ce: Array,
        Cc: Array,
        arrivals: Array,
        key: Array | None = None,
        forecast: Array | None = None,
        fault_view=None,
        deadline_view=None,
    ) -> Action:
        del fault_view, deadline_view
        Ce_eff, Cc_eff = self.effective_intensities(Ce, Cc, forecast)
        return super().__call__(state, spec, Ce_eff, Cc_eff, arrivals, key)


@dataclasses.dataclass(frozen=True)
class QueueLengthPolicy:
    """Paper §V baseline: queue-length based, carbon-blind.

    Edge: longest edge queues dispatch first, each type to its shortest
    cloud queue, as many as energy allows. Clouds: longest cloud queues
    process first, as many as energy allows. Same stacked greedy_fill
    engine as Algorithm 1, ordered by -queue-length (sort_key) instead
    of score-per-energy, never stopping at an unfit type.
    """

    fill_chunk: int = 64

    def __call__(
        self,
        state: NetworkState,
        spec: NetworkSpec,
        Ce: Array,
        Cc: Array,
        arrivals: Array,
        key: Array | None = None,
        fault_view=None,
        deadline_view=None,
    ) -> Action:
        del Ce, Cc, arrivals, key, fault_view, deadline_view
        pe, pc, Pe, Pc = spec.as_arrays()
        n1 = jnp.argmin(state.Qc, axis=1)

        # Longest-queue-first: order by -Q (only types with waiting
        # tasks), take as many as the remaining energy allows.
        scores = jnp.concatenate(
            [
                jnp.where(state.Qe > 0, -state.Qe, 1.0)[None, :],
                jnp.where(state.Qc > 0, -state.Qc, 1.0).T,
            ],
            axis=0,
        )
        counts = greedy_fill(
            scores,
            jnp.concatenate([pe[None, :], pc.T], axis=0),
            jnp.concatenate([state.Qe[None, :], state.Qc.T], axis=0),
            jnp.concatenate([jnp.reshape(Pe, (1,)), Pc], axis=0),
            stop_at_first_unfit=False,
            sort_key=scores,
            chunk=self.fill_chunk,
        )
        d = place_dispatch(state.Qc, n1, counts[0])
        return Action(d=d, w=counts[1:].T)


@dataclasses.dataclass(frozen=True)
class RandomPolicy:
    """Feasible uniformly-random actions (tests / stress)."""

    def __call__(
        self,
        state: NetworkState,
        spec: NetworkSpec,
        Ce: Array,
        Cc: Array,
        arrivals: Array,
        key: Array,
        fault_view=None,
        deadline_view=None,
    ) -> Action:
        del Ce, Cc, arrivals, fault_view, deadline_view
        pe, pc, Pe, Pc = spec.as_arrays()
        kd, kw = jax.random.split(key)
        # Random fractions of per-type feasible maxima, scaled to respect
        # the shared budget by dividing across types.
        M, N = spec.M, spec.N
        fd = jax.random.uniform(kd, (M, N), dtype=jnp.float32)
        cap_d = jnp.minimum(
            state.Qe[:, None] / N, (Pe / (M * N)) / pe[:, None]
        )
        d = jnp.floor(fd * jnp.maximum(cap_d, 0.0))
        fw = jax.random.uniform(kw, (M, N), dtype=jnp.float32)
        cap_w = jnp.minimum(state.Qc, (Pc[None, :] / M) / pc)
        w = jnp.floor(fw * jnp.maximum(cap_w, 0.0))
        return Action(d=d, w=w)


@dataclasses.dataclass(frozen=True)
class ExactDPPPolicy:
    """Beyond-paper: exact per-slot minimizer of (19) via unbounded-
    knapsack DP over a discretized energy grid. Exponential-free but
    O(M * budget/gcd) -- use on small instances to measure the greedy gap.
    """

    V: float = 0.05
    grid: int = 512  # energy discretization cells per knapsack

    def __call__(
        self,
        state: NetworkState,
        spec: NetworkSpec,
        Ce: Array,
        Cc: Array,
        arrivals: Array,
        key: Array | None = None,
        fault_view=None,
        deadline_view=None,
    ) -> Action:
        del arrivals, key, fault_view, deadline_view
        from repro.core.knapsack import bounded_knapsack_min

        pe, pc, Pe, Pc = spec.as_arrays()
        V = jnp.asarray(self.V, jnp.float32)

        n1 = jnp.argmin(state.Qc, axis=1)
        Qc_n1 = jnp.take_along_axis(state.Qc, n1[:, None], axis=1)[:, 0]
        b = V * Ce * pe + Qc_n1 - state.Qe
        d_counts = bounded_knapsack_min(b, pe, state.Qe, Pe, self.grid)
        d = place_dispatch(state.Qc, n1, d_counts)

        c = dpp.processing_scores(state, pc, Cc, V)
        w = jax.vmap(
            lambda c_n, pc_n, Qc_n, Pc_n: bounded_knapsack_min(
                c_n, pc_n, Qc_n, Pc_n, self.grid
            ),
            in_axes=(1, 1, 1, 0),
            out_axes=1,
        )(c, pc, state.Qc, Pc)
        return Action(d=d, w=w)


def literal_algorithm1(
    state, spec, Ce, Cc, V,
    stop_at_first_unfit=True, literal_edge_budget=False,
):
    """Pure-Python transcription of Algorithm 1 (numpy, data-dependent
    control flow). Oracle for tests: the vectorized policy must match.
    `literal_edge_budget=True` reproduces the printed edge line
    (`P <- P - floor(P/pe)*pe`, always breaking at the first unfit),
    mirroring CarbonIntensityPolicy's flag of the same name."""
    import numpy as np

    pe = np.asarray(spec.pe, np.float64)
    pc = np.asarray(spec.pc, np.float64)
    Qe = np.asarray(state.Qe, np.float64).copy()
    Qc = np.asarray(state.Qc, np.float64).copy()
    Ce = float(Ce)
    Cc = np.asarray(Cc, np.float64)
    M, N = pc.shape
    d = np.zeros((M, N))
    w = np.zeros((M, N))

    n1 = np.argmin(Qc, axis=1)
    b = V * Ce * pe + Qc[np.arange(M), n1] - Qe
    order = np.argsort(b / pe, kind="stable")
    P = float(spec.Pe)
    for m in order:
        fits = np.floor(P / pe[m])
        if fits <= 0:
            if stop_at_first_unfit or literal_edge_budget:
                break
            continue
        if b[m] < 0:
            take = min(Qe[m], fits)
            d[m, n1[m]] = take
            P -= (fits if literal_edge_budget else take) * pe[m]

    for n in range(N):
        c = V * Cc[n] * pc[:, n] - Qc[:, n]
        order = np.argsort(c / pc[:, n], kind="stable")
        P = float(np.asarray(spec.Pc)[n])
        for m in order:
            fits = np.floor(P / pc[m, n])
            if fits <= 0:
                if stop_at_first_unfit:
                    break
                continue
            if c[m] < 0:
                take = min(Qc[m, n], fits)
                w[m, n] = take
                P -= take * pc[m, n]
    return Action(d=jnp.asarray(d, jnp.float32), w=jnp.asarray(w, jnp.float32))
