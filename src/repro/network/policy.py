"""Route-aware scheduling policies over a LinkGraph.

* NetworkAwareDPPPolicy -- the drift-plus-penalty dispatch extended to
  the route lattice: instead of "each type to its emptiest cloud", each
  type goes to the (route, cloud) pair minimizing

      rc[m,l] = V*Ct[l]*pt[m,l]                (transfer carbon, route l)
              + route_compute_weight * V*Cc[dest[l]]*pc[m,dest[l]]
              + Qt[m,l] + Qc[m,dest[l]]        (in-flight + dest drift)

  with the dispatch score b[m] = V*Ce*pe[m] + min_l rc[m,l] - Qe[m]
  feeding the identical greedy energy fill as Algorithm 1. The Qt term
  is what makes the policy congestion-aware: a saturated route's
  backlog prices it out, no explicit bandwidth constraint needed in the
  score pass. Subclassing LookaheadDPPPolicy means an [H, N+1] forecast
  (PR 3) deferral-penalizes the whole intensity row -- link carbon
  regions included -- before any score is computed; H=1 (the default)
  is exactly myopic.

  On the degenerate `direct_graph` (one infinite-bandwidth,
  zero-transfer-carbon link per cloud) rc collapses bitwise onto the
  Qc column sweep, so actions are bit-identical to CarbonIntensityPolicy
  on both score backends -- the subsystem's regression anchor.

* StaticRoutePolicy -- transfer-blind adapter: runs any edge->cloud
  policy unchanged and ships its dispatches down the graph's primary
  routes, ignoring Qt and link carbon. The baseline the route-aware
  policy must beat on congested topologies (bench_network_routing).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.policies import LookaheadDPPPolicy, place_dispatch
from repro.core.queueing import NetworkSpec, NetworkState
from repro.network.graph import LinkGraph
from repro.network.transfer import NetAction
from repro.telemetry.profile import phase

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class NetworkAwareDPPPolicy(LookaheadDPPPolicy):
    """Joint route+schedule DPP. Inherits V / greedy-fill options /
    score_backend from CarbonIntensityPolicy and the receding-horizon
    machinery (H, discount, defer_weight) from LookaheadDPPPolicy;
    H defaults to 1 here (myopic) so the policy only plans ahead when
    explicitly configured with a horizon AND a forecaster.

    route_compute_weight anticipates the destination's compute carbon at
    dispatch time (end-to-end ranking). It defaults to 0 because strict
    DPP semantics charge compute carbon when the cloud processes (the
    cloud-side scores already see it) -- a nonzero weight is a bias that
    pays off when destination queues are short-lived; it breaks the
    degenerate-graph parity by design.
    """

    H: int = 1
    route_compute_weight: float = 0.0

    def _route_scores(self, state, Qt, graph, pe, pc, Ce, Cc, V):
        """Score pass over the route lattice via the selected backend:
        (rc [M,L], l1 [M], b [M]). The phase scope labels it in
        profiler traces (metadata only)."""
        with phase("route_score"):
            row = jnp.concatenate([Ce[None], Cc])         # [N+1]
            VCt = V * row[graph.region]                   # [L]
            Qcr = jnp.take(state.Qc, graph.dest, axis=1)  # [M, L]
            if self.route_compute_weight:
                pcr = jnp.take(pc, graph.dest, axis=1)
                extra = (
                    jnp.asarray(self.route_compute_weight, jnp.float32)
                    * (V * Cc)[graph.dest][None, :] * pcr
                )
            else:
                extra = jnp.zeros_like(Qcr)
            if self.score_backend == "pallas":
                from repro.kernels import ops

                return ops.route_scores(
                    Qt, graph.pt, Qcr, extra, state.Qe, pe, VCt,
                    V * Ce, block_m=self.score_block_m,
                    block_l=self.score_block_n,
                    interpret=self.score_interpret,
                )
            if self.score_backend != "reference":
                raise ValueError(
                    f"unknown score_backend {self.score_backend!r}"
                )
            from repro.kernels import ref

            return ref.route_scores_ref(
                Qt, graph.pt, Qcr, extra, state.Qe, pe, VCt, V * Ce
            )

    def __call__(
        self,
        state: NetworkState,
        spec: NetworkSpec,
        Ce: Array,
        Cc: Array,
        arrivals: Array,
        key: Array | None = None,
        *,
        graph: LinkGraph,
        Qt: Array,
        forecast: Array | None = None,
        fault_view=None,
        deadline_view=None,
    ) -> NetAction:
        del arrivals, key, fault_view, deadline_view
        Ce_eff, Cc_eff = self.effective_intensities(Ce, Cc, forecast)
        pe, pc, Pe, Pc = spec.as_arrays()
        V = jnp.asarray(self.V, jnp.float32)

        # Cloud half: unchanged Algorithm 1 (the c-matrix). Edge half:
        # dispatch each type onto its best route. Both fills run as the
        # parent's one stacked [N+1, M] greedy_fill call.
        c, _, _ = self._scores(state, pe, pc, Ce_eff, Cc_eff, V)
        _, l1, b = self._route_scores(
            state, Qt, graph, pe, pc, Ce_eff, Cc_eff, V
        )
        d_counts, w = self._fill_all(
            b, c, pe, pc, state.Qe, state.Qc, Pe, Pc
        )
        dt = place_dispatch(Qt, l1, d_counts)
        return NetAction(dt=dt, w=w)


@dataclasses.dataclass(frozen=True)
class StaticRoutePolicy:
    """Transfer-blind adapter: `inner` decides (d, w) as if clouds were
    directly attached; every dispatch to cloud n rides the graph's
    primary route. Qt, bandwidth and link carbon are invisible to it --
    exactly what a scheduler without the WAN layer would do."""

    inner: Callable

    def __call__(
        self,
        state: NetworkState,
        spec: NetworkSpec,
        Ce: Array,
        Cc: Array,
        arrivals: Array,
        key: Array | None = None,
        *,
        graph: LinkGraph,
        Qt: Array,
        forecast: Array | None = None,
        fault_view=None,
        deadline_view=None,
    ) -> NetAction:
        del Qt, fault_view
        kwargs = {}
        if forecast is not None:
            kwargs["forecast"] = forecast
        if deadline_view is not None:
            kwargs["deadline_view"] = deadline_view
        act = self.inner(state, spec, Ce, Cc, arrivals, key, **kwargs)
        onehot = jax.nn.one_hot(graph.primary, graph.L, dtype=act.d.dtype)
        # HIGHEST keeps the counts exact on a TPU (network/transfer.py)
        dt = jnp.matmul(act.d, onehot, precision=jax.lax.Precision.HIGHEST)
        return NetAction(dt=dt, w=act.w)
