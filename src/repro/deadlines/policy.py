"""Deadline-aware policies: scan-compatible wrappers over the DPP score.

Three escalation styles, all driven by the per-slot `DeadlineView` the
deadline-threaded simulators pass as `deadline_view=`:

* SlackThresholdPolicy -- the mirror image of StalenessGuardPolicy:
  where the guard DECAYS V toward pure backpressure as the carbon
  signal goes stale, this escalates the *effective* V toward pure
  backpressure as slack -> 0. Implemented as score post-processing
  (subtracting the urgency share of the carbon term reproduces the
  score at V_eff = (1 - u) * V exactly), so both score backends and
  the single stacked greedy fill are reused untouched.
* EDDPolicy -- earliest-due-date: carbon-blind dispatch ordered by
  slack (most urgent type first), longest-queue cloud processing. The
  classical deadline baseline the carbon-aware policies must beat on
  emissions while matching on misses.
* WaitAwhilePolicy -- suspend/resume deferral: act only when the
  current slot ranks among the J cheapest slots of the forecast inside
  each task's admissible window min(W, slack); otherwise suspend by
  lifting scores to >= 0, which `greedy_fill` never takes. Due work
  overrides the gate (resume), so deferral never converts into a miss
  by itself.

All three degrade gracefully: with `deadline_view=None` (or no
forecast, for WaitAwhile) they ARE their parent policy, so the
infinite-deadline bitwise anchor extends to them.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro.core.policies import (
    Action,
    LookaheadDPPPolicy,
    greedy_fill,
    place_dispatch,
)

# Slack values are capped here before entering sort keys so that +inf
# (empty queue / no deadline) stays orderable and arithmetic-safe.
_SLACK_CAP = 1e6


@dataclasses.dataclass(frozen=True)
class SlackThresholdPolicy(LookaheadDPPPolicy):
    """Urgency-escalated drift-plus-penalty.

    Per-type urgency u = clip(1 - slack / slack_scale, 0, 1) shrinks
    the carbon term of the DPP score to its (1 - u) share -- exactly
    the score evaluated at V_eff = (1 - u) * V, so u = 1 (slack 0) is
    pure backpressure and u = 0 (slack >= slack_scale, or +inf) is the
    parent policy bit-for-bit (the subtraction is an exact -0.0).
    Types at their last service opportunity (`due`) additionally get a
    `due_push` subtracted from their dispatch score, putting them at
    the head of the greedy fill regardless of carbon.
    """

    slack_scale: float = 4.0
    due_push: float = 1e6

    def __call__(
        self,
        state,
        spec,
        Ce,
        Cc,
        arrivals,
        key=None,
        forecast=None,
        fault_view=None,
        deadline_view=None,
    ) -> Action:
        del fault_view
        if deadline_view is None:
            return super().__call__(
                state, spec, Ce, Cc, arrivals, key, forecast=forecast
            )
        pe, pc, Pe, Pc = spec.as_arrays()
        V = jnp.asarray(self.V, jnp.float32)
        Ce_eff, Cc_eff = self.effective_intensities(Ce, Cc, forecast)
        c, n1, b = self._scores(state, pe, pc, Ce_eff, Cc_eff, V)

        # clip() maps slack = +inf through 1 - inf = -inf to exactly
        # 0.0: no-deadline types never see a perturbed score.
        u = jnp.clip(
            1.0 - deadline_view.slack
            / jnp.asarray(self.slack_scale, jnp.float32),
            0.0,
            1.0,
        )
        b = b - u * (V * Ce_eff) * pe
        c = c - u[:, None] * (V * Cc_eff)[None, :] * pc
        b = b - deadline_view.due * jnp.asarray(self.due_push, jnp.float32)

        d_counts, w = self._fill_all(
            b, c, pe, pc, state.Qe, state.Qc, Pe, Pc
        )
        d = place_dispatch(state.Qc, n1, d_counts)
        return Action(d=d, w=w)


@dataclasses.dataclass(frozen=True)
class EDDPolicy:
    """Earliest-due-date baseline: carbon-blind, deadline-greedy.

    Edge: every type with waiting tasks dispatches in ascending-slack
    order (to its shortest cloud queue), as many as energy allows.
    Clouds: longest queues process first, as in QueueLengthPolicy.
    Without a deadline_view all occupied types tie (slack +inf), and
    the fill degrades to stable type-index order.
    """

    fill_chunk: int = 64

    def __call__(
        self,
        state,
        spec,
        Ce,
        Cc,
        arrivals,
        key=None,
        fault_view=None,
        deadline_view=None,
    ) -> Action:
        del Ce, Cc, arrivals, key, fault_view
        pe, pc, Pe, Pc = spec.as_arrays()
        n1 = jnp.argmin(state.Qc, axis=1)

        slack = (
            deadline_view.slack
            if deadline_view is not None
            else jnp.full_like(state.Qe, jnp.inf)
        )
        # Occupied types get a strictly negative key ordered by slack
        # (greedy_fill's contract: only negative keys are ever taken).
        edge = jnp.where(
            state.Qe > 0,
            jnp.minimum(slack, _SLACK_CAP) - (_SLACK_CAP + 1.0),
            1.0,
        )
        scores = jnp.concatenate(
            [edge[None, :], jnp.where(state.Qc > 0, -state.Qc, 1.0).T],
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
class WaitAwhilePolicy(LookaheadDPPPolicy):
    """Suspend/resume deferral: act in the J cheapest admissible slots.

    Per type, the admissible window is min(window, slack) slots of the
    [H, N+1] forecast (a task may not defer past its deadline). The
    edge dispatch for type m suspends unless the CURRENT edge intensity
    ranks among the J cheapest admissible slots (strictly-cheaper
    count < J); cloud n's processing of type m suspends by the same
    rank test on cloud n's forecast column. Suspension lifts the score
    to max(score, 0) -- a non-negative score that `greedy_fill` never
    takes and that cannot trip its early stop. Due types resume
    unconditionally and get the `due_push` head-of-line boost, so
    deferral alone never expires work.

    Without a forecast or a deadline_view the gate has nothing to rank
    against and the policy IS its lookahead parent.
    """

    J: int = 2
    due_push: float = 1e6

    def __call__(
        self,
        state,
        spec,
        Ce,
        Cc,
        arrivals,
        key=None,
        forecast=None,
        fault_view=None,
        deadline_view=None,
    ) -> Action:
        del fault_view
        if deadline_view is None or forecast is None or self.H <= 0:
            return super().__call__(
                state, spec, Ce, Cc, arrivals, key, forecast=forecast
            )
        pe, pc, Pe, Pc = spec.as_arrays()
        V = jnp.asarray(self.V, jnp.float32)
        Ce_eff, Cc_eff = self.effective_intensities(Ce, Cc, forecast)
        c, n1, b = self._scores(state, pe, pc, Ce_eff, Cc_eff, V)

        f = forecast[: self.H].astype(jnp.float32)
        f = f.at[0].set(jnp.concatenate([Ce[None], Cc]))  # [H, N+1]
        wait = jnp.minimum(deadline_view.window, deadline_view.slack)
        h = jnp.arange(f.shape[0], dtype=jnp.float32)
        adm = h[None, :] <= wait[:, None]  # [M, H]; +inf -> all True

        # Edge gate: rank of now among admissible edge-intensity slots.
        fE = f[:, 0]
        rank_e = jnp.sum(
            (fE[None, :] < fE[0]) & adm, axis=1
        )  # [M]
        due = deadline_view.due > 0.0
        act_edge = (rank_e < self.J) | due
        b = jnp.where(act_edge, b, jnp.maximum(b, 0.0))
        b = b - deadline_view.due * jnp.asarray(self.due_push, jnp.float32)

        # Cloud gate: per (type, cloud) rank on that cloud's column.
        fC = f[:, 1:]  # [H, N]
        rank_c = jnp.sum(
            (fC[None, :, :] < fC[0][None, None, :]) & adm[:, :, None],
            axis=1,
        )  # [M, N]
        act_cloud = (rank_c < self.J) | due[:, None]
        c = jnp.where(act_cloud, c, jnp.maximum(c, 0.0))

        d_counts, w = self._fill_all(
            b, c, pe, pc, state.Qe, state.Qc, Pe, Pc
        )
        d = place_dispatch(state.Qc, n1, d_counts)
        return Action(d=d, w=w)


__all__ = [
    "SlackThresholdPolicy",
    "EDDPolicy",
    "WaitAwhilePolicy",
]
