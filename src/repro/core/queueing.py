"""Virtual queueing network model (paper §III).

State is a pytree of two integer arrays:
  Qe  [M]    -- edge queue m: type-m tasks waiting at the edge server
  Qc  [M,N]  -- cloud queue (m,n): type-m tasks waiting at cloud n

An *action* is (d, w):
  d  [M,N]   -- number of type-m tasks dispatched edge -> cloud n (eq. 1)
  w  [M,N]   -- number of type-m tasks processed at cloud n       (eq. 2)

Dynamics are eqs. (7)-(8) of the paper. Everything here is pure JAX so the
whole network simulates under jax.lax.scan and vmaps over policy
hyper-parameters (e.g. V sweeps).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.telemetry.profile import phase

Array = jax.Array

# Queue lengths are kept in float32 on purpose: counts are integral by
# construction (all updates add/subtract integers) but float32 keeps the
# whole simulator in one dtype for TPU-friendly vectorization; exactness
# holds up to 2**24 which is far beyond any stable queue length here.
DTYPE = jnp.float32


class NetworkState(NamedTuple):
    """Virtual queueing network state at one time slot."""

    Qe: Array  # [M]   edge queues
    Qc: Array  # [M,N] cloud queues

    @property
    def M(self) -> int:
        return self.Qe.shape[-1]

    @property
    def N(self) -> int:
        return self.Qc.shape[-1]


class Action(NamedTuple):
    """A scheduling action for one time slot (d, w >= 0 integers)."""

    d: Array  # [M,N] dispatch counts
    w: Array  # [M,N] processing counts


@dataclasses.dataclass(frozen=True)
class NetworkSpec:
    """Static problem data (paper §II).

    Attributes:
      pe:  [M]   energy for the edge to send one type-m task (kWh)
      pc:  [M,N] energy for cloud n to process one type-m task (kWh)
      Pe:  scalar edge energy budget per slot (kWh)
      Pc:  [N]   per-cloud energy budget per slot (kWh)
    """

    pe: Array
    pc: Array
    Pe: float
    Pc: Array

    @property
    def M(self) -> int:
        return self.pc.shape[0]

    @property
    def N(self) -> int:
        return self.pc.shape[1]

    def as_arrays(self):
        return (
            jnp.asarray(self.pe, DTYPE),
            jnp.asarray(self.pc, DTYPE),
            jnp.asarray(self.Pe, DTYPE),
            jnp.asarray(self.Pc, DTYPE),
        )


def init_state(M: int, N: int, dtype=DTYPE) -> NetworkState:
    return NetworkState(Qe=jnp.zeros((M,), dtype), Qc=jnp.zeros((M, N), dtype))


def edge_energy(spec_pe: Array, d: Array) -> Array:
    """Total edge energy of a dispatch action (eq. 1)."""
    return jnp.sum(d * spec_pe[:, None])


def cloud_energy(spec_pc: Array, w: Array) -> Array:
    """Per-cloud energy of a processing action (eq. 2). Returns [N]."""
    return jnp.sum(w * spec_pc, axis=0)


def emissions(spec: NetworkSpec, action: Action, Ce: Array, Cc: Array) -> Array:
    """Carbon emissions C(t) of an action (eq. 5).

    Ce: scalar edge carbon intensity; Cc: [N] cloud carbon intensities.
    """
    pe, pc, _, _ = spec.as_arrays()
    with phase("emissions"):
        return Ce * edge_energy(pe, action.d) + jnp.sum(
            Cc * cloud_energy(pc, action.w)
        )


def is_feasible(spec: NetworkSpec, action: Action, atol: float = 1e-3) -> Array:
    """Checks energy constraints (3)-(4) and integrality/non-negativity."""
    pe, pc, Pe, Pc = spec.as_arrays()
    ok_e = edge_energy(pe, action.d) <= Pe + atol
    ok_c = jnp.all(cloud_energy(pc, action.w) <= Pc + atol)
    ok_nonneg = jnp.all(action.d >= 0) & jnp.all(action.w >= 0)
    ok_int = jnp.all(action.d == jnp.round(action.d)) & jnp.all(
        action.w == jnp.round(action.w)
    )
    return ok_e & ok_c & ok_nonneg & ok_int


def step(state: NetworkState, action: Action, arrivals: Array) -> NetworkState:
    """One slot of queue dynamics, eqs. (7)-(8).

    Note the paper's order: departures are bounded by the *current* queue
    via max(.,0); arrivals land after service. d may exceed Qe in which
    case only Qe tasks actually move, yet the full d lands in Qc -- the
    paper's virtual-queue semantics (eq. 8 adds d[m,n] verbatim). Policies
    in this repo never overshoot (they clip to queue lengths), but the
    dynamics stay faithful to the equations.
    """
    with phase("queue_update"):
        d_sum = jnp.sum(action.d, axis=1)  # [M]
        Qe = jnp.maximum(state.Qe - d_sum, 0.0) + arrivals
        Qc = jnp.maximum(state.Qc - action.w, 0.0) + action.d
    return NetworkState(Qe=Qe, Qc=Qc)


def lyapunov(state: NetworkState) -> Array:
    """L(t) = 1/2 (sum Qe^2 + sum Qc^2), eq. (15)."""
    return 0.5 * (jnp.sum(state.Qe**2) + jnp.sum(state.Qc**2))


def drift_bound_B(spec: NetworkSpec, a_max: Array) -> Array:
    """A constant B satisfying eq. (18) for all feasible actions.

    From (18): 2B >= sum a_m^2 + sum (sum_n d)^2 + sum d^2 + sum w^2.
    Feasibility bounds each term: sum_n d[m,:] <= Pe/pe[m] (all budget on
    type m), d[m,n] <= Pe/pe[m], w[m,n] <= Pc[n]/pc[m,n]. We use those
    worst cases; tighter bounds only shrink the B/V gap of Theorem 1.
    """
    pe, pc, Pe, Pc = spec.as_arrays()
    a_max = jnp.asarray(a_max, DTYPE)
    d_row_max = Pe / pe  # [M]
    w_max = Pc[None, :] / pc  # [M,N]
    two_B = (
        jnp.sum(a_max**2)
        + jnp.sum(d_row_max**2)  # (sum_n d)^2 worst case
        + jnp.sum(d_row_max**2)  # sum_n d^2 <= (sum_n d)^2
        + jnp.sum(w_max**2)
    )
    return 0.5 * two_B
