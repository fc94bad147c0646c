"""In-flight transfer dynamics over a LinkGraph.

State is an aggregate pipe model per (task type, route):

  Qt   [M,L] -- tasks in flight (integral counts, float32 like the
                queues in core/queueing.py)
  prog [M,L] -- transfer progress in size-units toward the in-flight
                pool (fractional; < size[m] once completed tasks are
                removed)

Each slot a route drains up to bw[l] size-units, shared across task
types in proportion to their remaining work (processor sharing); a task
lands in its destination's Qc once a full size[m] of progress is booked
against it. Consequences, all covered by tests/test_network.py:

  * a single type-m task on an otherwise idle route l needs
    ceil(size[m] / bw[l]) slots edge->cloud -- transfer latency;
  * sustained throughput of route l is bw[l] size-units/slot -- the
    bandwidth cap (in tasks/slot: bw[l]/size[m]);
  * Qt only ever changes by integer dispatches in and integer
    deliveries out -- no task is lost or duplicated in flight;
  * bw = inf delivers everything the same slot with zero residual
    progress, which is what makes the degenerate direct_graph
    bit-identical to the link-free simulator.

Deliveries are aggregated per destination cloud with a one-hot matmul
at HIGHEST precision, so the whole step is dense linear algebra that
scans and vmaps. The precision is what keeps it exact for integral
counts: a TPU runs a DEFAULT-precision f32 matmul as one bfloat16 pass,
which rounds any count above 256 to 8 significant bits.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core.queueing import DTYPE, NetworkSpec, edge_energy

from repro.network.graph import LinkGraph
from repro.telemetry.profile import phase

Array = jax.Array

_TINY = 1e-30  # drain-ratio denominator guard (no NaN even at bw=inf)


class LinkState(NamedTuple):
    Qt: Array    # [M, L] tasks in flight per (type, route)
    prog: Array  # [M, L] size-units transferred toward the pool


class NetAction(NamedTuple):
    """One slot of WAN scheduling: dt routes dispatches, w processes."""

    dt: Array  # [M, L] tasks dispatched onto route l
    w: Array   # [M, N] tasks processed at cloud n


def init_links(M: int, L: int, dtype=DTYPE) -> LinkState:
    z = jnp.zeros((M, L), dtype)
    return LinkState(Qt=z, prog=z)


def sum_rows_in_order(x: Array) -> Array:
    """x[0] + x[1] + ... + x[M-1], added in index order. A reduce
    leaves its order of additions to the compiler, which on a TPU
    derives it from the array's layout; two programs that run this step
    with different neighbours (the Pallas score kernel pins the layout
    of its operands, the jnp reference does not) would then round the
    fractional transfer demand differently, and one ulp of progress
    flips a `floor` into a different delivery. The loop fixes the order
    for every program and every backend; it is the order the CPU's
    reduce already uses, so CPU results do not move."""
    return jax.lax.fori_loop(1, x.shape[0], lambda m, acc: acc + x[m], x[0])


def step_links(
    ls: LinkState, graph: LinkGraph, dt: Array, bw_scale: Array | None = None
) -> Tuple[LinkState, Array]:
    """Injects dt [M,L] new transfers, drains one slot of bandwidth,
    returns (next state, delivered [M,L] task counts).

    `bw_scale` [L] (repro.faults link flaps) scales each route's
    bandwidth for this slot. The guarded `where` keeps a hard flap
    (scale 0) on an infinite-bandwidth route at exactly 0 instead of
    inf * 0 = NaN; scale 1.0 is a bitwise no-op (inf * 1.0 = inf).

    The phase scope labels the link step in profiler traces
    (repro.telemetry §profiling, metadata only)."""
    with phase("transfer_step"):
        if bw_scale is None:
            bw = graph.bw
        else:
            bw = jnp.where(bw_scale > 0.0, graph.bw * bw_scale, 0.0)
        Qt = ls.Qt + dt
        demand = Qt * graph.size[:, None] - ls.prog      # [M, L] work left
        total = sum_rows_in_order(demand)                # [L]
        ratio = jnp.minimum(1.0, bw / jnp.maximum(total, _TINY))
        prog = ls.prog + demand * ratio
        # Clamp at 0 on both sides of the delivery: cancellation in
        # `prog - delivered*size` can leave prog at -eps, and
        # floor(-eps/size) = -1 would then "deliver" a NEGATIVE task --
        # un-delivering work onto an empty route and driving Qc below
        # zero (the telemetry conservation monitor caught exactly this
        # leak). Where prog >= 0 both clamps are exact no-ops, so the
        # direct-graph parity anchor is untouched.
        delivered = jnp.minimum(
            Qt,
            jnp.maximum(jnp.floor(prog / graph.size[:, None]), 0.0),
        )
        Qt = Qt - delivered
        prog = jnp.maximum(
            prog - delivered * graph.size[:, None], 0.0
        )
        return LinkState(Qt=Qt, prog=prog), delivered


def land_in_clouds(delivered: Array, graph: LinkGraph, N: int) -> Array:
    """Aggregates route deliveries [M,L] into cloud arrivals [M,N],
    exactly (see the module doc on the matmul precision)."""
    onehot = jax.nn.one_hot(graph.dest, N, dtype=delivered.dtype)  # [L, N]
    return jnp.matmul(delivered, onehot, precision=jax.lax.Precision.HIGHEST)


def transfer_energy(graph: LinkGraph, dt: Array) -> Array:
    """Per-route transfer energy of a dispatch action. Returns [L]."""
    return jnp.sum(dt * graph.pt, axis=0)


def network_emissions(
    spec: NetworkSpec,
    graph: LinkGraph,
    action: NetAction,
    Ce: Array,
    Cc: Array,
) -> Array:
    """End-to-end carbon of one slot: edge dispatch energy at the edge
    intensity, transfer energy priced in each route's carbon region
    (charged when the transfer starts -- same slot the policy scored
    it), compute energy at the destination intensities."""
    pe, pc, _, _ = spec.as_arrays()
    row = jnp.concatenate([Ce[None], Cc])                 # [N+1]
    Ct = row[graph.region]                                # [L]
    return (
        Ce * edge_energy(pe, action.dt)
        + jnp.sum(Ct * transfer_energy(graph, action.dt))
        + jnp.sum(Cc * jnp.sum(action.w * pc, axis=0))
    )
