"""Fused WAN route-scoring kernel (network subsystem, sibling of
carbon_score.py).

`NetworkAwareDPPPolicy` ranks (task-type, route, cloud) triples over a
link graph. With every route's destination fixed by the graph, the
[M, N, L] lattice collapses through the dest gather into an [M, L]
cost matrix

    rc[m,l] = V*Ct[l]*pt[m,l]      (transfer carbon on route l)
            + extra[m,l]           (optional anticipated compute carbon)
            + Qt[m,l]              (in-flight backlog on route l)
            + Qc[m, dest[l]]       (destination cloud backlog)

plus the per-type dispatch score b[m] = V*Ce*pe[m] + min_l rc[m,l]
- Qe[m] and the best route l1[m] = argmin_l rc[m,l]. At fleet scale
(M types x L routes per lane, many lanes) this is a memory-bound O(ML)
sweep: one HBM read of the four [M,L] operands produces the cost matrix
AND the per-row (min, argmin) reduction in a single pass. Grid tiles L
sequentially (innermost) with running min/argmin accumulators in VMEM,
exactly the carbon_scores pattern, so blockwise results are bit-identical
to the jnp reference (min is exact; argmin uses strict < so the first
occurrence wins across blocks, matching jnp.argmin).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.compat import VMEM, CompilerParams

POS_INF = 1e30


def _kernel(
    qt_ref, pt_ref, qcr_ref, extra_ref,  # [bm,bl] each
    qe_ref, pe_ref,                      # [bm,1] each
    vct_ref,                             # [1,bl]
    vce_ref,                             # [1,1]
    rc_ref, l1_ref, b_ref,               # [bm,bl] [bm,1] [bm,1]
    min_ref, arg_ref,                    # VMEM scratch [bm,1] each
    *, bl: int, nl: int,
):
    i_l = pl.program_id(1)

    @pl.when(i_l == 0)
    def _init():
        min_ref[...] = jnp.full_like(min_ref, POS_INF)
        arg_ref[...] = jnp.zeros_like(arg_ref)

    qt = qt_ref[...].astype(jnp.float32)      # [bm, bl]
    pt = pt_ref[...].astype(jnp.float32)
    qcr = qcr_ref[...].astype(jnp.float32)
    extra = extra_ref[...].astype(jnp.float32)
    vct = vct_ref[...].astype(jnp.float32)    # [1, bl]
    V_Ce = vce_ref[0, 0]

    # Same op order as route_scores_ref -- the bit-parity contract.
    rc = vct * pt + extra + qt + qcr
    rc_ref[...] = rc.astype(rc_ref.dtype)

    # running row min/argmin of rc
    blk_min = jnp.min(rc, axis=1, keepdims=True)              # [bm,1]
    # first column attaining the min (jnp.argmin's tie rule), spelled
    # out: Mosaic's argmin does not promise which tied index it returns
    col = jax.lax.broadcasted_iota(jnp.int32, rc.shape, 1).astype(jnp.float32)
    blk_arg = jnp.min(jnp.where(rc == blk_min, col, float(bl)), axis=1,
                      keepdims=True) + i_l * bl
    better = blk_min < min_ref[...]
    min_ref[...] = jnp.where(better, blk_min, min_ref[...])
    arg_ref[...] = jnp.where(better, blk_arg, arg_ref[...])

    @pl.when(i_l == nl - 1)
    def _finish():
        qe = qe_ref[...].astype(jnp.float32)  # [bm,1]
        pe = pe_ref[...].astype(jnp.float32)
        l1_ref[...] = arg_ref[...].astype(jnp.int32)
        b_ref[...] = (V_Ce * pe + min_ref[...] - qe).astype(b_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_l", "interpret")
)
def route_scores(
    Qt: jax.Array,     # [M, L] in-flight transfer queue
    pt: jax.Array,     # [M, L] transfer energy per task on route l
    Qcr: jax.Array,    # [M, L] destination backlog, Qc[:, dest]
    extra: jax.Array,  # [M, L] anticipated destination compute carbon
    Qe: jax.Array,     # [M]
    pe: jax.Array,     # [M]
    VCt: jax.Array,    # [L] V * link-region intensity
    V_Ce: jax.Array,   # scalar: V * Ce(t)
    *,
    block_m: int = 256,
    block_l: int = 256,
    interpret: bool = False,
):
    """Returns (route_costs [M,L] f32, l1 [M] int32, b [M] f32).

    Arbitrary M/L: inputs are padded up to the block grid. Padded Qcr
    entries are +inf so a padded route can never win the row argmin;
    padded rows/columns are sliced off the outputs before returning.
    """
    M, L = Qt.shape
    bm, bl = min(block_m, M), min(block_l, L)
    Mp, Lp = -(-M // bm) * bm, -(-L // bl) * bl
    if (Mp, Lp) != (M, L):
        dm, dl = Mp - M, Lp - L
        Qt = jnp.pad(Qt, ((0, dm), (0, dl)))
        pt = jnp.pad(pt, ((0, dm), (0, dl)))
        Qcr = jnp.pad(Qcr, ((0, dm), (0, dl)), constant_values=POS_INF)
        extra = jnp.pad(extra, ((0, dm), (0, dl)))
        Qe = jnp.pad(Qe, (0, dm))
        pe = jnp.pad(pe, (0, dm), constant_values=1.0)
        VCt = jnp.pad(VCt, (0, dl))
    nm, nl = Mp // bm, Lp // bl
    rc, l1, b = pl.pallas_call(
        functools.partial(_kernel, bl=bl, nl=nl),
        grid=(nm, nl),
        in_specs=[
            pl.BlockSpec((bm, bl), lambda m, l: (m, l)),
            pl.BlockSpec((bm, bl), lambda m, l: (m, l)),
            pl.BlockSpec((bm, bl), lambda m, l: (m, l)),
            pl.BlockSpec((bm, bl), lambda m, l: (m, l)),
            pl.BlockSpec((bm, 1), lambda m, l: (m, 0)),
            pl.BlockSpec((bm, 1), lambda m, l: (m, 0)),
            pl.BlockSpec((1, bl), lambda m, l: (0, l)),
            pl.BlockSpec((1, 1), lambda m, l: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bl), lambda m, l: (m, l)),
            pl.BlockSpec((bm, 1), lambda m, l: (m, 0)),
            pl.BlockSpec((bm, 1), lambda m, l: (m, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Mp, Lp), jnp.float32),
            jax.ShapeDtypeStruct((Mp, 1), jnp.int32),
            jax.ShapeDtypeStruct((Mp, 1), jnp.float32),
        ],
        scratch_shapes=[
            VMEM((bm, 1), jnp.float32),
            VMEM((bm, 1), jnp.float32),
        ],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="route_scores",
    )(
        Qt, pt, Qcr, extra, Qe[:, None], pe[:, None], VCt[None, :],
        jnp.asarray(V_Ce, jnp.float32)[None, None],
    )
    return rc[:M, :L], l1[:M, 0], b[:M, 0]
