"""Paper-specific Pallas kernel: fused drift-plus-penalty scores +
row-argmin for planetary-scale scheduling instances.

Algorithm 1 needs, per task type m:
  n1(m)   = argmin_n Qc[m,n]
  b(m)    = V*Ce*pe[m] + Qc[m, n1(m)] - Qe[m]    (dispatch score)
and the full processing-score matrix c[m,n] = V*Cc[n]*pc[m,n] - Qc[m,n].

At the paper's scale (M=5, N=5) this is trivial; at framework scale
(M = thousands of workload classes x N = thousands of clouds/pods) the
score pass is a memory-bound O(MN) sweep worth fusing: one HBM read of
Qc/pc produces both the c-scores and the per-row (min, argmin) reduction
without a second pass. Grid tiles N (sequential innermost) with running
min/argmin accumulators in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.compat import VMEM, CompilerParams

POS_INF = 1e30


def _kernel(
    qc_ref, pc_ref, qe_ref, pe_ref, cc_ref,  # [bm,bn] [bm,bn] [bm,1] [bm,1] [1,bn]
    vce_ref,                                  # [1,1] scalar-prefetch-free SMEM-ish
    c_ref, n1_ref, b_ref,                     # [bm,bn] [bm,1] [bm,1]
    min_ref, arg_ref,                         # VMEM scratch [bm,1] each
    *, bn: int, nn: int,
):
    i_n = pl.program_id(1)

    @pl.when(i_n == 0)
    def _init():
        min_ref[...] = jnp.full_like(min_ref, POS_INF)
        arg_ref[...] = jnp.zeros_like(arg_ref)

    qc = qc_ref[...].astype(jnp.float32)   # [bm, bn]
    pc = pc_ref[...].astype(jnp.float32)
    cc = cc_ref[...].astype(jnp.float32)   # [1, bn]
    V_Ce = vce_ref[0, 0]

    # processing scores c[m,n] = V*Cc[n]*pc[m,n] - Qc[m,n] (write-through)
    c_ref[...] = (cc * pc - qc).astype(c_ref.dtype)

    # running row min/argmin of Qc
    blk_min = jnp.min(qc, axis=1, keepdims=True)           # [bm,1]
    # first column attaining the min (jnp.argmin's tie rule), spelled
    # out: Mosaic's argmin does not promise which tied index it returns
    col = jax.lax.broadcasted_iota(jnp.int32, qc.shape, 1).astype(jnp.float32)
    blk_arg = jnp.min(jnp.where(qc == blk_min, col, float(bn)), axis=1,
                      keepdims=True) + i_n * bn
    better = blk_min < min_ref[...]
    min_ref[...] = jnp.where(better, blk_min, min_ref[...])
    arg_ref[...] = jnp.where(better, blk_arg, arg_ref[...])

    @pl.when(i_n == nn - 1)
    def _finish():
        qe = qe_ref[...].astype(jnp.float32)  # [bm,1]
        pe = pe_ref[...].astype(jnp.float32)
        n1_ref[...] = arg_ref[...].astype(jnp.int32)
        b_ref[...] = (V_Ce * pe + min_ref[...] - qe).astype(b_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "interpret")
)
def carbon_scores(
    Qc: jax.Array,  # [M, N]
    pc: jax.Array,  # [M, N]
    Qe: jax.Array,  # [M]
    pe: jax.Array,  # [M]
    Cc: jax.Array,  # [N]
    V_Ce: jax.Array,  # scalar: V * Ce(t)
    *,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
):
    """Returns (c_scores [M,N] f32, n1 [M] int32, b [M] f32).

    Arbitrary M/N: inputs are padded up to the block grid. Padded Qc
    entries are +inf so they can never win the row argmin; padded rows /
    columns are sliced off the outputs before returning.
    """
    M, N = Qc.shape
    bm, bn = min(block_m, M), min(block_n, N)
    Mp, Np = -(-M // bm) * bm, -(-N // bn) * bn
    if (Mp, Np) != (M, N):
        dm, dn = Mp - M, Np - N
        Qc = jnp.pad(Qc, ((0, dm), (0, dn)), constant_values=POS_INF)
        pc = jnp.pad(pc, ((0, dm), (0, dn)), constant_values=1.0)
        Qe = jnp.pad(Qe, (0, dm))
        pe = jnp.pad(pe, (0, dm), constant_values=1.0)
        Cc = jnp.pad(Cc, (0, dn))
    nm, nn = Mp // bm, Np // bn
    c, n1, b = pl.pallas_call(
        functools.partial(_kernel, bn=bn, nn=nn),
        grid=(nm, nn),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda m, n: (m, n)),
            pl.BlockSpec((bm, bn), lambda m, n: (m, n)),
            pl.BlockSpec((bm, 1), lambda m, n: (m, 0)),
            pl.BlockSpec((bm, 1), lambda m, n: (m, 0)),
            pl.BlockSpec((1, bn), lambda m, n: (0, n)),
            pl.BlockSpec((1, 1), lambda m, n: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda m, n: (m, n)),
            pl.BlockSpec((bm, 1), lambda m, n: (m, 0)),
            pl.BlockSpec((bm, 1), lambda m, n: (m, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
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
        name="carbon_scores",
    )(
        Qc, pc, Qe[:, None], pe[:, None], Cc[None, :],
        jnp.asarray(V_Ce, jnp.float32)[None, None],
    )
    return c[:M, :N], n1[:M, 0], b[:M, 0]
