"""jit'd public wrappers for the Pallas kernels.

Dispatch with `interpret=None` (auto), decided by `jax.default_backend()`:

* on a TPU every wrapper runs the compiled Mosaic kernel;
* elsewhere the score kernels (`carbon_scores`, `route_scores`) run their
  bit-identical jnp reference, and the LM kernels (flash attention,
  decode, SSD) run in Pallas interpret mode.

`interpret=True` forces the interpret-mode kernel (the parity tests'
oracle); `interpret=False` forces the compiled kernel (what a TPU compile
rehearsal on a CPU host asks for). The contracts match kernels/ref.py
exactly (see tests/test_kernels.py shape/dtype sweeps).
"""
from __future__ import annotations

import jax

from repro.kernels import carbon_score, flash_attention as fa, ssd_chunk
from repro.kernels import ref


def _auto_interpret(interpret):
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, *, mask_mode="causal", prefix_len=0,
                    bq=128, bk=128, interpret=None):
    return fa.flash_attention(
        q, k, v, mask_mode=mask_mode, prefix_len=prefix_len,
        bq=bq, bk=bk, interpret=_auto_interpret(interpret),
    )


def ssd_chunk_intra(a, x, Bm, Cm, *, block_heads=8, interpret=None):
    return ssd_chunk.ssd_chunk_intra(
        a, x, Bm, Cm, block_heads=block_heads,
        interpret=_auto_interpret(interpret),
    )


def carbon_scores(Qc, pc, Qe, pe, Cc, V_Ce, *, block_m=256, block_n=256,
                  interpret=None):
    """Fused score pass. Off-TPU with interpret=None (auto) this lowers
    to the bit-identical jnp reference: interpret mode emulates the
    Pallas grid loop in XLA and is strictly slower than letting XLA
    fuse the reference, so it is a correctness oracle (interpret=True,
    as the parity tests pass), never an auto-selected serving path."""
    if interpret is None and jax.default_backend() != "tpu":
        return ref.carbon_scores_ref(Qc, pc, Qe, pe, Cc, V_Ce)
    return carbon_score.carbon_scores(
        Qc, pc, Qe, pe, Cc, V_Ce, block_m=block_m, block_n=block_n,
        interpret=bool(interpret),
    )


def route_scores(Qt, pt, Qcr, extra, Qe, pe, VCt, V_Ce, *, block_m=256,
                 block_l=256, interpret=None):
    """Route-lattice score pass. Dispatch policy differs from the other
    kernels: off-TPU with interpret=None (auto) this lowers to the
    bit-identical jnp reference instead of the interpret-mode kernel --
    interpret mode emulates the grid loop in XLA and is strictly slower
    than the fused-by-XLA reference, so auto-dispatch treats it as a
    correctness oracle, not a serving path (DESIGN.md §WAN transfer).
    Pass interpret=True to force the emulated kernel (parity tests do)."""
    if interpret is None and jax.default_backend() != "tpu":
        return ref.route_scores_ref(Qt, pt, Qcr, extra, Qe, pe, VCt, V_Ce)
    from repro.kernels import route_score

    return route_score.route_scores(
        Qt, pt, Qcr, extra, Qe, pe, VCt, V_Ce, block_m=block_m,
        block_l=block_l, interpret=bool(interpret),
    )


# re-export oracles for convenience
flash_attention_ref = ref.flash_attention_ref
ssd_chunk_intra_ref = ref.ssd_chunk_intra_ref
carbon_scores_ref = ref.carbon_scores_ref
route_scores_ref = ref.route_scores_ref


def flash_decode(q, k, v, pos, *, block_s=512, interpret=None):
    from repro.kernels import flash_decode as fd

    return fd.flash_decode(
        q, k, v, pos, block_s=block_s, interpret=_auto_interpret(interpret)
    )


flash_decode_ref = ref.flash_decode_ref
