"""The one import of ``jax.experimental.pallas.tpu``.

Kernels pull ``CompilerParams`` / ``VMEM`` / ``PrefetchScalarGridSpec``
from here (enforced by the ``pltpu-import`` lint rule in
``repro.analysis``), so a rename in the Pallas TPU API costs one edit
instead of one per kernel. Written for the pinned JAX (pyproject.toml).
"""
from __future__ import annotations

import jax.experimental.pallas.tpu as pltpu  # lint: allow=pltpu-import

CompilerParams = pltpu.CompilerParams
VMEM = pltpu.VMEM
PrefetchScalarGridSpec = pltpu.PrefetchScalarGridSpec

__all__ = ["CompilerParams", "VMEM", "PrefetchScalarGridSpec"]
