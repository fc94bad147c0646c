"""Peaks and the least work of a kernel, kept with the benchmark.

`peaks.json` holds each device's published peaks, keyed by JAX's
`device_kind`; a kind that is not there is an error, never a default.
"""
from __future__ import annotations

import json
from pathlib import Path

F32 = 4


def peaks(kind: str, path=None) -> dict:
    path = Path(path) if path else Path(__file__).with_name("peaks.json")
    table = json.loads(path.read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in {path.name} "
                       f"(have {sorted(table)})")
    return table[kind]


def score_pass_cost(M: int, N: int) -> tuple:
    """(flops, bytes) that one score pass of Algorithm 1 needs at M task
    types and N clouds, all float32: it reads Qc and pc and writes the
    processing scores c ([M, N] each: 12 M N bytes), reads Qe, pe and the
    N cloud intensities, and writes the dispatch target n1 and score b
    ([M] each). Per entry it multiplies V Cc by pc, subtracts Qc and
    takes part in the row minimum: 3 M N flops, plus 3 per type for b."""
    nbytes = F32 * (3 * M * N + 4 * M + N)
    flops = 3 * M * N + 3 * M
    return flops, nbytes


def least_time(flops: float, nbytes: float, peak: dict) -> tuple:
    """(seconds, bound): the larger of flops over the bf16 peak and bytes
    over the HBM bandwidth, and which of the two it is."""
    t_flop = flops / peak["bf16_flop_per_s"]
    t_byte = nbytes / peak["hbm_byte_per_s"]
    return (t_byte, "memory") if t_byte >= t_flop else (t_flop, "compute")
