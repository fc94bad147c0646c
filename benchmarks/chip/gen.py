"""The benchmark's one traffic generator, and the deployments it serves.

Everything a cell feeds the scheduler is made here from the run's seed:
the NetworkSpec of a configuration file, the per-slot carbon intensities
and task arrivals of a served mix, and the stacked scenario lanes of a
fleet mix. A traffic mix is a data file (`traffic/<mix>.json`) of
parameters that these functions read; no mix has code of its own.

The carbon and scenario generators are copies of the program's
(`repro.core.carbon`, `repro.configs.fleet_scenarios`), kept here so
that a change to the program cannot move the yardstick. Where a copy
differs from its original, the docstring says how.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

SLOTS_PER_DAY = 48  # 30-minute slots, as in the ESO regional data

# National Grid ESO 2022 regional profiles (mean gCO2/kWh, diurnal
# amplitude, wind sensitivity), as in repro.core.carbon: column 0 backs
# the edge server, column j >= 1 (cloud j-1) takes profile j mod 6.
UK_REGIONS = np.array([
    (180.0, 60.0, 120.0),  # London          (edge)
    (45.0, 20.0, 35.0),    # North Scotland
    (330.0, 80.0, 150.0),  # South Wales
    (210.0, 70.0, 130.0),  # Midlands
    (120.0, 50.0, 90.0),   # North West
    (260.0, 75.0, 140.0),  # South East
], np.float32)


def load_json(path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def seed_key(seed: int, *tags: int):
    """A PRNG key from a seed of any size (PRNGKey alone keeps only the
    low 32 bits of a larger seed) and a few integer tags."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} must be >= 0")
    key = jax.random.PRNGKey(0)
    while True:
        key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            break
    for tag in tags:
        key = jax.random.fold_in(key, int(tag))
    return key


def seed_rng(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  *(int(t) for t in tags)])


# ---------------------------------------------------------------------------
# Configurations


def spec_arrays(cfg: dict):
    """(pe [M], pc [M, N], Pe, Pc [N]) of a configuration file, float32.
    pc cycles the listed per-type cloud energies over the M types and is
    the same at every cloud (Table I's clouds are homogeneous)."""
    M, N = int(cfg["M"]), int(cfg["N"])
    pc_col = np.resize(np.asarray(cfg["pc"], np.float32), M)
    pe = np.resize(np.asarray(cfg["pe"], np.float32), M)
    pc = np.tile(pc_col[:, None], (1, N))
    Pc = np.full((N,), cfg["Pc"], np.float32)
    return pe, pc, np.float32(cfg["Pe"]), Pc


def network_spec(cfg: dict):
    from repro.core.queueing import NetworkSpec

    pe, pc, Pe, Pc = spec_arrays(cfg)
    return NetworkSpec(pe=pe, pc=pc, Pe=float(Pe), Pc=Pc)


# ---------------------------------------------------------------------------
# Served mixes: pure-JAX functions of the slot and of data made from the
# seed. The program's step closes over the data (its sources take
# `(t, key)` and ignore the key it hands them); the reference takes the
# same data as arguments, so one compiled reference serves every seed.


def carbon_table(N: int, seed: int, slots: int) -> np.ndarray:
    """[slots, N+1] intensities of a served mix, made on the host: a copy
    of UKRegionalTraceSource (`uk_regional_table`) in which column j (0 the
    edge, j >= 1 cloud j-1) takes ESO profile j mod 6 and its own
    wind-front phase j; the original clamps every column past the sixth
    to the last profile. A table, and not the formula traced into each
    program, so that the program and the reference read the same
    intensities to the bit: XLA may round the formula differently in two
    programs."""
    return uk_regional_table(slots, N, seed_rng(seed, 1), 0)


def carbon_at(table, t, key=None):
    """(Ce, Cc [N]) at slot t, the table repeating after its last row."""
    del key
    row = table[jnp.asarray(t) % table.shape[0]]
    return row[0], row[1:]


def arrival_key(seed: int):
    return seed_key(seed, 2)


def arrivals_at(base, M: int, mix: dict, t, key=None):
    """a [M] at slot t: a_m(t) ~ U{0..amax} i.i.d. per type, drawn from
    `base` folded with t."""
    del key
    u = jax.random.uniform(jax.random.fold_in(base, t), (M,), jnp.float32)
    return jnp.floor(u * (jnp.float32(mix["amax"]) + 1.0))


# ---------------------------------------------------------------------------
# Fleet mixes: numpy copies of repro.configs.fleet_scenarios' scenario
# kinds over a configuration's lane (`_base` at M=N=5 is Table I itself).


def diurnal_table(T, N, rng, mean=220.0, amp=90.0, noise=20.0):
    t = np.arange(T)[:, None]
    phase = rng.uniform(0, 2 * np.pi, (1, N + 1))
    means = mean * rng.uniform(0.6, 1.4, (1, N + 1))
    day = 2 * np.pi * (t % SLOTS_PER_DAY) / SLOTS_PER_DAY
    tab = means + amp * np.sin(day - phase) + noise * rng.normal(
        size=(T, N + 1))
    return np.clip(tab, 5.0, 700.0).astype(np.float32)


def bursty_table(T, N, rng, base=120.0, spike=450.0, p_spike=0.05,
                 spike_len=6):
    tab = base * rng.uniform(0.7, 1.3, (T, N + 1))
    starts = rng.random((T, N + 1)) < p_spike
    for dt in range(spike_len):
        rolled = np.roll(starts, dt, axis=0)
        rolled[:dt] = False
        tab = np.where(rolled, tab + spike * (1 - dt / spike_len), tab)
    tab += 15.0 * rng.normal(size=(T, N + 1))
    return np.clip(tab, 5.0, 700.0).astype(np.float32)


def uk_regional_table(T, N, rng, rotate):
    """UKRegionalTraceSource rendered to a [T, N+1] table with the
    region profiles rotated by `rotate`; numpy noise in place of the
    original's per-slot PRNG draws."""
    R = len(UK_REGIONS)
    regions = np.arange(N + 1)
    params = UK_REGIONS[(regions + rotate) % R]
    t = np.arange(T)[:, None].astype(np.float32)
    day = 2.0 * np.pi * (t % SLOTS_PER_DAY) / SLOTS_PER_DAY
    diurnal = params[:, 1] * (np.sin(day - 2.0 * np.pi * 18.0 / 24.0)
                              + 0.3 * np.sin(2.0 * day))
    national = np.sin(2 * np.pi * t / (SLOTS_PER_DAY * 3.3) + 1.7)
    regional = np.sin(2 * np.pi * t / (SLOTS_PER_DAY * 2.1) + regions)
    front = params[:, 2] * (0.7 * national + 0.3 * regional)
    noise = 25.0 * rng.normal(size=(T, N + 1))
    return np.clip(params[:, 0] + diurnal + front + noise, 5.0,
                   700.0).astype(np.float32)


def _lane(kind, base, Tc, rng):
    """One scenario lane: (pe, pc, Pe, Pc, table, amax)."""
    pe, pc, Pe, Pc, amax0 = base
    M, N = pc.shape
    if kind == "diurnal":
        return pe, pc, Pe, Pc, diurnal_table(Tc, N, rng), amax0
    if kind == "bursty":
        amax = np.round(amax0 * rng.pareto(1.5, M).clip(0.05, 4.0))
        return pe, pc, Pe, Pc, bursty_table(Tc, N, rng), amax
    if kind == "heterogeneous-fleet":
        eff = rng.uniform(0.5, 2.0, (1, N)).astype(np.float32)
        pe_h = pe * rng.uniform(0.5, 2.0, M).astype(np.float32)
        Pc_h = Pc * rng.uniform(0.4, 1.6, N).astype(np.float32)
        amax = np.round(amax0 * rng.uniform(0.3, 1.5, M))
        return pe_h, pc * eff, Pe, Pc_h, diurnal_table(Tc, N, rng), amax
    if kind == "multi-region-uk":
        table = uk_regional_table(Tc, N, rng,
                                  int(rng.integers(len(UK_REGIONS))))
        return pe, pc, Pe, Pc, table, amax0
    raise KeyError(f"unknown fleet scenario kind {kind!r}")


def fleet_lanes(cfg: dict, mix: dict, seed: int) -> dict:
    """The stacked lanes of a fleet mix, as numpy arrays with a leading
    lane axis F: pe [F, M], pc [F, M, N], Pe [F], Pc [F, N],
    carbon [F, Tc, N+1], amax [F, M]. `mix["kinds"]` maps each scenario
    kind to its number of lanes; lane i of kind k draws from its own
    generator (seed, k, i)."""
    pe, pc, Pe, Pc = spec_arrays(cfg)
    M = pe.shape[0]
    base = (pe, pc, Pe, Pc, np.full((M,), float(mix["amax"]), np.float32))
    lanes = []
    for k, (kind, count) in enumerate(mix["kinds"].items()):
        for i in range(int(count)):
            lanes.append(_lane(kind, base, int(mix["Tc"]),
                               seed_rng(seed, k, i)))
    cols = list(zip(*lanes))
    names = ("pe", "pc", "Pe", "Pc", "carbon", "amax")
    return {n: np.stack([np.asarray(x, np.float32) for x in col])
            for n, col in zip(names, cols)}


def fleet_scenario(lanes: dict):
    """The program's FleetScenario of a `fleet_lanes` stack."""
    from repro.core.simulator import FleetScenario, FleetSpec

    return FleetScenario(
        spec=FleetSpec(pe=jnp.asarray(lanes["pe"]),
                       pc=jnp.asarray(lanes["pc"]),
                       Pe=jnp.asarray(lanes["Pe"]),
                       Pc=jnp.asarray(lanes["Pc"])),
        carbon=jnp.asarray(lanes["carbon"]),
        arrival_amax=jnp.asarray(lanes["amax"]),
    )


def bench_dir() -> Path:
    return Path(__file__).resolve().parent
