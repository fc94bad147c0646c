"""Jaxpr invariant auditor.

Abstractly traces every registered (policy x backend x scenario)
combination -- no simulation is executed -- and checks the invariants
the repo's perf and parity claims rest on:

  dtype discipline   no 64-bit value anywhere in a traced hot path
                     under the repo's default config, and no float64
                     anywhere when the same program is re-traced with
                     x64 enabled (the mode that exposes unpinned
                     ``jax.random.*`` / ``jnp.zeros`` defaults that
                     float32 discipline currently only masks).
  scan carries       every ``lax.scan`` / ``while_loop`` carry leaf is
                     exactly {float32, int32, uint32, bool} and never
                     weak-typed: a weak carry re-types with context and
                     is a silent-retrace hazard.
  effect freedom     no host callbacks (``io_callback`` /
                     ``pure_callback`` / ``debug_callback``) and no
                     JAX effects at all inside the traced program --
                     the fleet scan must stay a pure compiled loop.
                     The ONE sanctioned exception is the opt-in
                     streaming-telemetry flush (telemetry.stream):
                     combos named in ``EFFECTFUL_ALLOWLIST`` may carry
                     ``io_callback`` and its IO effect, nothing else,
                     and every other check still applies to them. A
                     streaming combo absent from the allowlist fails
                     the audit -- the default path stays provably
                     callback-free.
  retrace audit      across the full scenario registry, each
                     (policy, backend) presents exactly ONE abstract
                     input signature per shape class, and the policy
                     object itself is hashable and reconstructible-
                     equal -- together the preconditions for "compiles
                     exactly once per shape class" under ``jax.jit``.

``audit_all()`` runs everything; ``python -m repro.analysis --audit``
is the CLI entry. See DESIGN.md §Static analysis.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Callable, Dict, Iterable, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from jax.extend import core as jcore

# Primitives that reach back to the host from inside a jitted program.
CALLBACK_PRIMITIVES = {
    "io_callback",
    "pure_callback",
    "debug_callback",
    "outside_call",
    "host_callback_call",
}

# The only dtypes allowed to live in a scan/while carry: the simulator
# contract is float32 state + int32 counters + uint32 PRNG keys + bool
# flags (core/queueing.py DTYPE).
ALLOWED_CARRY_DTYPES = {"float32", "int32", "uint32", "bool"}

# Combos allowed to carry the streaming-telemetry io_callback (and the
# IO effect it hoists onto enclosing scan/pjit eqns) -- the explicit
# registration DESIGN.md §Live observability requires. Populated next
# to the streaming combos in iter_combos; anything else tracing an
# io_callback (including an unregistered StreamConfig combo) still
# fails the effects check.
EFFECTFUL_ALLOWLIST: set = set()

AUDIT_T = 8          # slots traced per combo (tracing cost only)
AUDIT_M, AUDIT_N = 4, 3
AUDIT_TC = 24
AUDIT_PER_KIND = 2


@dataclasses.dataclass(frozen=True)
class AuditViolation:
    combo: str
    check: str   # "dtype64" | "weak-carry" | "carry-dtype" | "effects" | "x64" | "retrace"
    message: str

    def __str__(self) -> str:
        return f"{self.combo}: [{self.check}] {self.message}"


class Combo(NamedTuple):
    """One traceable (policy, forecaster, scenario-family) combination."""

    name: str
    policy_key: str        # retrace-grouping key: policy x backend
    scenario: str
    make_policy: Callable  # () -> policy (called twice: equality check)
    forecaster: object
    fleet: object          # FleetScenario
    record: object         # "full" | "summary" | int stride
    telemetry: object = None  # TelemetryConfig | None (jit static)


# ---------------------------------------------------------------------------
# Registry enumeration


def _policy_factories():
    from repro.core.extensions import ThresholdPolicy
    from repro.core.policies import (
        CarbonIntensityPolicy,
        ExactDPPPolicy,
        LookaheadDPPPolicy,
        QueueLengthPolicy,
        RandomPolicy,
    )
    from repro.forecast import SeasonalNaiveForecaster

    fc = SeasonalNaiveForecaster(H=4, period=6)
    return [
        # (policy_key, factory, forecaster)
        ("ci/reference", lambda: CarbonIntensityPolicy(), None),
        ("ci/pallas",
         lambda: CarbonIntensityPolicy(score_backend="pallas"), None),
        ("queue-length", lambda: QueueLengthPolicy(), None),
        ("lookahead/reference", lambda: LookaheadDPPPolicy(H=4), fc),
        ("threshold", lambda: ThresholdPolicy(), None),
        ("random", lambda: RandomPolicy(), None),
        ("exact-dpp", lambda: ExactDPPPolicy(grid=32), None),
    ]


def _wan_policy_factories():
    from repro.core.policies import CarbonIntensityPolicy
    from repro.forecast import SeasonalNaiveForecaster
    from repro.network import NetworkAwareDPPPolicy, StaticRoutePolicy

    fc = SeasonalNaiveForecaster(H=4, period=6)
    return [
        ("aware/reference", lambda: NetworkAwareDPPPolicy(), None),
        ("aware/pallas",
         lambda: NetworkAwareDPPPolicy(score_backend="pallas"), None),
        ("blind",
         lambda: StaticRoutePolicy(CarbonIntensityPolicy()), None),
        ("aware-lookahead/reference",
         lambda: NetworkAwareDPPPolicy(H=4), fc),
    ]


def iter_combos(per_kind: int = AUDIT_PER_KIND) -> List[Combo]:
    """Every (policy x backend) crossed with every registered scenario
    (plain fleets) and every registered topology (WAN fleets), at audit
    size. One representative per (policy, scenario) additionally audits
    the "summary" and stride recording modes."""
    from repro.configs.fleet_scenarios import (
        NETWORK_SCENARIOS,
        SCENARIOS,
        build_fleet,
        build_network_fleet,
    )
    from repro.core.simulator import sweep_forecast_errors
    from repro.forecast import ClairvoyantTableForecaster

    combos: List[Combo] = []
    fleets = {
        kind: build_fleet([kind], per_kind=per_kind, M=AUDIT_M,
                          N=AUDIT_N, Tc=AUDIT_TC, seed=0)
        for kind in SCENARIOS
    }
    for policy_key, make, fc in _policy_factories():
        for kind, fleet in fleets.items():
            combos.append(Combo(
                name=f"{policy_key}@{kind}",
                policy_key=policy_key, scenario=kind,
                make_policy=make, forecaster=fc, fleet=fleet,
                record="full",
            ))
    # recording-mode coverage (same policy+scenario, different program)
    base = fleets["diurnal-slack"]
    for record in ("summary", 2):
        combos.append(Combo(
            name=f"ci/reference@diurnal-slack/record={record}",
            policy_key="ci/reference", scenario="diurnal-slack",
            make_policy=_policy_factories()[0][1], forecaster=None,
            fleet=base, record=record,
        ))
    # the per-lane forecast-error sweep axis (traced err_bias/err_noise)
    combos.append(Combo(
        name="lookahead/clairvoyant-err@diurnal-slack",
        policy_key="lookahead/reference", scenario="diurnal-slack+err",
        make_policy=_policy_factories()[3][1],
        forecaster=ClairvoyantTableForecaster(H=4),
        fleet=sweep_forecast_errors(base, bias=0.05, noise=0.1),
        record="full",
    ))

    # WAN topologies: the two 2N-route kinds share a shape class; star
    # (N routes) is its own.
    wan_fleets = {
        kind: build_network_fleet([kind], per_kind=per_kind, M=AUDIT_M,
                                  N=AUDIT_N, Tc=AUDIT_TC, seed=0)
        for kind in NETWORK_SCENARIOS
    }
    for policy_key, make, fc in _wan_policy_factories():
        for kind, fleet in wan_fleets.items():
            combos.append(Combo(
                name=f"{policy_key}@{kind}",
                policy_key=policy_key, scenario=kind,
                make_policy=make, forecaster=fc, fleet=fleet,
                record="full",
            ))

    # Fault-layer combos (repro.faults): stacked FaultParams put the
    # fault chains, staleness/backoff counters and retry pool into the
    # scan carry -- every gate (carry dtypes, weak types, x64 re-trace,
    # retrace signatures) covers them from day one.
    from repro.configs.fleet_scenarios import with_faults
    from repro.core.policies import CarbonIntensityPolicy
    from repro.faults import StalenessGuardPolicy
    from repro.network import NetworkAwareDPPPolicy

    blackout = with_faults(base, "regional-blackout")
    brownout = with_faults(base, "telemetry-brownout")
    flappy = with_faults(wan_fleets["congested-uplink"], "flappy-uplink")
    fault_combos = [
        ("ci/reference", lambda: CarbonIntensityPolicy(),
         "regional-blackout", blackout, "full"),
        ("ci/pallas",
         lambda: CarbonIntensityPolicy(score_backend="pallas"),
         "regional-blackout", blackout, "full"),
        ("guard-ci/reference",
         lambda: StalenessGuardPolicy(CarbonIntensityPolicy()),
         "regional-blackout", blackout, "full"),
        ("guard-ci/reference",
         lambda: StalenessGuardPolicy(CarbonIntensityPolicy()),
         "telemetry-brownout", brownout, "full"),
        ("guard-ci/reference",
         lambda: StalenessGuardPolicy(CarbonIntensityPolicy()),
         "telemetry-brownout/summary", brownout, "summary"),
        ("queue-length", _policy_factories()[2][1],
         "telemetry-brownout", brownout, "full"),
        ("aware/reference", lambda: NetworkAwareDPPPolicy(),
         "flappy-uplink", flappy, "full"),
        ("guard-aware/reference",
         lambda: StalenessGuardPolicy(NetworkAwareDPPPolicy()),
         "flappy-uplink", flappy, "full"),
    ]
    for policy_key, make, scen, fleet, record in fault_combos:
        combos.append(Combo(
            name=f"{policy_key}@diurnal-slack+{scen}",
            policy_key=policy_key, scenario=scen,
            make_policy=make, forecaster=None, fleet=fleet,
            record=record,
        ))

    # Telemetry-on combos (repro.telemetry): taps put TapState in the
    # carry and a stacked TapSeries on the output path -- all four
    # simulator variants must stay effect-free, 32-bit and re-trace
    # clean with the extra accumulators threaded through. Covers both
    # score backends, the record modes, the WAN path and guard+faults.
    from repro.telemetry import TelemetryConfig

    tcfg = TelemetryConfig()
    telemetry_combos = [
        ("ci/reference", lambda: CarbonIntensityPolicy(),
         "diurnal-slack+taps", base, "full"),
        ("ci/pallas",
         lambda: CarbonIntensityPolicy(score_backend="pallas"),
         "diurnal-slack+taps", base, "full"),
        ("ci/reference", lambda: CarbonIntensityPolicy(),
         "diurnal-slack+taps/summary", base, "summary"),
        ("ci/reference", lambda: CarbonIntensityPolicy(),
         "diurnal-slack+taps/stride", base, 2),
        ("aware/reference", lambda: NetworkAwareDPPPolicy(),
         "congested-uplink+taps", wan_fleets["congested-uplink"],
         "full"),
        ("guard-ci/reference",
         lambda: StalenessGuardPolicy(CarbonIntensityPolicy()),
         "telemetry-brownout+taps", brownout, "full"),
        ("guard-aware/reference",
         lambda: StalenessGuardPolicy(NetworkAwareDPPPolicy()),
         "flappy-uplink+taps", flappy, "full"),
    ]
    for policy_key, make, scen, fleet, record in telemetry_combos:
        combos.append(Combo(
            name=f"{policy_key}@{scen}",
            policy_key=policy_key, scenario=scen,
            make_policy=make, forecaster=None, fleet=fleet,
            record=record, telemetry=tcfg,
        ))

    # Deadline-layer combos (repro.deadlines): stacked DeadlineParams
    # put the [M, D] age rings and the mu estimator into the scan carry
    # and three new policies (with a deadline_view kwarg) onto the
    # traced path -- both score backends, the guarded+faulted
    # composition, shedding under overload, and a taps-on run all pass
    # the same gates (carry dtypes, weak types, x64 re-trace, retrace
    # signatures, effect freedom) as every other combo.
    from repro.configs.fleet_scenarios import with_deadlines
    from repro.deadlines import (
        EDDPolicy,
        SlackThresholdPolicy,
        WaitAwhilePolicy,
    )
    from repro.forecast import SeasonalNaiveForecaster

    tight = with_deadlines(base, "tight-uniform")
    shed = with_deadlines(fleets["overload"], "shed-overload")
    tight_blackout = with_deadlines(blackout, "tight-uniform")
    fc4 = SeasonalNaiveForecaster(H=4, period=6)
    deadline_combos = [
        ("slack/reference", lambda: SlackThresholdPolicy(),
         "tight-uniform", tight, "full", None, None),
        ("slack/pallas",
         lambda: SlackThresholdPolicy(score_backend="pallas"),
         "tight-uniform", tight, "full", None, None),
        ("edd", lambda: EDDPolicy(),
         "tight-uniform", tight, "full", None, None),
        ("waitawhile/reference", lambda: WaitAwhilePolicy(H=4),
         "tight-uniform", tight, "full", fc4, None),
        ("ci/reference", lambda: CarbonIntensityPolicy(),
         "overload+shed", shed, "summary", None, None),
        ("guard-slack/reference",
         lambda: StalenessGuardPolicy(SlackThresholdPolicy()),
         "tight-uniform+regional-blackout", tight_blackout, "full",
         None, None),
        ("slack/reference", lambda: SlackThresholdPolicy(),
         "tight-uniform+taps", tight, "full", None, tcfg),
    ]
    for policy_key, make, scen, fleet, record, fcst, tel in \
            deadline_combos:
        combos.append(Combo(
            name=f"{policy_key}@{scen}",
            policy_key=policy_key, scenario=scen,
            make_policy=make, forecaster=fcst, fleet=fleet,
            record=record, telemetry=tel,
        ))

    # Streaming-telemetry combos (repro.telemetry.stream): the ONLY
    # registry entries whose traced program may carry an io_callback.
    # Each name is registered in EFFECTFUL_ALLOWLIST; audit_all traces
    # them with allow_io=True, which tolerates exactly the io_callback
    # primitive + IO effect while every other check (carry dtypes, weak
    # types, x64 re-trace, retrace signatures, other callbacks) still
    # applies. flush_every=4 divides AUDIT_T=8 (streaming requires it).
    from repro.telemetry import StreamConfig

    scfg = StreamConfig(taps=tcfg, flush_every=4, channel="audit")
    stream_combos = [
        ("ci/reference", lambda: CarbonIntensityPolicy(),
         "diurnal-slack+stream", base, "full"),
        ("ci/pallas",
         lambda: CarbonIntensityPolicy(score_backend="pallas"),
         "diurnal-slack+stream", base, "full"),
        ("ci/reference", lambda: CarbonIntensityPolicy(),
         "diurnal-slack+stream/summary", base, "summary"),
        ("aware/reference", lambda: NetworkAwareDPPPolicy(),
         "congested-uplink+stream", wan_fleets["congested-uplink"],
         "full"),
        ("guard-ci/reference",
         lambda: StalenessGuardPolicy(CarbonIntensityPolicy()),
         "telemetry-brownout+stream", brownout, "full"),
    ]
    for policy_key, make, scen, fleet, record in stream_combos:
        name = f"{policy_key}@{scen}"
        EFFECTFUL_ALLOWLIST.add(name)
        combos.append(Combo(
            name=name, policy_key=policy_key, scenario=scen,
            make_policy=make, forecaster=None, fleet=fleet,
            record=record, telemetry=scfg,
        ))
    return combos


def _combo_fn(combo: Combo) -> Callable:
    """The function the auditor traces: one full fleet simulation."""
    from repro.core.simulator import simulate_fleet

    policy = combo.make_policy()

    def run(fleet, key):
        return simulate_fleet(
            policy, fleet, AUDIT_T, key,
            forecaster=combo.forecaster, record=combo.record,
            telemetry=combo.telemetry,
        )

    return run


# ---------------------------------------------------------------------------
# Jaxpr walking


def _subjaxprs(eqn) -> Iterable:
    for val in eqn.params.values():
        vals = val if isinstance(val, (list, tuple)) else (val,)
        for v in vals:
            if isinstance(v, jcore.ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, jcore.Jaxpr):
                yield v


def _iter_eqns(jaxpr) -> Iterable:
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs(eqn):
            yield from _iter_eqns(sub)


def _aval_desc(aval) -> str:
    dtype = getattr(aval, "dtype", None)
    weak = getattr(aval, "weak_type", False)
    shape = getattr(aval, "shape", ())
    return f"{dtype}{shape}{' weak' if weak else ''}"


def _scan_carry_avals(eqn) -> List:
    name = eqn.primitive.name
    if name == "scan":
        nc, ncar = eqn.params["num_consts"], eqn.params["num_carry"]
        return [v.aval for v in eqn.invars[nc:nc + ncar]]
    if name == "while":
        skip = eqn.params["cond_nconsts"] + eqn.params["body_nconsts"]
        return [v.aval for v in eqn.invars[skip:]]
    return []


def _is_io_effect(effect) -> bool:
    return "io" in type(effect).__name__.lower()


def audit_jaxpr(closed_jaxpr, combo_name: str,
                x64_mode: bool = False,
                allow_io: bool = False) -> List[AuditViolation]:
    """Static checks over one traced program (see module docstring).

    `allow_io=True` (set by audit_all for EFFECTFUL_ALLOWLIST combos
    only) tolerates exactly the streaming-telemetry escape hatch: the
    `io_callback` primitive and the IOEffect it hoists onto enclosing
    scan/pjit equations. Every other callback/effect, and every other
    check, is unaffected.
    """
    out: List[AuditViolation] = []
    seen: set = set()

    def emit(check, msg):
        if (check, msg) not in seen:  # dedupe identical findings
            seen.add((check, msg))
            out.append(AuditViolation(combo_name, check, msg))

    for eqn in _iter_eqns(closed_jaxpr.jaxpr):
        name = eqn.primitive.name
        if name in CALLBACK_PRIMITIVES:
            if not (allow_io and name == "io_callback"):
                emit("effects", f"host callback primitive '{name}' in a "
                     "jitted path")
        elif eqn.effects:
            leaked = [
                e for e in eqn.effects
                if not (allow_io and _is_io_effect(e))
            ]
            if leaked:
                emit("effects",
                     f"primitive '{name}' carries effects {leaked}")
        for var in eqn.outvars:
            dtype = getattr(var.aval, "dtype", None)
            if dtype is None:
                continue
            if jax.dtypes.issubdtype(dtype, jax.dtypes.extended):
                # typed PRNG keys (key<fry> from random_wrap etc.) have
                # no itemsize and are not a width-discipline concern
                continue
            if x64_mode:
                # int64 from arange/iota defaults is jax-canonical under
                # x64; the discipline violation is 64-bit FLOAT compute.
                if jnp.issubdtype(dtype, jnp.floating) and \
                        jnp.dtype(dtype).itemsize >= 8:
                    emit("x64", f"'{name}' produces {dtype} under "
                         "x64: an unpinned float default in the hot "
                         "path")
            elif jnp.dtype(dtype).itemsize >= 8 and not jnp.issubdtype(
                dtype, jnp.complexfloating
            ):
                emit("dtype64", f"'{name}' produces {dtype}")
            elif jnp.issubdtype(dtype, jnp.complexfloating):
                emit("dtype64", f"'{name}' produces complex {dtype}")
        for aval in _scan_carry_avals(eqn):
            dtype = getattr(aval, "dtype", None)
            if dtype is None:
                continue
            if jax.dtypes.issubdtype(dtype, jax.dtypes.extended):
                continue  # typed PRNG key threaded through the carry
            if getattr(aval, "weak_type", False):
                emit("weak-carry",
                     f"{eqn.primitive.name} carry leaf {_aval_desc(aval)} "
                     "is weak-typed (re-types with context; retrace "
                     "hazard)")
            if not x64_mode and str(dtype) not in ALLOWED_CARRY_DTYPES:
                emit("carry-dtype",
                     f"{eqn.primitive.name} carry leaf {_aval_desc(aval)} "
                     f"outside {sorted(ALLOWED_CARRY_DTYPES)}")
    return out


def _with_x64(enabled: bool):
    """Context manager flipping jax_enable_x64 (trace-time only)."""
    import contextlib

    @contextlib.contextmanager
    def ctx():
        prev = jax.config.jax_enable_x64
        jax.config.update("jax_enable_x64", enabled)
        try:
            yield
        finally:
            jax.config.update("jax_enable_x64", prev)

    return ctx()


def audit_combo(combo: Combo,
                allow_io: bool = False) -> List[AuditViolation]:
    """Traces one combo under the default config AND under x64, and
    runs the static checks on both jaxprs. The x64 trace never executes
    anything -- it exists to surface unpinned float defaults
    (``jax.random.uniform`` / ``jnp.zeros`` without ``dtype=``) that
    default-config float32 canonicalization silently papers over.
    `allow_io` threads to audit_jaxpr (the streaming-combo escape
    hatch; audit_all sets it from EFFECTFUL_ALLOWLIST)."""
    fn = _combo_fn(combo)
    key = jax.random.PRNGKey(0)
    out: List[AuditViolation] = []
    try:
        closed = jax.make_jaxpr(fn)(combo.fleet, key)
    except Exception as e:  # trace failure is itself a finding
        return [AuditViolation(combo.name, "trace",
                               f"default-config trace failed: {e}")]
    out.extend(audit_jaxpr(closed, combo.name, x64_mode=False,
                           allow_io=allow_io))
    with _with_x64(True):
        try:
            closed64 = jax.make_jaxpr(fn)(combo.fleet, key)
        except Exception as e:
            out.append(AuditViolation(
                combo.name, "x64",
                f"trace fails with x64 enabled -- some op re-types with "
                f"the config instead of being pinned to float32: {e}",
            ))
        else:
            out.extend(audit_jaxpr(closed64, combo.name, x64_mode=True,
                                   allow_io=allow_io))
    return out


# ---------------------------------------------------------------------------
# Retrace audit


def _signature(tree, shapes_only: bool = False) -> str:
    leaves, treedef = jax.tree.flatten(tree)
    parts = [str(treedef)]
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        if shapes_only:
            parts.append(f"{shape}")
        else:
            parts.append(
                f"{shape}:{getattr(leaf, 'dtype', type(leaf).__name__)}:"
                f"{getattr(leaf, 'weak_type', False)}"
            )
    return hashlib.sha1("|".join(parts).encode()).hexdigest()[:16]


def retrace_audit(combos: List[Combo] | None = None
                  ) -> Tuple[List[AuditViolation], Dict]:
    """Proves each (policy, backend) compiles exactly once per shape
    class across the registry, without tracing anything:

    ``jax.jit``'s cache key is (static closure, input avals). The
    static closure is constant per combo family iff the policy object
    is hashable and a rebuilt copy compares equal -- checked here via
    the factory. The input avals are constant per shape class iff every
    scenario of that shape presents the identical (treedef, shape,
    dtype, weak_type) signature -- checked by hashing. Any scenario
    whose full signature differs from its shape-class peers would
    silently retrace at run time; it is reported before that happens.

    Returns (violations, report) where report maps
    policy_key -> {shape_class_hash: signature_hash}.
    """
    combos = iter_combos() if combos is None else combos
    out: List[AuditViolation] = []
    # policy_key -> shape_class -> {full_sig: [combo names]}
    table: Dict[str, Dict[str, Dict[str, list]]] = {}
    for combo in combos:
        policy = combo.make_policy()
        rebuilt = combo.make_policy()
        try:
            h1, h2 = hash(policy), hash(rebuilt)
        except TypeError as e:
            out.append(AuditViolation(
                combo.name, "retrace",
                f"policy is unhashable ({e}): cannot be a jit static",
            ))
            continue
        if policy != rebuilt or h1 != h2:
            out.append(AuditViolation(
                combo.name, "retrace",
                "rebuilding the policy from identical config yields an "
                "unequal object: every construction would recompile",
            ))
        args = (combo.fleet, jax.random.PRNGKey(0))
        # record/forecaster/telemetry are static closure -> the key
        static = (
            f"{combo.record}|{combo.forecaster!r}|{combo.telemetry!r}"
        )
        full = _signature(args) + f"|{static}"
        shape = _signature(args, shapes_only=True) + f"|{static}"
        slot = table.setdefault(combo.policy_key, {}).setdefault(
            shape, {}
        )
        slot.setdefault(full, []).append(combo.name)
    for policy_key, classes in table.items():
        for shape, sigs in classes.items():
            if len(sigs) > 1:
                names = [n for group in sigs.values() for n in group]
                out.append(AuditViolation(
                    f"{policy_key}", "retrace",
                    f"{len(sigs)} distinct abstract signatures within "
                    f"one shape class (scenarios {names}): dtype or "
                    "weak_type drift between scenarios would trigger "
                    "a silent retrace",
                ))
    report = {
        pk: {shape: next(iter(sigs)) for shape, sigs in classes.items()}
        for pk, classes in table.items()
    }
    return out, report


def audit_all(per_kind: int = AUDIT_PER_KIND,
              trace_all: bool = False) -> List[AuditViolation]:
    """The full audit: retrace audit over every registry combo (cheap,
    no tracing) + jaxpr checks. By default the jaxpr checks trace one
    representative scenario per (policy_key, shape-class) -- the traced
    program is scenario-independent within a shape class, which is
    exactly what the retrace audit proves first. ``trace_all=True``
    traces every combo (slow; belt-and-braces mode)."""
    combos = iter_combos(per_kind=per_kind)
    violations, _ = retrace_audit(combos)
    if trace_all:
        rep = combos
    else:
        seen: set = set()
        rep = []
        for combo in combos:
            k = (combo.policy_key,
                 _signature((combo.fleet,), shapes_only=True),
                 str(combo.record), repr(combo.forecaster),
                 repr(combo.telemetry))
            if k not in seen:
                seen.add(k)
                rep.append(combo)
    for combo in rep:
        violations.extend(audit_combo(
            combo, allow_io=combo.name in EFFECTFUL_ALLOWLIST
        ))
    return violations
