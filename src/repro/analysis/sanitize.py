"""Checkify sanitizer harness for the simulators.

``jax.experimental.checkify`` instruments a traced program with
functional error checks -- NaN production, division by zero, out-of-
bounds gather/scatter -- that jit compiles away into a threaded error
value instead of silently producing garbage. The repo had zero checkify
coverage before this module; the carbon ledger (emissions accounting)
is exactly the kind of number a NaN corrupts silently at fleet scale.

``checkified_simulate_fleet`` lifts a whole fleet simulation;
``sanitize_smoke`` is the CI battery (one case per simulator entry
point, including the chunked-fill ``while_loop`` path), run by
``python -m repro.analysis --sanitize-smoke``.
"""
from __future__ import annotations

from typing import Callable, List, Tuple

import jax
from jax.experimental import checkify

# NaN + div-by-zero + OOB-index: everything that can corrupt the carbon
# ledger without crashing. user_checks stays out of the default set so
# future explicit checkify.check() calls can be opted in separately.
DEFAULT_CHECKS = checkify.float_checks | checkify.index_checks

# The fleet simulators vmap the per-instance program, and checkify's
# OOB rule cannot instrument a *batched* scatter (the error rule itself
# raises IndexError on any scatter carrying operand_batching_dims), nor
# discharge through a vmapped while_loop.
# Fleet lifts therefore run NaN + div-by-zero only; OOB coverage for
# the identical per-instance program comes from the single-instance
# lanes in `sanitize_smoke`, which carry the full DEFAULT_CHECKS.
FLEET_CHECKS = checkify.float_checks

SMOKE_T = 24
SMOKE_M, SMOKE_N = 4, 3
SMOKE_PER_KIND = 2


def checkified_simulate_fleet(
    policy: Callable,
    fleet,
    T: int,
    key,
    forecaster: Callable | None = None,
    record="summary",
    errors=FLEET_CHECKS,
):
    """Runs ``simulate_fleet`` under checkify and returns
    ``(error, result)``. ``error.get()`` is None on a clean run; call
    ``error.throw()`` to raise instead. The checkified program is
    jitted, so the checks compile into the fleet scan itself rather
    than running in op-by-op eager mode."""
    from repro.core.simulator import simulate_fleet

    def run(k):
        return simulate_fleet(
            policy, fleet, T, k, forecaster=forecaster, record=record
        )

    checked = checkify.checkify(run, errors=errors)
    return jax.jit(checked)(key)


def sanitize_smoke(T: int = SMOKE_T) -> List[Tuple[str, str | None]]:
    """One checkified run per simulator entry point at smoke size.
    Returns ``[(case name, error message or None)]``; all-None = clean.

    Fleet lanes run ``FLEET_CHECKS`` (NaN + div-by-zero); the
    single-instance lanes run full ``DEFAULT_CHECKS`` including OOB
    index checks -- see the ``FLEET_CHECKS`` comment for why.

    Cases:
      * ``simulate_fleet`` on the diurnal-slack fleet (the acceptance
        anchor) under the default policy;
      * the same fleet under ``LookaheadDPPPolicy`` + seasonal-naive
        forecaster (forecast carry threading + the deferral math);
      * single-instance ``simulate`` with ``fill_chunk < M`` forcing the
        chunked greedy fill's ``while_loop`` path (checkify must
        discharge the full check set through it);
      * the WAN path: ``NetworkAwareDPPPolicy`` on the congested-uplink
        topology (transfer dynamics incl. the bw=inf-safe drain ratio);
      * fleet sweep with the clairvoyant forecaster + error model (the
        ``jax.random.normal`` corruption path);
      * single-instance ``simulate`` at the paper spec with full checks;
      * the fault layer: the blackout fleet under the staleness guard,
        the flappy-uplink WAN fleet (hard link flap -> the bw-scale
        ``inf * 0`` guard in ``step_links``), and a single-instance
        faulted run with full checks (outage masking, stochastic
        requeue rounding, and the wasted-emissions ledger must all
        stay NaN-free and in-bounds).
    """
    from repro.configs.fleet_scenarios import (
        build_fleet,
        build_network_fleet,
    )
    from repro.core.policies import (
        CarbonIntensityPolicy,
        LookaheadDPPPolicy,
    )
    from repro.core.simulator import simulate, sweep_forecast_errors
    from repro.forecast import (
        ClairvoyantTableForecaster,
        SeasonalNaiveForecaster,
    )
    from repro.configs.fleet_scenarios import with_faults
    from repro.faults import StalenessGuardPolicy
    from repro.network import NetworkAwareDPPPolicy

    key = jax.random.PRNGKey(0)
    fleet = build_fleet(["diurnal-slack"], per_kind=SMOKE_PER_KIND,
                        M=SMOKE_M, N=SMOKE_N, Tc=24, seed=0)
    wan = build_network_fleet(["congested-uplink"],
                              per_kind=SMOKE_PER_KIND, M=SMOKE_M,
                              N=SMOKE_N, Tc=24, seed=0)
    cases = [
        ("fleet/diurnal-slack/ci",
         lambda: checkified_simulate_fleet(
             CarbonIntensityPolicy(), fleet, T, key)),
        ("fleet/diurnal-slack/lookahead-seasonal",
         lambda: checkified_simulate_fleet(
             LookaheadDPPPolicy(H=4), fleet, T, key,
             forecaster=SeasonalNaiveForecaster(H=4, period=6))),
        ("fleet/congested-uplink/aware",
         lambda: checkified_simulate_fleet(
             NetworkAwareDPPPolicy(), wan, T, key)),
        ("fleet/diurnal-slack/clairvoyant-err",
         lambda: checkified_simulate_fleet(
             LookaheadDPPPolicy(H=4),
             sweep_forecast_errors(fleet, bias=0.05, noise=0.1), T, key,
             forecaster=ClairvoyantTableForecaster(H=4))),
        ("fleet/diurnal-slack+blackout/guard-ci",
         lambda: checkified_simulate_fleet(
             StalenessGuardPolicy(inner=CarbonIntensityPolicy()),
             with_faults(fleet, "regional-blackout"), T, key)),
        ("fleet/congested-uplink+flappy/guard-aware",
         lambda: checkified_simulate_fleet(
             StalenessGuardPolicy(inner=NetworkAwareDPPPolicy()),
             with_faults(wan, "flappy-uplink"), T, key)),
    ]

    # single-instance simulate() path (non-fleet entry point)
    from repro.configs.paper_workloads import paper_spec
    from repro.core.carbon import RandomCarbonSource
    from repro.core.simulator import UniformArrivals

    spec = paper_spec()

    def single(policy):
        def case():
            def run(k):
                return simulate(
                    policy, spec,
                    RandomCarbonSource(N=spec.N),
                    UniformArrivals(M=spec.M), T, k,
                )

            return jax.jit(
                checkify.checkify(run, errors=DEFAULT_CHECKS)
            )(key)

        return case

    cases.append(("single/paper-spec/ci", single(CarbonIntensityPolicy())))
    # fill_chunk < M forces the chunked greedy fill's while_loop; the
    # full check set (incl. OOB) must discharge through it
    cases.append(("single/paper-spec/chunked-fill-while-loop",
                  single(CarbonIntensityPolicy(fill_chunk=2))))

    # single-instance faulted path with the full check set: brownouts +
    # telemetry dropouts + task failures exercise the requeue rounding
    # and the wasted-emissions ledger under OOB instrumentation too
    from repro.faults import make_faults, simulate_faulted

    def single_faulted():
        fp = make_faults(
            spec.N, cloud_p_down=0.05, cloud_p_up=0.3,
            brown_p_start=0.1, brown_p_end=0.2, brown_floor=0.5,
            telem_p_down=0.2, telem_p_up=0.2, task_p_fail=0.1,
        )

        def run(k):
            return simulate_faulted(
                StalenessGuardPolicy(inner=CarbonIntensityPolicy()),
                spec, fp, RandomCarbonSource(N=spec.N),
                UniformArrivals(M=spec.M), T, k,
            )

        return jax.jit(checkify.checkify(run, errors=DEFAULT_CHECKS))(key)

    cases.append(("single/paper-spec+faults/guard-ci", single_faulted))

    # deadline layer with the full check set: slack math runs through
    # +inf (empty queues / no deadline) and the admission cap through
    # an inf branch -- both must stay NaN- and div-by-zero-free with
    # shedding active, and the age-ring scatter in-bounds
    def single_deadlines():
        import numpy as np

        from repro.deadlines import SlackThresholdPolicy, make_deadlines

        dl = make_deadlines(
            spec.M,
            deadline=np.array([1.0, 3.0, np.inf, 2.0, np.inf],
                              np.float32)[: spec.M],
            window=2.0, shed_on=1.0, headroom=0.8,
        )

        def run(k):
            return simulate(
                SlackThresholdPolicy(), spec,
                RandomCarbonSource(N=spec.N),
                UniformArrivals(M=spec.M), T, k, deadlines=dl,
            )

        return jax.jit(checkify.checkify(run, errors=DEFAULT_CHECKS))(key)

    cases.append(("single/paper-spec+deadlines/slack-shed",
                  single_deadlines))

    results: List[Tuple[str, str | None]] = []
    for name, runner in cases:
        try:
            err, res = runner()
            jax.block_until_ready(res)
            results.append((name, err.get()))
        except Exception as e:  # checkify lift itself failed
            results.append((name, f"checkify lift failed: {e}"))
    return results
