"""Queueing-network simulator (paper §V numerical analysis).

A single `lax.scan` over time slots: observe carbon intensity + arrivals,
act with the policy, account emissions (eq. 5), step the dynamics
(eqs. 7-8). Fully jittable; `simulate_vsweep` vmaps the whole simulation
over a vector of V values (beyond-paper: the paper's Figs. 2/4 tradeoff
curve computed in one compiled call).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.carbon import TableCarbonSource
from repro.core.queueing import (
    Action,
    NetworkSpec,
    NetworkState,
    emissions,
    init_state,
    step,
)
from repro.telemetry.profile import phase
from repro.telemetry.stream import split_telemetry, stream_flush
from repro.telemetry.taps import (
    TelemetryProbe,
    finalize_taps,
    init_taps,
    step_taps,
)

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class UniformArrivals:
    """a_m(t) ~ U{0..amax} i.i.d. (paper §V uses amax=400)."""

    M: int
    amax: int = 400

    def __call__(self, t: Array, key: Array) -> Array:
        k = jax.random.fold_in(key, t)
        return jax.random.randint(k, (self.M,), 0, self.amax + 1).astype(
            jnp.float32
        )

    @property
    def a_max(self) -> float:
        return float(self.amax)


@dataclasses.dataclass(frozen=True)
class PoissonArrivals:
    """a_m(t) ~ Poisson(rate_m), clipped at `clip` to keep a_m bounded
    (Lemma 1 requires bounded arrivals)."""

    rates: tuple
    clip: int = 2000

    def __call__(self, t: Array, key: Array) -> Array:
        k = jax.random.fold_in(key, t)
        lam = jnp.asarray(self.rates, jnp.float32)
        return jnp.minimum(
            jax.random.poisson(k, lam).astype(jnp.float32), float(self.clip)
        )

    @property
    def a_max(self) -> float:
        return float(self.clip)


def init_forecaster_carry(forecaster, N, key, carbon_source, error_params):
    """Builds the forecaster's scan carry the one canonical way (shared
    by `simulate` and the WAN `simulate_network`): hand over the carbon
    key, the playback table when the source carries one, and the
    per-run (bias, noise) ForecastErrorModel override when given --
    omitted entirely otherwise so third-party forecasters without an
    `error` kwarg keep working."""
    init_kwargs = {}
    if error_params is not None:
        init_kwargs["error"] = error_params
    return forecaster.init(
        N,
        key=key,
        table=getattr(carbon_source, "table", None),
        **init_kwargs,
    )


class SimResult(NamedTuple):
    emissions: Array      # [T] per-slot carbon emissions C(t)
    cum_emissions: Array  # [T] cumulative sum
    Qe: Array             # [R, M] edge queue trajectory (post-step)
    Qc: Array             # [R, M, N] cloud queue trajectory (post-step)
    dispatched: Array     # [T] total tasks dispatched
    processed: Array      # [T] total tasks processed
    energy_edge: Array    # [T] edge energy spent
    energy_cloud: Array   # [T, N] cloud energy spent
    telemetry: object = None  # repro.telemetry.Telemetry frame, or None
    deadlines: object = None  # repro.deadlines.DeadlineLedger, or None

    # R depends on the `record` mode: T for "full" (every slot), 1 for
    # "summary" (final state only), T//k for stride k (state at the end
    # of every k-th slot). Scalar series always cover all T slots, and
    # Qe[-1]/Qc[-1] is the final state in every mode.

    @property
    def final_backlog(self) -> Array:
        return self.Qe[-1].sum() + self.Qc[-1].sum()


def _record_scan(body, state_of, carry0, T, record,
                 stream=None, lane=None):
    """Shared scan driver for the recording modes.

    `body(carry, t) -> (carry, scalars)` runs one slot and emits the
    per-slot scalar tuple; `state_of(carry)` extracts the (large) queue
    trajectories to record. Modes:

    * "full"    -- one scan, states recorded every slot ([T, ...]).
    * "summary" -- one scan, scalars only; the final state is recorded
      once ([1, ...]), so device memory stops scaling as O(T * state).
    * stride k  -- scan of scans: the inner scan covers k slots of
      scalars, the outer scan snapshots the post-step state once per
      chunk ([T//k, ...] -- the rows "full" records at slots k-1,
      2k-1, ...). Requires k to divide T.

    Per-slot scalar ops are identical in every mode (same `body`), so
    the scalar series agree bitwise across modes; only the recorded
    queue trajectories differ in length.

    `stream` (a telemetry.stream.StreamConfig) turns on live flushes:
    every mode restructures into the stride-style scan of
    T//flush_every chunks and `stream_flush` hands each chunk's stacked
    TapSeries (the last element of the body's scalar tuple -- streaming
    requires taps-on bodies) to the host channel, tagged with `lane`
    (the fleet lane id; 0 when None). The per-slot values are the same
    `body` program, so streamed runs stay bitwise equal to batch runs.
    """
    if stream is not None:
        return _record_scan_streaming(
            body, state_of, carry0, T, record, stream,
            jnp.int32(0) if lane is None else lane,
        )
    if record == "full":
        def with_state(carry, t):
            carry, scalars = body(carry, t)
            return carry, (scalars, state_of(carry))

        carry, (scalars, states) = jax.lax.scan(
            with_state, carry0, jnp.arange(T)
        )
        return scalars, states
    if record == "summary":
        carry, scalars = jax.lax.scan(body, carry0, jnp.arange(T))
        states = jax.tree.map(lambda x: x[None], state_of(carry))
        return scalars, states
    if not isinstance(record, int) or record <= 0 or T % record != 0:
        raise ValueError(
            f"record={record!r} must be 'full', 'summary', or a positive "
            f"int stride dividing T={T}"
        )
    k = record

    def chunk(carry, ts):
        carry, scalars = jax.lax.scan(body, carry, ts)
        return carry, (scalars, state_of(carry))

    carry, (scalars, states) = jax.lax.scan(
        chunk, carry0, jnp.arange(T).reshape(T // k, k)
    )
    scalars = jax.tree.map(
        lambda x: x.reshape((T,) + x.shape[2:]), scalars
    )
    return scalars, states


def _record_scan_streaming(body, state_of, carry0, T, record, stream,
                           lane):
    """The streaming variants of the recording modes: a scan of
    T//flush_every chunks, each an inner scan of `body` followed by one
    unconditional `stream_flush` of the chunk's TapSeries slice. The
    per-slot program is untouched, so scalar outputs stay bitwise equal
    to the non-streaming modes (the stride mode above already proves
    scan-of-scans stacking is value-neutral)."""
    k = stream.flush_every
    if T % k != 0:
        raise ValueError(
            f"streaming needs flush_every={k} to divide T={T}"
        )
    if record not in ("full", "summary"):
        if not isinstance(record, int) or record != k:
            raise ValueError(
                f"streaming runs chunk the scan at flush_every={k}; "
                f"record must be 'full', 'summary', or the stride "
                f"{k} itself (got record={record!r})"
            )
    ts = jnp.arange(T).reshape(T // k, k)

    def flat(x):  # [T//k, k, ...] -> [T, ...]
        return x.reshape((T,) + x.shape[2:])

    if record == "full":
        def with_state(carry, t):
            carry, scalars = body(carry, t)
            return carry, (scalars, state_of(carry))

        def chunk(carry, tsk):
            carry, (scalars, states) = jax.lax.scan(
                with_state, carry, tsk
            )
            stream_flush(stream, lane, tsk[0], scalars[-1])
            return carry, (scalars, states)

        carry, (scalars, states) = jax.lax.scan(chunk, carry0, ts)
        return (jax.tree.map(flat, scalars),
                jax.tree.map(flat, states))

    if record == "summary":
        def chunk(carry, tsk):
            carry, scalars = jax.lax.scan(body, carry, tsk)
            stream_flush(stream, lane, tsk[0], scalars[-1])
            return carry, scalars

        carry, scalars = jax.lax.scan(chunk, carry0, ts)
        states = jax.tree.map(lambda x: x[None], state_of(carry))
        return jax.tree.map(flat, scalars), states

    def chunk(carry, tsk):
        carry, scalars = jax.lax.scan(body, carry, tsk)
        stream_flush(stream, lane, tsk[0], scalars[-1])
        return carry, (scalars, state_of(carry))

    carry, (scalars, states) = jax.lax.scan(chunk, carry0, ts)
    return jax.tree.map(flat, scalars), states


def as_data(spec: NetworkSpec, graph=None):
    """(spec, graph) behind an optimization barrier: what every run
    computes from them is then the same whether the caller passed them
    into `jax.jit` or closed over them. Closed over, they are XLA
    constants, and XLA's simplifier rewrites arithmetic on constants
    into forms that round differently (a closed-over fleet or route
    graph loses a multiply of the score pass); the greedy fill turns
    one ulp into a different decision, so the reference backend would
    drift from the Pallas kernel, whose op order is fixed."""
    pe, pc, Pe, Pc, graph = jax.lax.optimization_barrier(
        (*spec.as_arrays(), graph)
    )
    return NetworkSpec(pe=pe, pc=pc, Pe=Pe, Pc=Pc), graph


def simulate(
    policy: Callable,
    spec: NetworkSpec,
    carbon_source: Callable,
    arrival_source: Callable,
    T: int,
    key: Array,
    state0: NetworkState | None = None,
    forecaster: Callable | None = None,
    graph=None,
    error_params=None,
    record: str | int = "full",
    faults=None,
    telemetry=None,
    stream_lane=None,
    deadlines=None,
) -> SimResult:
    """Runs the network for T slots under `policy`.

    `record` controls how much trajectory the result carries: "full"
    (default) stacks the post-step queues every slot; "summary" keeps
    only the final state (Qe/Qc come back with a length-1 leading axis,
    so `Qe[-1]` and `final_backlog` work unchanged); an int stride k
    snapshots the state every k-th slot ([T//k, ...]). The per-slot
    scalar series (emissions/dispatched/processed/energy) cover all T
    slots bitwise identically in every mode -- see `_record_scan`.

    When `forecaster` is given (see repro.forecast), its carry threads
    through the scan next to the queue state: every slot the observed
    intensity row updates the forecaster, its [H, N+1] prediction is
    handed to the policy as `forecast=`, and emissions are still
    accounted against the TRUE intensities -- forecast error can only
    mislead the policy, never the ledger. The forecaster sees the
    carbon key (so clairvoyant wrappers predict the realized world) and
    the playback table when the source carries one
    (`carbon_source.table`, e.g. TableCarbonSource / fleet lanes).
    Policies consuming forecasts must accept a `forecast` kwarg
    (LookaheadDPPPolicy does).

    `error_params = (bias, noise)` overrides the forecaster's
    ForecastErrorModel parameters for this run (traced values allowed:
    `simulate_fleet` uses it to sweep forecast quality across vmapped
    lanes; clairvoyant forecasters honor it, statistical ones ignore
    it).

    When `graph` (a repro.network.LinkGraph) is given the run goes
    through the WAN transfer layer instead: the in-flight queue
    Qt [M, L] joins the scan carry, the policy is called with
    `graph=`/`Qt=` keywords and must return a NetAction, and the result
    is a NetSimResult (extra Qt / delivered / energy_transfer fields).

    When `faults` (a repro.faults.FaultParams) is given the run goes
    through the fault layer (repro.faults.sim): outage/brownout/
    telemetry chains join the scan carry, the policy sees observed
    (possibly stale) intensities, capacity-masked budgets and a
    `fault_view=` kwarg, and the result is a FaultSimResult. With
    `faults=None` this body is untouched, and with all fault rates zero
    the faulted body is bitwise-identical to it (tests/test_faults.py).

    `telemetry` (a repro.telemetry.TelemetryConfig, trace-time static)
    turns on the in-scan metrics taps and SLO monitors: the result's
    `.telemetry` field then carries a Telemetry frame of per-slot
    series, run gauges, and structured alert records (DESIGN.md
    §Observability). With `telemetry=None` the tap carry is `()` (zero
    pytree leaves) and the run is bit-identical to a build without the
    telemetry layer -- a standing parity anchor
    (tests/test_telemetry.py, asserted again before bench timing).
    A `repro.telemetry.StreamConfig` additionally flushes TapSeries
    slices to a host channel every `flush_every` slots while the scan
    runs (DESIGN.md §Live observability): same tap values bitwise, but
    the traced program carries an io_callback, so only audit-allowlisted
    combos may stream. `stream_lane` tags those flushes with the fleet
    lane id (set by `simulate_fleet`; defaults to lane 0).

    When `deadlines` (a repro.deadlines.DeadlineParams) is given, the
    age-ringed deadline state joins the scan carry: the policy is
    called with a `deadline_view=` kwarg, overdue tasks expire into the
    result's `.deadlines` ledger (missed/shed/admitted series plus the
    recorded `Qd` rings), admission control may shed arrivals, and the
    telemetry probe's missed/shed fields go live. With
    `deadlines=no_deadlines(M)` (all-infinite, shedding off) every
    shared result field is bitwise-identical to the `deadlines=None`
    run -- the subsystem's standing parity anchor
    (tests/test_deadlines.py).
    """
    spec, graph = as_data(spec, graph)
    if graph is not None:
        from repro.network.sim import simulate_network

        return simulate_network(
            policy, spec, graph, carbon_source, arrival_source, T, key,
            state0=state0, forecaster=forecaster,
            error_params=error_params, record=record, faults=faults,
            telemetry=telemetry, stream_lane=stream_lane,
            deadlines=deadlines,
        )
    if faults is not None:
        from repro.faults.sim import simulate_faulted

        return simulate_faulted(
            policy, spec, faults, carbon_source, arrival_source, T, key,
            state0=state0, forecaster=forecaster,
            error_params=error_params, record=record,
            telemetry=telemetry, stream_lane=stream_lane,
            deadlines=deadlines,
        )
    telemetry, stream = split_telemetry(telemetry)
    pe, pc, _, _ = spec.as_arrays()
    if state0 is None:
        state0 = init_state(spec.M, spec.N)
    if deadlines is not None:
        from repro.deadlines.model import (
            DeadlineLedger,
            deadline_view,
            init_deadlines,
            step_deadlines,
        )
    k_carbon, k_arrive, k_policy = jax.random.split(key, 3)

    if forecaster is not None:
        fcarry0 = init_forecaster_carry(
            forecaster, spec.N, k_carbon, carbon_source, error_params
        )

    def body(carry, t):
        state, fcarry, tap, dstate = carry
        with phase("carbon"):
            Ce, Cc = carbon_source(t, k_carbon)
        with phase("arrivals"):
            a = arrival_source(t, k_arrive)
        k_t = jax.random.fold_in(k_policy, t)
        pkw = {}
        if deadlines is not None:
            pkw["deadline_view"] = deadline_view(deadlines, dstate)
        if forecaster is None:
            act: Action = policy(state, spec, Ce, Cc, a, k_t, **pkw)
        else:
            fcarry = forecaster.update(
                fcarry, jnp.concatenate([Ce[None], Cc])
            )
            act = policy(
                state, spec, Ce, Cc, a, k_t,
                forecast=forecaster.predict(fcarry, t), **pkw,
            )
        C_t = emissions(spec, act, Ce, Cc)
        if deadlines is None:
            nxt = step(state, act, a)
            missed = shed = jnp.float32(0.0)
        else:
            d_sum = jnp.sum(act.d, axis=1)
            dstate, admitted, expired, shed_v = step_deadlines(
                deadlines, dstate, d_sum, a
            )
            # Same queue update as `step`, with arrivals replaced by
            # (admitted - expired): bitwise `+ a` under the
            # no_deadlines anchor (admitted == a, expired == +0.0).
            with phase("queue_update"):
                nxt = NetworkState(
                    Qe=jnp.maximum(state.Qe - d_sum, 0.0)
                    + admitted - expired,
                    Qc=jnp.maximum(state.Qc - act.w, 0.0) + act.d,
                )
            missed = jnp.sum(expired)
            shed = jnp.sum(shed_v)
        out = (
            C_t,
            jnp.sum(act.d),
            jnp.sum(act.w),
            jnp.sum(act.d * pe[:, None]),
            jnp.sum(act.w * pc, axis=0),
        )
        if deadlines is not None:
            out = out + (missed, shed, jnp.sum(admitted))
        if telemetry is None:
            return (nxt, fcarry, tap, dstate), out
        probe = TelemetryProbe(
            emissions=C_t,
            arrived=jnp.sum(a),
            dispatched=jnp.sum(act.d, axis=0),
            processed=jnp.sum(act.w),
            failed=jnp.float32(0.0),
            wasted=jnp.float32(0.0),
            backlog=jnp.sum(nxt.Qe) + jnp.sum(nxt.Qc),
            stale=jnp.int32(0),
            clouds_down=jnp.float32(0.0),
            retry_depth=jnp.float32(0.0),
            transfer_occupancy=jnp.float32(0.0),
            missed=missed,
            shed=shed,
        )
        tap, tseries = step_taps(telemetry, tap, probe)
        return (nxt, fcarry, tap, dstate), (out, tseries)

    carry0 = (
        state0,
        fcarry0 if forecaster is not None else (),
        init_taps() if telemetry is not None else (),
        init_deadlines(spec.M, deadlines.rings.shape[-1])
        if deadlines is not None else (),
    )
    if deadlines is None:
        state_of = lambda carry: (carry[0].Qe, carry[0].Qc)  # noqa: E731
    else:
        state_of = lambda carry: (  # noqa: E731
            carry[0].Qe, carry[0].Qc, carry[3].Qd
        )
    scalars, states = _record_scan(
        body, state_of, carry0, T,
        record, stream=stream, lane=stream_lane,
    )
    if telemetry is None:
        scal, tel = scalars, None
    else:
        scal, tseries = scalars
        tel = finalize_taps(telemetry, tseries)
    if deadlines is None:
        (C, disp, proc, ee, ec) = scal
        (Qe, Qc), led = states, None
    else:
        (C, disp, proc, ee, ec, missed, shed, adm) = scal
        Qe, Qc, Qd = states
        led = DeadlineLedger(missed=missed, shed=shed, admitted=adm,
                             Qd=Qd)
    return SimResult(
        emissions=C,
        cum_emissions=jnp.cumsum(C),
        Qe=Qe,
        Qc=Qc,
        dispatched=disp,
        processed=proc,
        energy_edge=ee,
        energy_cloud=ec,
        telemetry=tel,
        deadlines=led,
    )


def simulate_vsweep(
    make_policy: Callable[[Array], Callable],
    Vs: Array,
    spec: NetworkSpec,
    carbon_source: Callable,
    arrival_source: Callable,
    T: int,
    key: Array,
) -> SimResult:
    """vmaps the full simulation over a vector of V values.

    `make_policy(V)` must build a policy whose only V-dependence flows
    through traced arithmetic (CarbonIntensityPolicy qualifies).
    """

    def one(V):
        return simulate(
            make_policy(V), spec, carbon_source, arrival_source, T, key
        )

    return jax.vmap(one)(jnp.asarray(Vs, jnp.float32))


class FleetSpec(NamedTuple):
    """Stacked NetworkSpec arrays; every field has leading fleet axis F."""

    pe: Array  # [F, M]
    pc: Array  # [F, M, N]
    Pe: Array  # [F]
    Pc: Array  # [F, N]


class FleetScenario(NamedTuple):
    """A stack of F independent simulation instances.

    One FleetScenario = one compiled `simulate_fleet` call sweeping F
    region x workload-mix scenarios. Carbon is a playback table per
    instance (col 0 = edge, cols 1..N = clouds; rows repeat modulo the
    table length), arrivals are per-type uniform U{0..amax} draws so the
    whole scenario is a pytree of arrays that vmaps.

    Optional axes (None = feature off for the whole fleet):
      graph     -- a stacked repro.network.LinkGraph (leading axis F):
                   every lane simulates through the WAN transfer layer
                   and the result is a NetSimResult.
      err_bias / err_noise -- [F] per-lane ForecastErrorModel overrides,
                   handed to the forecaster's init as
                   `error=(bias, noise)`: ONE compiled call sweeps
                   forecast quality across lanes.
      faults    -- stacked repro.faults.FaultParams (leading axis F):
                   every lane simulates through the fault layer and the
                   result is a FaultSimResult / NetFaultSimResult. See
                   configs.fleet_scenarios.with_faults for the scenario
                   registry.
      deadlines -- stacked repro.deadlines.DeadlineParams (leading axis
                   F): every lane simulates through the deadline layer
                   (expiry, admission control, `deadline_view=` to the
                   policy) and the result carries a DeadlineLedger. See
                   configs.fleet_scenarios.with_deadlines.
    """

    spec: FleetSpec
    carbon: Array        # [F, Tc, N+1] intensity playback tables
    arrival_amax: Array  # [F, M] per-type uniform arrival caps
    graph: object | None = None       # stacked LinkGraph or None
    err_bias: Array | None = None     # [F] forecast bias per lane
    err_noise: Array | None = None    # [F] forecast noise per lane
    faults: object | None = None      # stacked FaultParams or None
    deadlines: object | None = None   # stacked DeadlineParams or None

    @property
    def F(self) -> int:
        return self.arrival_amax.shape[0]


def stack_scenarios(instances, graphs=None) -> FleetScenario:
    """Stacks an iterable of (NetworkSpec, carbon_table [Tc,N+1],
    amax [M]) triples into one FleetScenario. Tables must share Tc and
    specs must share (M, N). `graphs`, when given, is a parallel
    iterable of LinkGraphs (sharing M, N, L) stacked onto the fleet's
    graph axis."""
    pes, pcs, Pes, Pcs, tabs, amaxs = [], [], [], [], [], []
    for spec, table, amax in instances:
        pe, pc, Pe, Pc = spec.as_arrays()
        pes.append(pe)
        pcs.append(pc)
        Pes.append(Pe)
        Pcs.append(Pc)
        tabs.append(jnp.asarray(table, jnp.float32))
        amaxs.append(jnp.broadcast_to(
            jnp.asarray(amax, jnp.float32), pe.shape
        ))
    fleet = FleetScenario(
        spec=FleetSpec(
            pe=jnp.stack(pes), pc=jnp.stack(pcs),
            Pe=jnp.stack(Pes), Pc=jnp.stack(Pcs),
        ),
        carbon=jnp.stack(tabs),
        arrival_amax=jnp.stack(amaxs),
    )
    if graphs is not None:
        from repro.network.graph import stack_graphs

        fleet = fleet._replace(graph=stack_graphs(list(graphs)))
    return fleet


def sweep_forecast_errors(
    fleet: FleetScenario, bias, noise
) -> FleetScenario:
    """Attaches per-lane ForecastErrorModel parameters ([F] arrays or
    scalars, broadcast) so one compiled `simulate_fleet` call sweeps
    forecast quality across lanes instead of looping configs."""
    F = fleet.F
    return fleet._replace(
        err_bias=jnp.broadcast_to(
            jnp.asarray(bias, jnp.float32), (F,)
        ),
        err_noise=jnp.broadcast_to(
            jnp.asarray(noise, jnp.float32), (F,)
        ),
    )


def simulate_fleet(
    policy: Callable,
    fleet: FleetScenario,
    T: int,
    key: Array,
    forecaster: Callable | None = None,
    record: str | int = "full",
    telemetry=None,
) -> SimResult:
    """Runs F independent network instances for T slots in ONE compiled
    call: the full `simulate` scan is vmapped over the stacked
    (spec, carbon table, arrival caps) axes, so sweeping 64+ scenarios
    costs one compilation and one device dispatch.

    Returns a SimResult whose every field carries a leading fleet axis
    [F, ...] (index before using reductions like `final_backlog`);
    a NetSimResult when the fleet carries a stacked LinkGraph.
    Instance f draws its own arrival/policy randomness from
    `jax.random.split(key, F)[f]`.

    `record` threads through to every lane's `simulate`: full-recording
    fleet memory scales as O(F * T * M * N); `record="summary"` keeps
    only per-slot scalars plus the final state ([F, 1, M] / [F, 1, M, N])
    -- the mode that unlocks F >= 512 lanes in one compiled call.

    `telemetry` threads to every lane: the result's `.telemetry` frame
    carries a leading [F] axis on every field (select one lane with
    `repro.telemetry.lane`, or reduce the fleet with
    `repro.telemetry.manifest`). A StreamConfig streams every lane to
    the same channel with `lane=f` payload tags (the vmapped
    io_callback fires once per lane per chunk with unbatched slices,
    so the tag is the only lane identity a consumer gets); the lane
    axis only joins the vmap when streaming is on, keeping the
    batch-telemetry program untouched.
    """
    F = fleet.F
    M = fleet.arrival_amax.shape[1]
    keys = jax.random.split(key, F)
    streaming = split_telemetry(telemetry)[1] is not None
    lanes = jnp.arange(F, dtype=jnp.int32) if streaming else None

    def one(pe, pc, Pe, Pc, ctab, amax, k, graph, err, faults, dl,
            lane):
        spec = NetworkSpec(pe=pe, pc=pc, Pe=Pe, Pc=Pc)
        # TableCarbonSource traces fine with a batched ctab; its .table
        # attribute is also how simulate() hands each lane's slab to
        # table-backed forecasters.
        carbon_source = TableCarbonSource(table=ctab)

        def arrival_source(t, kk):
            u = jax.random.uniform(jax.random.fold_in(kk, t), (M,),
                                   dtype=jnp.float32)
            return jnp.floor(u * (amax + 1.0))

        return simulate(
            policy, spec, carbon_source, arrival_source, T, k,
            forecaster=forecaster, graph=graph, error_params=err,
            record=record, faults=faults, telemetry=telemetry,
            stream_lane=lane, deadlines=dl,
        )

    err = (
        (fleet.err_bias, fleet.err_noise)
        if fleet.err_bias is not None else None
    )
    return jax.vmap(
        one,
        in_axes=(0, 0, 0, 0, 0, 0, 0,
                 0 if fleet.graph is not None else None,
                 0 if err is not None else None,
                 0 if fleet.faults is not None else None,
                 0 if fleet.deadlines is not None else None,
                 0 if streaming else None),
    )(
        fleet.spec.pe, fleet.spec.pc, fleet.spec.Pe, fleet.spec.Pc,
        fleet.carbon, fleet.arrival_amax, keys, fleet.graph, err,
        fleet.faults, fleet.deadlines, lanes,
    )


def mean_rate_stability_metric(result: SimResult) -> Array:
    """E[Q(T)]/T proxy for (10)-(11): total terminal backlog over horizon.
    A mean-rate-stable system drives this toward 0 as T grows."""
    T = result.emissions.shape[0]
    return result.final_backlog / T
