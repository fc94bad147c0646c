"""Telemetry: in-scan metrics taps, phase annotation, SLO monitors,
and host-side exporters (DESIGN.md §Observability).

Turn it on by passing `telemetry=TelemetryConfig()` to any simulator
(`simulate`, `simulate_network`, `simulate_faulted`,
`simulate_network_faulted`, `simulate_fleet`); the result's
`.telemetry` field then carries a `Telemetry` frame of per-slot series,
run gauges, and structured alert records. `telemetry=None` (the
default) is bit-identical to a build without this package.

Live mode: pass `telemetry=StreamConfig(flush_every=k)` instead and
attach a `follow_run` consumer -- TapSeries slices flush to a host
StreamChannel every k slots WHILE the scan runs, feeding the same
Prometheus/JSONL formats incrementally (DESIGN.md §Live observability;
the traced program then carries an io_callback and must be on the
jaxpr audit's effectful allowlist).
"""
from repro.telemetry.export import (
    FollowedRun,
    follow_run,
    manifest,
    oracle_gap_series,
    to_chrome_trace,
    to_jsonl,
    to_prometheus,
    validate_chrome_trace,
    validate_dir,
    validate_jsonl,
    validate_prometheus,
    write_run,
)
from repro.telemetry.monitors import MONITORS, monitor_conditions
from repro.telemetry.profile import HOST_SPANS, PHASES, phase, span, trace_to
from repro.telemetry.stream import (
    StreamChannel,
    StreamConfig,
    channel,
    reset_channel,
    split_telemetry,
)
from repro.telemetry.taps import (
    METRICS,
    MetricSpec,
    TapSeries,
    TapState,
    Telemetry,
    TelemetryConfig,
    TelemetryProbe,
    finalize_taps,
    init_taps,
    lane,
    step_taps,
)

__all__ = [
    "MONITORS",
    "METRICS",
    "HOST_SPANS",
    "PHASES",
    "FollowedRun",
    "MetricSpec",
    "StreamChannel",
    "StreamConfig",
    "TapSeries",
    "TapState",
    "Telemetry",
    "TelemetryConfig",
    "TelemetryProbe",
    "channel",
    "finalize_taps",
    "follow_run",
    "init_taps",
    "lane",
    "manifest",
    "reset_channel",
    "split_telemetry",
    "monitor_conditions",
    "oracle_gap_series",
    "phase",
    "span",
    "step_taps",
    "to_chrome_trace",
    "to_jsonl",
    "to_prometheus",
    "trace_to",
    "validate_chrome_trace",
    "validate_dir",
    "validate_jsonl",
    "validate_prometheus",
    "write_run",
]
