"""Phase scopes and host spans for xprof / Perfetto traces.

Device side: `phase("policy_score")` is a thin wrapper around
`jax.named_scope`: it attaches a `repro.<name>/` prefix to every HLO op
traced under it, so a profiler timeline shows the simulator's slot
anatomy (the arrival draw, the carbon lookup, the score pass, the fill,
the queue update, the emissions, and the WAN, fault and deadline steps)
instead of a wall of fused ops. Scopes are metadata only: they never
change the computation, so every bit-parity anchor in the test suite
holds with them in place.

Host side: `span("serve.dispatch")` is a `jax.profiler.TraceAnnotation`,
a named interval on the host timeline of the same trace, on the
profiler's clock shared with the device timeline. `serve_loop` wraps
each slot's host work in the four `HOST_SPANS`, in order:

- `serve.dispatch`: the step call up to its return (argument
  conversion, the jit's dispatch, the enqueue);
- `serve.sync`: `block_until_ready` on the slot's metrics;
- `serve.pull`: the metrics' transfer to the host as Python floats;
- `serve.bookkeeping`: the run totals, the queue-age FIFO and the live
  exporter's record.

The first two lie inside the loop's latency bracket, the last two after
it. With no profiler session running a span only costs its entry and
exit (under a microsecond each on a CPU host).

To record a served trace, run the loop inside `trace_to`:

    with trace_to("artifacts/trace"):
        serve_loop(policy, spec, carbon, arrivals, T, key)

and open the directory in xprof/TensorBoard, or read its `.xplane.pb`
with `jax.profiler.ProfileData`.

The canonical names live in `PHASES` and `HOST_SPANS` so dashboards
and trace post-processors can rely on them.
"""
from __future__ import annotations

import contextlib

import jax

# The slot anatomy, in execution order. Keep in sync with the scopes
# placed in core/queueing.py, core/simulator.py, core/policies.py,
# serve/loop.py, network/, faults/model.py and deadlines/model.py.
PHASES = (
    "carbon",         # the slot's carbon intensities
    "arrivals",       # the slot's arrival draw
    "policy_score",   # DPP score tables (reference or pallas backend)
    "route_score",    # WAN (type, route, cloud) score tables
    "greedy_fill",    # chunked top_k budget fill
    "fill_dense",     # the fill's one-hot permute on rows of <= 128 items
                      # (nested in greedy_fill: which path each fill took)
    "emissions",      # the action's carbon emissions (eq. 5)
    "transfer_step",  # link injection / drain / delivery
    "fault_step",     # fault chain transitions + observation masking
    "fault_retry",    # failure draws + retry-pool backoff
    "deadline_step",  # deadline rings: drain, age, expiry, admission
    "queue_update",   # queue dynamics, eqs. (7)-(8)
)

# The served loop's host spans, in the order each slot runs them.
HOST_SPANS = (
    "serve.dispatch",
    "serve.sync",
    "serve.pull",
    "serve.bookkeeping",
)


def phase(name: str):
    """Context manager labelling ops traced inside it as `repro.<name>`."""
    return jax.named_scope(f"repro.{name}")


def span(name: str):
    """Context manager recording a host interval `name` in a running
    profiler trace; without one it records nothing."""
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def trace_to(logdir: str):
    """Host-side convenience: records a `jax.profiler` trace (viewable
    in xprof/TensorBoard or as a Perfetto dump) for the enclosed block.
    Purely host-side -- never call under jit."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
