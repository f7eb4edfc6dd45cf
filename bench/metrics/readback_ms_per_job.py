"""Host time in the program's readback per job, in ms, from the trace.

The summed wall time of the ``devdag.readback`` host spans (one per
shard of a walker launch, opened by ``repro.core.telemetry.phase`` in
``dag_walk_sharded``: the host copy of a shard's outputs, in which the
host waits for the device) in the traced window, over the answers
returned in that window. A tenant of a coalesced launch counts as one
job, as in ``jobs_per_s``; a fixed-point job sums its rounds. The
profiler's own cost on the host is in it. A trace without such a span
gives nothing.
"""

SPAN = "devdag.readback"


def read(m):
    if m.trace is None or not m.trace_jobs:
        return None
    s = m.trace.span_seconds.get(SPAN)
    return s / m.trace_jobs * 1e3 if s else None
