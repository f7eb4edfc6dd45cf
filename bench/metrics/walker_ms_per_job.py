"""Device time of the fused walker kernel per job, in ms, from the trace.

The walker's device seconds in the traced window over the jobs answered
in that window.

The walker (``kernels/dag_walk.py:walk_call``) is a ``pallas_call`` without
a ``name=``. On a TPU v5e its operation in the trace's ``XLA Ops`` line is
named by its HLO text, ``%tpu_custom_call.1 = (...) custom-call(...),
custom_call_target="tpu_custom_call", ...``, as is every Mosaic kernel.
The walker is the only Mosaic kernel these cells run, so KERNEL matches
the custom-call target; a trace without such an operation gives nothing.
"""

KERNEL = r'custom_call_target="tpu_custom_call"'


def walker_seconds(m):
    """Summed device seconds of the walker's operations in the traced window, or None."""
    if m.trace is None:
        return None
    seconds, count = m.trace.matching(KERNEL)
    return seconds if count else None


def read(m):
    s = walker_seconds(m)
    return s / m.trace_jobs * 1e3 if s and m.trace_jobs else None
