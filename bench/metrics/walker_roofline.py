"""The walker kernel's share of its roofline, in %.

The least time one job's work needs on this chip (the larger of its
operations over peak FLOP/s and its bytes over peak bytes/s, from the
configuration's count of the least work and ``bench/peaks.py``) over the
walker's device time per job in the traced window. The count is of the
least work any implementation needs, so no implementation can read over
100%.
"""

from bench.metrics.walker_ms_per_job import walker_seconds


def read(m):
    s = walker_seconds(m)
    return 100.0 * m.least_s * m.trace_jobs / s if s and m.trace_jobs else None
