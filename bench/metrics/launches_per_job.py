"""Walker launches per job in the traced window: the loop's rounds as the device saw them.

The count of the walker's operations in the traced window (the Mosaic
kernel, matched as ``walker_ms_per_job`` matches it) over the jobs
answered in that window. A job of a fixed-point loop makes one launch
per round on one shard, the last round (which changes nothing)
included, so this is the round count of the seed's graph. The count
repeats exactly.
"""

from bench.metrics.walker_ms_per_job import KERNEL


def read(m):
    if m.trace is None or not m.trace_jobs:
        return None
    _, count = m.trace.matching(KERNEL)
    return count / m.trace_jobs if count else None
