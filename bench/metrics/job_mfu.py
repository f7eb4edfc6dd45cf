"""The whole job's share of the chip's roofline, in %.

The least time of one job's work (as for ``walker_roofline``) over the
job's measured wall time: the untraced window over the jobs it answered,
so the profiler's cost on the host does not lower it. It bounds every
kernel's share from above and stays when a kernel is renamed or taken off
the path. Reported beside the other per-layer metrics.
"""


def read(m):
    return 100.0 * m.least_s * m.jobs / m.window_s if m.jobs else None
