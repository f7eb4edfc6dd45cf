"""The share of the traced window in which the device ran no operation, in %.

One minus the union of the device's operation intervals over the window,
from the profiler's trace, averaged over the chips used.
"""


def read(m):
    return 100.0 * m.trace.idle_share if m.trace is not None else None
