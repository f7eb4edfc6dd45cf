"""Answered jobs over the length of the measured window, by the host's clock.

The window runs from the first job's call to the last job's answer, so a
stall anywhere in it (a compile, a collection) lowers the rate. In a
tenants mix every member of a launch counts as one job.
"""


def read(m):
    return m.jobs / m.window_s if m.jobs else None
