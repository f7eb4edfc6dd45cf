"""Seconds from the process's start to the start of the measured window.

It holds JAX's start-up, the host's generation of the data, its transfer
to the device, the table build and the warm-up jobs with their compiles.
"""


def read(m):
    return m.setup_s
