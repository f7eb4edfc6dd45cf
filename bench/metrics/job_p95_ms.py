"""The 95th percentile of every job's latency in the window, in ms.

A latency runs from the harness calling the program's entry to the job's
answer on the host. Every job of the window counts, none is left out.
"""

import numpy as np


def read(m):
    return float(np.percentile(m.latencies_s, 95)) * 1e3 if m.latencies_s else None
