"""95th percentile over every request of the window, from its call's send
to its last token."""
import numpy as np

UNIT, SOURCE = "ms", "host_clock"


def read(run):
    if not run.latencies_s:
        return None
    return float(np.percentile(np.asarray(run.latencies_s), 95) * 1e3)
