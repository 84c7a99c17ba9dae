"""Share of the traced slice in which no operation ran on the device,
averaged over the chips."""
import numpy as np

UNIT = "%"


def read(run):
    t = run.trace
    if t is None or t.window_ns <= 0:
        return None
    return 100.0 * (1.0 - float(np.mean(t.busy_ns)) / t.window_ns)
