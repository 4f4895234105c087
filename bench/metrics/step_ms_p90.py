"""90th percentile of the host-clock time between the stamps of consecutive
steps in the window, in milliseconds."""
import numpy as np


def read(run):
    t = [run.stamp(k) for k in range(run.open_step, run.close_step + 1)]
    return float(np.percentile(np.diff(t), 90) * 1e3)
