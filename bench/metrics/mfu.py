"""Required model FLOPs of the traced steps (``lib/flops.py``) over the traced
window over the chip's bf16 peak."""
from lib import flops, peaks, trace


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    cfg, traffic = run.cell.config, run.cell.traffic
    work = sum(flops.step_flops(cfg, traffic, s) for s in run.window_steps)
    peak = peaks.peak(run.device_kind)["bf16_flops_per_s"]
    return 100.0 * work / trace.window_s(run.trace) / peak
