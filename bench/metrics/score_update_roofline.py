"""The Pallas Eq. (3.1) store update (``kernels/score_update``): the least
time its required bytes take at the chip's HBM bandwidth, over the kernel's
device time in the traced window. Found in the trace by its name."""
from lib import flops, peaks, trace

KERNEL = "fused_score_update"   # the op name of its custom call


def read(run):
    if run.trace is None:
        return None
    events = trace.kernel_events(run.trace, KERNEL)
    if not events:
        return None
    spent = sum(e.end - e.start for e in events) / 1e9
    need = len(events) * flops.score_update_bytes(
        run.cell.traffic["meta_batch"])
    bw = peaks.peak(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / bw / spent
