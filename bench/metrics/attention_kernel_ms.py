"""Device time per traced step of the flash attention kernels
(``kernels/flash_attn``: forward, dK/dV and dQ custom calls), found in the
trace by their name. None where no such kernel ran."""
from lib import trace

KERNEL = "flash_attn_"   # flash_attn_fwd, flash_attn_dkv, flash_attn_dq


def read(run):
    if run.trace is None:
        return None
    events = trace.kernel_events(run.trace, KERNEL)
    if not events:
        return None
    spent = sum(e.end - e.start for e in events) / len(run.trace.devices)
    return spent / 1e6 / len(run.window_steps)
