"""Smith-Waterman/Gotoh kernel rate: useful DP cells over the kernel's
device time, in GCUPS (1e9 cell updates per second).

Useful cells are counted from the benchmark's own inputs: every
non-center sequence against the center, each of the configuration's
length, for each family completed in the traced window. The kernel's
time is that of the SW forward kernel in the trace: the Pallas custom
call of ``gotoh_forward_pallas``.
"""

KERNEL = "gotoh_forward_pallas"


def read(ctx):
    tr = ctx["trace"]
    cells = ctx["work"].get("dp_cells", 0)
    if not tr or tr["truncated"] or not cells:
        return None
    seconds = tr["kernels"].get(KERNEL, 0.0)
    if seconds <= 0:
        return None
    return cells / seconds / 1e9
