"""Model FLOPs of every request served in the window over the window's
time and the chip's bf16 peak (``chipbench.work.model_flops``)."""
from chipbench import work


def read(run):
    if not run.peak or run.window_s <= 0:
        return None
    pre, dec = work.model_flops(run.dims, work.served(run.rounds))
    chips = run.cell.chips
    return 100.0 * (pre + dec) / (run.window_s * chips * run.peak["bf16_flops_per_s"])
