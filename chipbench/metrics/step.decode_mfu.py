"""Model FLOPs of the decode steps over the host time inside decode
launches (``PagedStats.decode_s``) and the chip's bf16 peak."""
from chipbench import work


def read(run):
    secs = sum(r.stats.decode_s for r in run.rounds)
    if not run.peak or secs <= 0:
        return None
    _, dec = work.model_flops(run.dims, work.served(run.rounds))
    return 100.0 * dec / (secs * run.cell.chips * run.peak["bf16_flops_per_s"])
