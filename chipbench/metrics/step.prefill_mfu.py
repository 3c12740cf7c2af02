"""Model FLOPs of the prompts over the host time inside packed-prefill
launches (``PagedStats.prefill_s``) and the chip's bf16 peak."""
from chipbench import work


def read(run):
    secs = sum(r.stats.prefill_s for r in run.rounds)
    if not run.peak or secs <= 0:
        return None
    pre, _ = work.model_flops(run.dims, work.served(run.rounds))
    return 100.0 * pre / (secs * run.cell.chips * run.peak["bf16_flops_per_s"])
