"""Share of the packed varlen prefill kernel's whole (q block, key stage)
grid that it iterates, over every round: ``PagedStats.prefill_kv_live``
(the work items of each launch the kernel runs) over
``PagedStats.prefill_kv_rect`` (``nqb * (bound + nqb)``, the grid the
kernel ran before it skipped dead pairs).  None where the program counts
neither, or ran no launch on the kernel."""


def read(run):
    rect = sum(getattr(rd.stats, "prefill_kv_rect", 0) for rd in run.rounds)
    if not rect:
        return None
    return 100.0 * sum(rd.stats.prefill_kv_live for rd in run.rounds) / rect
