"""Host time per decode launch, over every round: ``PagedStats.decode_s``
(each launch ends in the fetch of its tokens) over the decode steps."""


def read(run):
    steps = sum(r.stats.steps for r in run.rounds)
    if not steps:
        return None
    return 1e3 * sum(r.stats.decode_s for r in run.rounds) / steps
