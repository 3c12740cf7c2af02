"""Share of the serving slots holding a request, averaged over the decode
steps of every round (``PagedStats.mean_slot_occupancy`` over the slots)."""


def read(run):
    steps = sum(r.stats.steps for r in run.rounds)
    if not steps:
        return None
    busy = sum(r.stats.mean_slot_occupancy * r.stats.steps for r in run.rounds)
    return 100.0 * busy / steps / run.slots
