"""Host time per iteration of the serving loop, over every round:
``PagedStats.host_s`` (each iteration's wall time less its waits for the
device, ``prefill:wait`` and ``decode:fetch``) over
``PagedStats.boundaries``.  None where the program counts no iteration."""


def read(run):
    n = sum(getattr(rd.stats, "boundaries", 0) for rd in run.rounds)
    if not n:
        return None
    return 1e3 * sum(rd.stats.host_s for rd in run.rounds) / n
