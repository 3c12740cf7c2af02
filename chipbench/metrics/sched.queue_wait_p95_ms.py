"""Time a request waits in the queue before its first admission, p95 over
the completed requests of every round (``RequestResult.queue_s``, the
``request:queued`` span), in ms.  None where the program records no
queue time."""
from chipbench import stats


def read(run):
    waits = [r.queue_s * 1e3 for rd in run.rounds for r in rd.stats.results
             if r.status == "completed" and hasattr(r, "queue_s")]
    return stats.p95(waits) if waits else None
