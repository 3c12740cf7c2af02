"""Share of the requests' decode time spent held up by prefill launches,
over every round: the sum over ``prefill:packed`` events of duration times
``decoding`` (requests holding a token and unfinished when the launch
starts), over the sum over completed requests with two or more tokens of
``latency_s - ttft_s`` (the ``request:decode`` span, the time
``tpot_p95_ms`` divides).  None where no such request completed, or the
program tags no launch with ``decoding``."""


def read(run):
    stalled = 0.0
    for rd in run.rounds:
        for name, begin, end, tags in rd.events:
            if name != "prefill:packed":
                continue
            if "decoding" not in tags:
                return None
            stalled += (end - begin) * tags["decoding"]
    decode = sum(r.latency_s - r.ttft_s for rd in run.rounds
                 for r in rd.stats.results
                 if r.status == "completed" and len(r.tokens) >= 2)
    if decode <= 0:
        return None
    return 100.0 * stalled / decode
