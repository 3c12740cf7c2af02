"""Prompt tokens computed per second of packed-prefill launches, over
every round: ``PagedStats.prefill_tokens`` over ``prefill_s`` (each launch
ends in ``block_until_ready`` of its logits)."""


def read(run):
    secs = sum(r.stats.prefill_s for r in run.rounds)
    if secs <= 0:
        return None
    return sum(r.stats.prefill_tokens for r in run.rounds) / secs
