"""Highest share of the KV page pool in use in any round
(``PagedStats.peak_pages_in_use`` over ``num_pages``)."""


def read(run):
    shares = [r.stats.peak_pages_in_use / r.stats.num_pages
              for r in run.rounds if r.stats.num_pages]
    return 100.0 * max(shares) if shares else None
