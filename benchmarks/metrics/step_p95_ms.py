"""95th percentile of the gaps between consecutive step completions, over
every step of the window: the stall a synchronous job feels."""

import statistics


def read(rec):
    gaps = rec["window"]["gaps_ms"]
    if len(gaps) < 2:
        return None
    return statistics.quantiles(gaps, n=20, method="inclusive")[18]
