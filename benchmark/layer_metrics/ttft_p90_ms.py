"""90th percentile of first token minus due over the sampled requests: with
some tens of requests it is nearly the maximum, so it is recorded, not judged."""

import statistics

from benchmark.end_to_end.ttft_p50_ms import ttfts_ms


def read(ctx):
    v = ttfts_ms(ctx)
    return statistics.quantiles(v, n=10)[-1] if len(v) >= 2 else None
