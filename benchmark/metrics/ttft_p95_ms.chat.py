"""95th percentile of first frame minus due over ALL requests due in the
window; a failed or unanswered request counts as the worst. Beside
``ttft_ms.p50`` and not end to end: its runs spread too widely (PERF.md)."""

from benchmark import measure


def read(ctx):
    return measure.percentile(measure.ttft_ms(ctx), 95)
