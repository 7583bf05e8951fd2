"""p999_ms: 99.9th percentile latency of every op served in the window."""
from bench.readers import percentile_ms


def read(run):
    return percentile_ms(run.latency_s, 99.9)
