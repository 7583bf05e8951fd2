"""p50_ms: median latency of every op served in the window."""
from bench.readers import percentile_ms


def read(run):
    return percentile_ms(run.latency_s, 50)
