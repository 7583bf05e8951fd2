"""queue_wait_p99_ms: open-loop client, due -> commit start (host clock)."""
from bench.readers import queue_wait_p99_ms


def read(run):
    return queue_wait_p99_ms(run)
