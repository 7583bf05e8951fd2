"""apply_us_per_op.ingest: engine adapter, host time in a synced
``StorageEngine.apply`` per op (benchmark spans)."""
from bench.readers import apply_us_per_op


def read(run):
    return apply_us_per_op(run)
