"""dispatches_per_op.serve: device tier, ``NBTreeIndex.dispatch_count``
delta over the traced part per op served."""
from bench.readers import dispatches_per_op


def read(run):
    return dispatches_per_op(run)
