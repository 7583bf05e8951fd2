"""query_device_us_per_op: device tier, device time of
``_query_batch_impl`` per read served (profiler trace), in microseconds."""
from bench.readers import query_device_us_per_op


def read(run):
    return query_device_us_per_op(run)
