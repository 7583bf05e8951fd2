"""merge_roofline: kernels ``merge_sorted`` / ``merge_sorted_batch``, share of
the HBM roofline: the bytes the merge needs (``bench.trace.merge_bytes``)
at peak bandwidth over the kernel's device time, in %."""
from bench.readers import merge_roofline


def read(run):
    return merge_roofline(run)
