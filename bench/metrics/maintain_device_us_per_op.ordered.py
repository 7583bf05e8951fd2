"""maintain_device_us_per_op.ordered: maintenance, device time of the
flush, split and row-clear programs (``jit__flush_impl``,
``jit__split_impl``, ``jit__clear_impl``) per insert served in the traced
part (profiler trace), in microseconds."""
from bench.readers import _traced_commits

PROGRAMS = ("jit__flush_impl", "jit__split_impl", "jit__clear_impl")


def read(run):
    c = _traced_commits(run)
    t = run.trace
    if c is None or t is None:
        return None
    inserts = c[:, 3].sum() - c[:, 4].sum()
    dev_s = sum(t.module_seconds(p) for p in PROGRAMS)
    if inserts <= 0 or dev_s <= 0:
        return None
    return float(dev_s / inserts * 1e6)
