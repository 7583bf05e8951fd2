"""device_idle.serve: device, 1 - union of program intervals over the
traced window (profiler trace), in %."""
from bench.readers import device_idle


def read(run):
    return device_idle(run)
