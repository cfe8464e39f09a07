"""Share of a steady traced stretch of the window in which no operation
ran on the device (profiler trace, `bench/trace_reduce.py`)."""
from bench import readers

WRAPS = []


def read(run):
    return readers.device_idle_pct(run)
