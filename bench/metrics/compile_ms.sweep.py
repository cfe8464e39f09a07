"""Host time inside the window spent tracing, lowering, compiling or
loading programs from the persistent cache (JAX monitoring events)."""
from bench import readers

WRAPS = []


def read(run):
    return readers.compile_ms(run)
