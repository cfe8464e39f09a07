"""Wall of `fl.sim._dispatch_group` (trace, compile-cache load and the
scanned rounds, through `block_until_ready`) per simulated round."""
from bench import readers

WRAPS = [("repro.fl.sim._dispatch_group", "sweep.engine", True)]


def read(run):
    return readers.per_unit_ms(run, "sweep.engine", "sim_rounds")
