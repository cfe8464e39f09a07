"""Model FLOP utilization of the sweep window: dispatched clients' training
(forward and backward of local_steps x batch examples) plus evaluation,
over window x chips x bf16 peak.  Padding slots are not counted."""
from bench import readers

WRAPS = []


def read(run):
    return readers.mfu_pct(run)
