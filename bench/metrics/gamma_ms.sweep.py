"""Wall of `fl.sim._solve_horizons` (the batched Γ solve) per sweep call."""
from bench import readers

WRAPS = [("repro.fl.sim._solve_horizons", "sweep.gamma", False)]


def read(run):
    return readers.mean_ms(run, "sweep.gamma")
