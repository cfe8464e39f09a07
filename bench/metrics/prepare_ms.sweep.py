"""Wall of `fl.sim._prepare` (dataset, partition, channel and scenario
traces, permutations) per sweep call."""
WRAPS = [("repro.fl.sim._prepare", "sweep.prepare", False)]


def read(run):
    walls = run.in_window("sweep.prepare")
    calls = run.counters.get("calls", 0)
    return 1e3 * sum(walls) / calls if walls and calls else None
