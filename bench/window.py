"""Window arithmetic of the end-to-end metrics, on plain logs of host
times.  The throughput rule is that of the program's
`service.observability` (copied here so that the yardstick stays put):
work over the span from the first start to the last completion."""
from __future__ import annotations

import numpy as np


def sweep(calls: list[tuple[float, float]], rounds_per_call: list[int]) -> dict:
    """Calls back to back: simulated rounds of every call over the span
    from the first call's start to the last call's end."""
    if not calls or len(calls) != len(rounds_per_call):
        raise ValueError("a sweep window needs one round count per call")
    window = float(max(b for _, b in calls) - min(a for a, _ in calls))
    return {"sim_rounds_per_s": sum(rounds_per_call) / window,
            "calls": len(calls), "window_s": window}
