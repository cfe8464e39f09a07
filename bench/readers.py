"""Arithmetic shared by the per-layer readers in `metrics/`.  A reader
that finds nothing to read returns None and the metric is left out of the
result line; a share of a peak is never reported as 0 for want of data."""
from __future__ import annotations

import statistics


def mean_ms(run, span: str):
    walls = run.in_window(span)
    return 1e3 * statistics.fmean(walls) if walls else None


def per_unit_ms(run, span: str, counter: str):
    walls, units = run.in_window(span), run.counters.get(counter, 0)
    return 1e3 * sum(walls) / units if walls and units else None


def compile_ms(run):
    return 1e3 * run.compile_s_in_window() if run.window else None


def device_idle_pct(run):
    tr = run.trace_result
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def mfu_pct(run):
    """Model FLOPs (training of dispatched clients + evaluation) over the
    window's length times the chips' bf16 peak."""
    flops = run.counters.get("model_flops")
    peak = run.counters.get("peak_flops")
    if not flops or not peak or not run.window:
        return None
    w0, w1 = run.window
    return 100.0 * flops / ((w1 - w0) * run.cell["chips"] * peak)
