"""Arithmetic the metric readers share. A reader is a file named after its
metric with one function ``read(ctx)`` that returns a number, or ``None``
when there is nothing to read (the harness then leaves the metric out, and
the contract check fails the run: a listed metric that cannot be read in a
cell is a fault in the cell's list or in the reduction, never a 0).

``ctx`` (``run.py`` ``Context``): ``requests`` (one record per attempted
request: ``t_ref`` the due or send time, ``ttft_s``, ``tpot_s``,
``prompt_tokens``, ``cached_tokens``, ``t_first``, ``t_last``, ``n_out``,
``ok``), ``steps`` (``(t, StepStats)`` of the window), ``trace`` (a
``trace_reduce.Reduced`` or ``None``), ``trace_host`` (the traced
sub-window on the host clock), ``cfg`` (the configuration file), ``engine``
(what the options resolved to), ``peaks``, ``seconds``, ``setup_s``,
``tokens_in_window``, ``drain_end``.
"""

from __future__ import annotations

import statistics
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not values:
        return None
    v = sorted(values)
    k = (len(v) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def ttft_ms(ctx, q: float) -> Optional[float]:
    """Over ALL attempted requests: one that never gave a token ranks last,
    with the time to the end of the drain as its value."""
    vals = [
        (r["ttft_s"] if r["ttft_s"] is not None else ctx.drain_end - r["t_ref"]) * 1e3
        for r in ctx.requests
    ]
    return percentile(vals, q)


def tpot_ms(ctx, q: float) -> Optional[float]:
    """Per request (t_last - t_first) / (n_out - 1): the decode horizon emits
    tokens in groups, so single gaps are 0 or a whole horizon."""
    vals = []
    for r in ctx.requests:
        if r["ok"] and r["n_out"] >= 2:
            vals.append(r["tpot_s"] * 1e3)
        elif not r["ok"]:
            vals.append((ctx.drain_end - r["t_ref"]) * 1e3)
    return percentile(vals, q)


def step_mean(ctx, field, phases: Optional[Sequence[str]] = None) -> Optional[float]:
    vals = [field(s) for _, s in ctx.steps if phases is None or s.phase in phases]
    return statistics.fmean(vals) if vals else None


def module_median_ms(ctx, pattern: str, divide_by: float = 1.0) -> Optional[float]:
    if ctx.trace is None:
        return None
    durs = ctx.trace.module_durations_s(pattern)
    return statistics.median(durs) * 1e3 / divide_by if durs else None


def idle_share(ctx) -> Optional[float]:
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
