"""How many of a window's mixed steps were launched before the mixed step
before them had been read: what the ``mixed_chained_share`` readers share.

Since ISSUE 42 a mixed step (one request's prefill chunk beside one decode
step of every resident row) is a link of the decode chain: its sampled tokens
stay on the device as the next mixed step's input, and the loop launches that
step before it reads them. ``StepStats.mixed_chained`` is true on a ``mixed``
step launched that way, false where the loop read first (the first mixed step
after a horizon, a guided row, no room to book past the token in flight), and
``None`` on ``prefill`` and ``decode`` steps.

A program that predates the field chains nothing: its mixed steps read false,
and the share is 0.0, a true number, not ``None`` (the driver runs the parent
under this reader). A window with no mixed step at all reads 0.0 too.
"""

from __future__ import annotations


def share(ctx) -> float:
    mixed = [s for _, s in ctx.steps if s.phase == "mixed"]
    if not mixed:
        return 0.0
    chained = sum(1 for s in mixed if getattr(s, "mixed_chained", None))
    return 100.0 * chained / len(mixed)
