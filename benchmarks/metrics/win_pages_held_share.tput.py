"""Pages the live rows hold in the WINDOWED page groups, as a share of what
ONE block table would hold for their contexts (the pages they hold in the
group that lives as long as the request), over the window's steps
(``StepStats.page_groups_held``): what keeping pages by layer kind leaves of
a sliding layer's share of the cache. 100 would be one table."""
from benchmarks.metrics import _cmda


def read(ctx):
    steps = [s.page_groups_held for _, s in _cmda.grouped(ctx.steps)]
    whole = sum(h[0] for h in steps) * max(len(steps[0]) - 1, 1) if steps else 0
    return 100.0 * sum(sum(h[1:]) for h in steps) / whole if whole else None
