"""What the dense-latent readers share: the steps that carry the program's
counters of a latent cache without an indexer (``StepStats.mla_keys_attended``,
``.mla_decode_rows``, PERF.md section 3). A program without the counters
gives none, and the readers return ``None``."""

from typing import List, Tuple


def counted(steps) -> List[Tuple[float, object]]:
    return [(t, s) for t, s in steps if getattr(s, "mla_decode_rows", None)]


def decode_horizons(ctx) -> List[Tuple[float, object]]:
    """The window's decode horizons with counters: ``decode_steps`` steps
    each (the loop decodes step by step only while a request waits)."""
    return [(t, s) for t, s in counted(ctx.steps) if s.phase == "decode" and s.queue_depth == 0]
