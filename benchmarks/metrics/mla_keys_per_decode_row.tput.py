"""Keys a decode row attended over, mean over the window's decode and mixed
steps (``StepStats.mla_keys_attended`` over ``.mla_decode_rows``: real decode
rows, both summed over layers): the row's whole context where nothing
selects. The dense twin of ``dsa_selected_share.tput``."""
from benchmarks.metrics import _mla


def read(ctx):
    steps = _mla.counted(ctx.steps)
    rows = sum(s.mla_decode_rows for _, s in steps)
    return sum(s.mla_keys_attended for _, s in steps) / rows if rows else None
