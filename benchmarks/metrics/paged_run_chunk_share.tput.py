"""Of the whole chunks of pages the window's decode steps and horizons read through the decode-only kernel (StepStats.paged_chunks_whole: under the decode rows' contexts, x the steps each took x the layers that launch paged_decode_attention), the share whose pages are consecutive block ids and are read with one descriptor an array (StepStats.paged_chunks_run): what PageReader's run path leans on. None where the counters say no whole chunk was read. 0.0 from a program whose StepStats has no such fields (the parent of PR 50 starts every page by itself): run.py's own check refuses a line that lacks a listed metric, and the driver runs the parent under this PR's benchmark files."""


def read(ctx):
    if not any(hasattr(s, "paged_chunks_whole") for _, s in ctx.steps):
        return 0.0
    whole = sum(s.paged_chunks_whole or 0 for _, s in ctx.steps)
    run = sum(s.paged_chunks_run or 0 for _, s in ctx.steps)
    return 100.0 * run / whole if whole else None
