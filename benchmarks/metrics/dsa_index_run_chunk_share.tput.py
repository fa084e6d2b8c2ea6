"""Of the whole chunks of pages the window's steps read their indexers' keys by (StepStats.dsa_index_chunks_whole: every table of a step to its last page, summed over selecting layers), the share whose pages lie one after the other in the pool and are read with one strided descriptor (StepStats.dsa_index_chunks_run): what the launch paged_index_keys' run path leans on. None where the counters say no whole chunk was read. 0.0 from a program whose StepStats has no such fields (the parent of PR 48 reads no chunk as a run: its keys come by a gather behind a re-tiling of the whole array): run.py's own check refuses a line that lacks a listed metric, and the driver runs the parent under this PR's benchmark files."""


def read(ctx):
    if not any(hasattr(s, "dsa_index_chunks_whole") for _, s in ctx.steps):
        return 0.0
    whole = sum(s.dsa_index_chunks_whole or 0 for _, s in ctx.steps)
    run = sum(s.dsa_index_chunks_run or 0 for _, s in ctx.steps)
    return 100.0 * run / whole if whole else None
