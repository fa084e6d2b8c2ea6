"""Of the whole chunks of pages the window's steps read for their latent rows (StepStats.mla_chunks_whole, summed over layers), the share whose pages lie one after the other in the pool and are read with one descriptor an array (StepStats.mla_chunks_run): what the dense latent kernel's run path leans on."""


def read(ctx):
    whole = sum(getattr(s, "mla_chunks_whole", None) or 0 for _, s in ctx.steps)
    run = sum(getattr(s, "mla_chunks_run", None) or 0 for _, s in ctx.steps)
    return 100.0 * run / whole if whole else None
