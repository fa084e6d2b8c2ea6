"""The host's clock over a run of chunk-carrying steps, a token: milliseconds
from the start of the first call of a maximal run of consecutive chunk
launches (``StepStats.launches``: ``mixed_step`` / ``prefill``) to the arrival
of the results of the last of them that the host read
(``StepStats.arrivals``), summed over the window's runs, over the tokens
those steps carried (``StepStats.tokens``: a chunk's and, in a mixed step, its
decode rows').

A PACE ON THE HOST'S CLOCK, not device time: what the device waited for the
host inside a run, and for a horizon launched before the run to end, is in
it, so a shorter host turn between two chunk launches lowers it with no
kernel changed. It is what a cell can say of its chunk steps whose traced
sub-window holds none (``minicpmsala-longdoc-reason``: 32 callers send at
once, the sub-window at 40% of the window lies inside the first wave's
decode; PERF.md section 7 (26)(c)): the readers of a chunk program's DEVICE
time (``prefill_chunk_ms``, ``chunk_step_ms_per_token``,
``device_wait_before_mixed_ms``, a share of the peak) need a trace that
holds one. A lone chunk that is not a prompt's last returns nothing, and a
run's tail of such launches is left out, tokens and time. A program without
the ledger gives ``None``.
"""
from benchmarks.metrics import _launches


def chunk_runs(steps):
    """(tokens, nanoseconds) over the maximal runs of consecutive chunk
    launches of ``steps``, each cut behind its last launch with an arrival."""
    rec = _launches.records(steps)
    if rec is None:
        return 0, 0
    launches, arrivals = rec
    tokens_of = {}
    for _, s in steps:
        if s.phase in ("prefill", "mixed"):
            for seq in s.launches[_launches.SEQ::_launches.VALUES]:
                tokens_of[seq] = s.tokens
    tokens = ns = 0
    run = []                       # (seq, t0) of the run being read
    for launch in launches + [None]:
        if launch is not None and launch[_launches.PROGRAM] in _launches.CHUNK_PROGRAMS:
            run.append((launch[_launches.SEQ], launch[_launches.T0]))
            continue
        landed = [i for i, (seq, _) in enumerate(run) if seq in arrivals]
        if landed:
            kept = run[: landed[-1] + 1]
            tokens += sum(tokens_of.get(seq, 0) for seq, _ in kept)
            ns += arrivals[kept[-1][0]] - kept[0][1]
        run = []
    return tokens, ns


def read(ctx):
    tokens, ns = chunk_runs(ctx.steps)
    if not tokens or ns <= 0:
        return None
    return ns * 1e-6 / tokens
