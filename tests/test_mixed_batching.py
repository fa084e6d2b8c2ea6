"""Mixed continuous batching e2e: the fused chunk+decode step (engine
mixed_step + ops/pallas_unified) must be byte-identical to the split
prefill/decode dispatches, while decode keeps advancing through a long
prefill.

Engines here OPT IN via mixed_admission=True (tests/conftest.py pins
DTPU_MIXED=0 suite-wide so the other ~40 engine-building files do not each
pay the fused program's XLA compile). The core greedy/sampled/logprobs
equivalence runs in tier-1; the int8, in-engine-Pallas and gated-family
variants (gpt-oss / gemma / LoRA — mixed-eligible since the per-row
kernel attributes landed) are ``slow`` per the existing convention (they
each build two more engines).

The tier-1 pair also proves the ASYNC STEP-PREP pipeline byte-identical:
the mixed engine runs with DTPU_ASYNC_PREP on (default — chunk packing for
step N+1 prebuilt under step N's device compute) while the split reference
engine packs serially, and the streams still match exactly.
"""

import asyncio
import os

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models.llama import LlamaConfig
from dynamo_tpu.runtime import Context

MODEL = LlamaConfig(
    vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
    num_kv_heads=2, head_dim=16, intermediate_size=128, dtype=jnp.float32,
)

P_RESIDENT = [(i * 37 + 11) % 500 for i in range(30)]
P_ARRIVER = [(i * 53 + 7) % 500 for i in range(90)]  # 3 chunks of 32


def make_engine(mixed, model=MODEL, serial_prep=False, **kw):
    cfg = TpuEngineConfig(
        model=model, num_blocks=256, block_size=4, max_batch_size=4,
        max_context=512, prefill_buckets=(16, 32), decode_steps=4,
        decode_pipeline=2, mixed_admission=mixed, **kw,
    )
    if serial_prep:
        prev = os.environ.get("DTPU_ASYNC_PREP")
        os.environ["DTPU_ASYNC_PREP"] = "0"
        try:
            return TpuEngine(cfg)
        finally:
            if prev is None:
                os.environ.pop("DTPU_ASYNC_PREP", None)
            else:
                os.environ["DTPU_ASYNC_PREP"] = prev
    return TpuEngine(cfg)


def preq(rid, tokens, n, sampling=None, logprobs=0):
    return PreprocessedRequest(
        request_id=rid, model="m", token_ids=tokens,
        stop=StopConditions(max_tokens=n, ignore_eos=True),
        sampling=sampling or SamplingOptions(temperature=0.0, logprobs=logprobs),
    )


async def run_one(eng, req, first_token=None):
    toks, lps = [], []
    async for out in eng.generate(req, Context()):
        toks.extend(out.token_ids)
        if out.logprobs:
            lps.extend(out.logprobs)
        if first_token is not None and toks:
            first_token.set()
    return toks, lps


async def overlap_scenario(eng, r1, r2):
    """r1 decodes; r2's multi-chunk prompt arrives after r1's first token —
    the window where the fused mixed step serves both."""
    first = asyncio.Event()
    t1 = asyncio.create_task(run_one(eng, r1, first))
    await asyncio.wait_for(first.wait(), 90)
    t2 = asyncio.create_task(run_one(eng, r2))
    return await asyncio.gather(t1, t2)


async def _mixed_vs_split(mk_mixed, mk_split):
    e_mixed = mk_mixed()
    phases: dict = {}
    e_mixed.stats_hook = lambda s: phases.setdefault(s.phase, []).append(s)
    try:
        m = await overlap_scenario(
            e_mixed,
            preq("r1", P_RESIDENT, 30),
            preq("r2", P_ARRIVER, 8, logprobs=2),
        )
        samp = SamplingOptions(temperature=1.2, seed=123)
        ms = await overlap_scenario(
            e_mixed,
            preq("s1", P_RESIDENT, 20, sampling=samp),
            preq("s2", P_ARRIVER, 6,
                 sampling=SamplingOptions(temperature=0.9, seed=7)),
        )
    finally:
        e_mixed.stop()
    assert "mixed" in phases, f"mixed step never ran (phases: {set(phases)})"
    # a fused step's token count spans the chunk AND the decode rows it
    # carried; occupancy reflects the resident batch
    assert any(s.tokens > 1 for s in phases["mixed"])
    # async step-prep fired: at least one chunk-carrying step consumed a
    # prebuilt pack (the first chunk of each prompt is always a serial
    # miss — there was no prior step to prep under)
    chunk_steps = phases.get("mixed", []) + phases.get("prefill", [])
    assert any(s.prep_hit for s in chunk_steps), (
        "no step consumed an async-prepped chunk"
    )

    e_split = mk_split()
    assert e_split._prep is None, "split reference engine must pack serially"
    sphases: dict = {}
    e_split.stats_hook = lambda s: sphases.setdefault(s.phase, []).append(s)
    try:
        s = await overlap_scenario(
            e_split,
            preq("r1", P_RESIDENT, 30),
            preq("r2", P_ARRIVER, 8, logprobs=2),
        )
        ss = await overlap_scenario(
            e_split,
            preq("s1", P_RESIDENT, 20,
                 sampling=SamplingOptions(temperature=1.2, seed=123)),
            preq("s2", P_ARRIVER, 6,
                 sampling=SamplingOptions(temperature=0.9, seed=7)),
        )
    finally:
        e_split.stop()
    assert "mixed" not in sphases

    # greedy token streams byte-identical; logprobs within attention-math
    # tolerance (the fused step's packed forward reduces in a different
    # order than the split programs)
    assert m[0][0] == s[0][0]
    assert m[1][0] == s[1][0]
    np.testing.assert_allclose(m[1][1], s[1][1], atol=1e-4, rtol=1e-4)
    # seeded sampling rides the same (seed, step) streams -> identical too
    assert ms[0][0] == ss[0][0]
    assert ms[1][0] == ss[1][0]


@pytest.mark.slow
def test_mixed_equals_split_e2e():
    """Greedy + logprobs + seeded-sampling streams from the mixed engine
    (async step-prep ON) match the serial-prep split engine byte-for-byte
    (tokens) while the mixed phase actually fires and consumes prebuilt
    chunks. Sync wrapper with its own budget: two engine builds."""
    asyncio.run(asyncio.wait_for(
        _mixed_vs_split(
            lambda: make_engine(True),
            lambda: make_engine(False, serial_prep=True),
        ),
        timeout=420,
    ))


@pytest.mark.slow
async def test_mixed_decode_not_starved():
    """While the 3-chunk prompt prefills, the resident stream keeps
    producing: every mixed step advanced the decode rows (tokens include
    the ride-along decode), and no decode stall spans the prefill."""
    eng = make_engine(True)
    phases: dict = {}
    eng.stats_hook = lambda s: phases.setdefault(s.phase, []).append(s)
    try:
        (t1, _), (t2, _) = await overlap_scenario(
            eng, preq("a", P_RESIDENT, 30), preq("b", P_ARRIVER, 8),
        )
        assert len(t1) == 30 and len(t2) == 8
        assert "mixed" in phases
        for s in phases["mixed"]:
            assert s.batch_occupancy >= 2  # fused launch carried both
    finally:
        eng.stop()


@pytest.mark.slow
def test_mixed_equals_split_int8():
    """Mixed continuous batching over the int8 paged cache (quantize-on-
    write + scale-row machinery under the unified path)."""
    asyncio.run(asyncio.wait_for(
        _mixed_vs_split(
            lambda: make_engine(True, kv_dtype="int8"),
            lambda: make_engine(False, kv_dtype="int8", serial_prep=True),
        ),
        timeout=420,
    ))


@pytest.mark.slow
def test_mixed_pallas_kernel_in_engine():
    """The unified Pallas kernel (interpreted on CPU) inside the engine's
    fused step produces the same greedy tokens as the split pure-JAX
    engine — the in-engine analog of the interpret parity suite."""
    asyncio.run(asyncio.wait_for(
        _mixed_vs_split(
            lambda: make_engine(True, use_pallas=True),
            lambda: make_engine(False, use_pallas=False, serial_prep=True),
        ),
        timeout=600,
    ))


# ------------------------------------------- gated families (now eligible)
async def _family_mixed_vs_split(model, **kw):
    """Minimal mixed-vs-split token identity for a family engine pair
    (no logprob leg — family engines are compile-heavy enough)."""
    e_mixed = make_engine(True, model=model, **kw)
    phases: dict = {}
    e_mixed.stats_hook = lambda s: phases.setdefault(s.phase, []).append(s)
    try:
        m = await overlap_scenario(
            e_mixed, preq("r1", P_RESIDENT, 16), preq("r2", P_ARRIVER, 6),
        )
    finally:
        e_mixed.stop()
    assert "mixed" in phases, f"mixed never fired (phases: {set(phases)})"
    e_split = make_engine(False, model=model, serial_prep=True, **kw)
    sphases: dict = {}
    e_split.stats_hook = lambda s: sphases.setdefault(s.phase, []).append(s)
    try:
        s = await overlap_scenario(
            e_split, preq("r1", P_RESIDENT, 16), preq("r2", P_ARRIVER, 6),
        )
    finally:
        e_split.stop()
    assert "mixed" not in sphases
    assert m[0][0] == s[0][0]
    assert m[1][0] == s[1][0]


@pytest.mark.slow
def test_mixed_equals_split_gptoss():
    """gpt-oss (sliding window + per-head sinks, MoE) rides the mixed
    step: window/sink extras thread into the unified launch as per-row
    attributes; outputs byte-identical to the split dispatches."""
    from dynamo_tpu.models.gptoss import GptOssConfig

    asyncio.run(asyncio.wait_for(
        _family_mixed_vs_split(GptOssConfig.tiny_gptoss(vocab_size=512)),
        timeout=600,
    ))


@pytest.mark.slow
def test_mixed_equals_split_gemma():
    """gemma-2 (interleaved sliding layers + attn-logit softcap) rides the
    mixed step; outputs byte-identical to the split dispatches."""
    from dynamo_tpu.models.gemma import GemmaConfig

    asyncio.run(asyncio.wait_for(
        _family_mixed_vs_split(GemmaConfig.tiny_gemma2(vocab_size=512)),
        timeout=600,
    ))


@pytest.mark.slow
def test_mixed_equals_split_gptoss_pallas():
    """gpt-oss with the Pallas kernels FORCED (interpreted on CPU): the
    windowed/sink layers route through the unified kernel — both the
    fused mixed step and the split decode dispatch (which serves windowed
    layers as q_len=1 unified rows) — and the greedy stream still equals
    the pure-JAX split engine's."""
    from dynamo_tpu.models.gptoss import GptOssConfig

    asyncio.run(asyncio.wait_for(
        _family_mixed_vs_split(
            GptOssConfig.tiny_gptoss(vocab_size=512), use_pallas=True,
        ),
        timeout=600,
    ))


@pytest.mark.slow
def test_mixed_equals_split_lora():
    """Batched LoRA rides the mixed step: per-row adapter indices thread
    through the packed buffer, and streams (base + two adapters, one
    arriving mid-decode) are byte-identical mixed vs split."""
    import numpy as _np

    def _adapter(seed):
        rng = _np.random.default_rng(seed)
        L, H = MODEL.num_layers, MODEL.hidden_size
        w = {}
        for t, out in (("wq", MODEL.q_size), ("wk", MODEL.kv_size),
                       ("wv", MODEL.kv_size), ("wo", MODEL.hidden_size)):
            inp = MODEL.q_size if t == "wo" else H
            w[f"{t}.A"] = rng.standard_normal((L, inp, 4)).astype(
                _np.float32)
            w[f"{t}.B"] = rng.standard_normal((L, 4, out)).astype(
                _np.float32)
        return w

    def lreq(rid, tokens, n, lora=None):
        return PreprocessedRequest(
            request_id=rid, model="m", token_ids=tokens,
            stop=StopConditions(max_tokens=n, ignore_eos=True),
            sampling=SamplingOptions(temperature=0.0),
            annotations={"lora": lora} if lora else {},
        )

    async def run(mixed):
        eng = make_engine(
            mixed, lora_max_adapters=2, lora_rank=4,
            serial_prep=not mixed,
        )
        eng.lora.load("a", _adapter(5), alpha=8.0)
        eng.lora.load("b", _adapter(9), alpha=8.0)
        phases: dict = {}
        eng.stats_hook = lambda s: phases.setdefault(s.phase, []).append(s)
        try:
            first = asyncio.Event()
            t1 = asyncio.create_task(
                run_one(eng, lreq("r1", P_RESIDENT, 16, lora="a"), first)
            )
            await asyncio.wait_for(first.wait(), 120)
            t2 = asyncio.create_task(
                run_one(eng, lreq("r2", P_ARRIVER, 6, lora="b"))
            )
            t3 = asyncio.create_task(run_one(eng, lreq("r3", P_RESIDENT, 8)))
            out = await asyncio.gather(t1, t2, t3)
        finally:
            eng.stop()
        return [o[0] for o in out], phases

    async def both():
        m, phases_m = await run(True)
        s, phases_s = await run(False)
        assert "mixed" in phases_m and "mixed" not in phases_s
        assert m == s

    asyncio.run(asyncio.wait_for(both(), timeout=600))


# ---------------------------------------------------------------------------
# a mixed step is a link of the decode chain (ISSUE 42): its sampled tokens
# stay on the device as the next mixed step's input, and the loop launches
# that step before it reads them. Tier-1: one run of every scenario on a
# chained engine and on one that reads every mixed step at once (depth 0 of
# the same code: ``_reads_at_once`` answering True), shared by the cases.
# ---------------------------------------------------------------------------

P_SHORT = [(i * 29 + 5) % 500 for i in range(20)]
P_LONG = [(i * 61 + 3) % 500 for i in range(200)]     # 7 chunks of 32
P_LONGER = [(i * 43 + 17) % 500 for i in range(400)]  # 13 chunks of 32
PENALISED = dict(presence_penalty=0.4, frequency_penalty=0.3,
                 repetition_penalty=1.2)


def sreq(rid, tokens, n, stop_ids=(), min_tokens=0, **sampling):
    sampling.setdefault("temperature", 0.0)
    return PreprocessedRequest(
        request_id=rid, model="m", token_ids=tokens,
        stop=StopConditions(max_tokens=n, ignore_eos=True, min_tokens=min_tokens,
                            stop_token_ids=list(stop_ids)),
        sampling=SamplingOptions(**sampling),
    )


async def collect(eng, req, started=None, cancel_after=None):
    """One request's stream: tokens, logprobs, the finish reason, and what
    came after the finish frame (nothing may)."""
    ctx = Context()
    rec = {"tokens": [], "logprobs": [], "finish": None, "after_finish": 0}
    async for out in eng.generate(req, ctx):
        if rec["finish"] is not None:
            rec["after_finish"] += 1 + len(out.token_ids)
        rec["tokens"].extend(out.token_ids)
        rec["logprobs"].extend(out.logprobs or [])
        rec["finish"] = out.finish_reason or rec["finish"]
        if started is not None and rec["tokens"]:
            started.set()
        if cancel_after is not None and len(rec["tokens"]) >= cancel_after:
            ctx.stop_generating()
    return rec


STOP_AT = 18       # the resident's output index of its stop token
CANCEL_AFTER = 16  # tokens the resident's caller reads before it goes away


async def chain_scenarios(eng):
    """Every scenario on one engine, one after another: a resident request
    decodes, and once its first token is out the others arrive, so that
    their chunks ride its decode steps. Returns the records by request id,
    the StepStats, and every mixed dispatch's link."""
    steps, links, joined = [], [], []
    eng.stats_hook = steps.append
    run_mixed = eng._run_mixed_step

    def recording(st, seqs, prev, at_once):
        link, res = run_mixed(st, seqs, prev, at_once)
        links.append(link)
        if prev is not None:
            # rows fed from the host beside rows fed from the device
            new = [s.req.request_id for i, s in enumerate(seqs)
                   if s is not None and prev.seqs[i] is not s]
            if new and len(new) < sum(s is not None for s in seqs):
                joined.extend(new)
        return link, res

    eng._run_mixed_step = recording
    out = {}

    async def behind(resident, *reqs, **kw):
        first = asyncio.Event()
        task = asyncio.ensure_future(collect(eng, resident, started=first, **kw))
        await asyncio.wait_for(first.wait(), 90)
        recs = await asyncio.gather(*[collect(eng, r) for r in reqs])
        out.update({r.request_id: rec for r, rec in zip(reqs, recs)})
        out[resident.request_id] = await task

    # crowd: chunks of three requests meet a resident decode, greedy with
    # logprobs beside seeded sampling with penalties; a fifth request waits
    # for a slot and joins beside rows that are carried on the device
    await behind(
        sreq("crowd-res", P_RESIDENT, 40, logprobs=2),
        sreq("crowd-long", P_LONG, 12, temperature=0.9, seed=7, **PENALISED),
        sreq("crowd-short", P_SHORT, 10, **PENALISED),
        sreq("crowd-arriver", P_ARRIVER, 9, temperature=1.1, seed=123, top_k=40),
        sreq("crowd-waits", P_SHORT[::-1], 14, logprobs=1),
    )
    # stop: the resident ends on a stop token, which the host cannot
    # foresee, while two long prompts keep every step a mixed step; the
    # request that waited takes its slot
    greedy = out["crowd-res"]["tokens"]
    await behind(
        sreq("stop-res", P_RESIDENT, 40, stop_ids=[greedy[STOP_AT]], min_tokens=STOP_AT),
        sreq("stop-long", P_LONGER, 4),
        sreq("stop-other", P_LONG, 30),
        sreq("stop-short", P_SHORT, 40, **PENALISED),
        sreq("stop-next", P_ARRIVER[:40], 10, **PENALISED),
    )
    out["stop-next-alone"] = await collect(
        eng, sreq("stop-next-alone", P_ARRIVER[:40], 10, **PENALISED))
    # cancel: the caller goes away between a link's launch and its fetch
    await behind(
        sreq("cancel-res", P_RESIDENT[::-1], 60),
        sreq("cancel-long", P_LONGER[::-1], 6, logprobs=1),
        cancel_after=CANCEL_AFTER,
    )
    for _ in range(50):  # the loop reaps and goes idle
        if all(s is None for s in eng._slots):
            break
        await asyncio.sleep(0.02)
    out["free_blocks"] = eng.allocator.free_blocks
    eng.stats_hook = None
    # what the mixed steps sampled for each request, in launch order
    sampled = {}
    for link in links:
        for st, tok, *_ in link.results[0]:
            sampled.setdefault(st.req.request_id, []).append(tok)
    return {"recs": out, "steps": steps, "sampled": sampled, "joined": joined}


@pytest.fixture(scope="module")
def chain_runs():
    def one(at_once):
        eng = make_engine(True)
        if at_once:
            eng._reads_at_once = lambda seqs: True
        try:
            return asyncio.run(asyncio.wait_for(chain_scenarios(eng), 600))
        finally:
            eng.stop()

    return {"chained": one(False), "at_once": one(True)}


CROWD = ["crowd-res", "crowd-long", "crowd-short", "crowd-arriver", "crowd-waits"]


@pytest.mark.parametrize(
    "rid", CROWD + ["stop-long", "stop-other", "stop-short", "cancel-long"])
def test_the_chained_loop_gives_each_request_the_synchronous_loops_tokens(chain_runs, rid):
    """Greedy and seeded sampling, penalties (the counts ride the device from
    link to link), logprobs: token for token what the loop gives when it
    reads every mixed step before it builds the next."""
    a, b = (chain_runs[k]["recs"][rid] for k in ("chained", "at_once"))
    assert a["tokens"] == b["tokens"] and a["finish"] == b["finish"] == "length"
    assert len(a["tokens"]) == len(a["logprobs"]) > 0
    np.testing.assert_allclose(a["logprobs"], b["logprobs"], atol=1e-5, rtol=1e-5)
    assert a["after_finish"] == b["after_finish"] == 0


def test_the_scenarios_ran_chained_and_at_once(chain_runs):
    """The chained engine launched most mixed steps on the carry of the one
    before and waited for each in the loop's ``fetch``; the other none, each
    read under the executor's ``sync``."""
    for name in ("chained", "at_once"):
        mixed = [s for s in chain_runs[name]["steps"] if s.phase == "mixed"]
        assert len(mixed) >= 30
        share = sum(bool(s.mixed_chained) for s in mixed) / len(mixed)
        waits = [set(s.host_spans[0::3]) & {"sync", "fetch"} for s in mixed]
        if name == "chained":
            assert share > 0.75, share
            assert all(w == {"fetch"} for w in waits)
        else:
            assert share == 0.0 and all("sync" in w for w in waits)
    other = [s for s in chain_runs["chained"]["steps"] if s.phase != "mixed"]
    assert other and all(s.mixed_chained is None for s in other)


def test_a_row_joins_beside_carried_rows(chain_runs):
    """A request whose last chunk rode a link gets its first token through
    the host (``_finish_prefill``), and its first decode step is a mixed
    step launched on a link's carry: its token comes from the step's packed
    buffer, its batchmates' from the device."""
    run = chain_runs["chained"]
    assert len(run["joined"]) >= 3 and not chain_runs["at_once"]["joined"]
    for rid in set(run["joined"]):
        assert run["recs"][rid]["tokens"] == chain_runs["at_once"]["recs"][rid]["tokens"]


@pytest.mark.parametrize("name", ["chained", "at_once"])
def test_a_stop_token_ends_the_row_with_a_link_in_flight(chain_runs, name):
    """The stop token is not emitted and nothing follows the finish. The
    chained loop had launched one more step over the row: that step's token
    (what greedy decoding gives next) is thrown away, and the slot's next
    occupant decodes as if alone."""
    recs = chain_runs[name]["recs"]
    greedy = recs["crowd-res"]["tokens"]
    got = recs["stop-res"]
    assert got["tokens"] == greedy[:STOP_AT] and got["finish"] == "stop"
    assert got["after_finish"] == 0
    assert recs["stop-next"]["tokens"] == recs["stop-next-alone"]["tokens"]
    assert recs["stop-next"]["finish"] == "length" and len(recs["stop-next"]["tokens"]) == 10
    # the row's tokens out of mixed steps: a run of its greedy stream up to
    # the stop token, and in the chained loop the one after it
    sampled = chain_runs[name]["sampled"]["stop-res"]
    tail = 1 if name == "chained" else 0
    end = STOP_AT + 1 + tail
    assert len(sampled) > 3 + tail and sampled == greedy[end - len(sampled):end]


@pytest.mark.parametrize("name", ["chained", "at_once"])
def test_a_cancel_between_launch_and_fetch(chain_runs, name):
    """The cancelled row's stream ends at the cancel, whatever was in
    flight for it; its batchmate is untouched (compared above)."""
    got = chain_runs[name]["recs"]["cancel-res"]
    assert got["finish"] == "cancelled" and got["after_finish"] == 0
    assert CANCEL_AFTER <= len(got["tokens"]) <= CANCEL_AFTER + 2
    want = chain_runs["at_once"]["recs"]["cancel-res"]["tokens"]
    n = min(len(want), len(got["tokens"]))
    assert got["tokens"][:n] == want[:n]
    # it was riding mixed steps when its caller left
    assert len(chain_runs[name]["sampled"]["cancel-res"]) >= 3


def test_the_allocator_ends_where_the_synchronous_run_left_it(chain_runs):
    a, b = (chain_runs[k]["recs"]["free_blocks"] for k in ("chained", "at_once"))
    assert a == b == 256 - 1  # every page back but the scratch block


def test_a_guided_row_is_read_at_once_and_still_matches():
    """A guided decode row's FSM state is walked on the host as its tokens
    are accepted, and the next dispatch resyncs from there: a mixed step
    that carries one is read under ``sync``, and the step after it is not
    launched ahead. The plain arriver beside it still gets its own tokens."""
    import jax

    from dynamo_tpu.parallel.mesh import make_mesh

    eos = 257
    vocab = [bytes([i]) for i in range(256)] + [b"<pad>", b"</s>", b"<x>", b"<y>"]
    model = LlamaConfig(
        vocab_size=260, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=16, intermediate_size=128, dtype=jnp.float32,
    )
    eng = TpuEngine(
        TpuEngineConfig(
            model=model, num_blocks=256, block_size=4, max_batch_size=4,
            max_context=512, prefill_buckets=(16, 32), decode_steps=4,
            decode_pipeline=2, mixed_admission=True, guided_max_states=256,
            guided_max_classes=128,
        ),
        guided_vocab=(vocab, eos),
        mesh=make_mesh(tp=1, devices=jax.devices()[:1]),
    )
    pattern = {"kind": "regex", "value": r"(ab|cd){12}e"}

    def greq(rid):
        return PreprocessedRequest(
            request_id=rid, model="m", token_ids=[104, 105, 32],
            stop=StopConditions(max_tokens=40, stop_token_ids=[eos]),
            sampling=SamplingOptions(temperature=0.0, guided=pattern),
        )

    plain = [t % 250 for t in P_LONG]
    steps = []

    async def run():
        alone = await collect(eng, greq("g-alone"))
        plain_alone = await collect(eng, sreq("p-alone", plain, 6))
        await eng.clear_kv_blocks()
        eng.stats_hook = steps.append
        # the plain prompt arrives once the guided row decodes: sent together,
        # who is admitted first is a race on a busy host, and chunks that run
        # before the guided row decodes ride no mixed step
        decoding = asyncio.Event()
        guided = asyncio.ensure_future(collect(eng, greq("g"), started=decoding))
        await decoding.wait()
        arrived = await collect(eng, sreq("p", plain, 6))
        return alone, plain_alone, (await guided, arrived)

    try:
        alone, plain_alone, (g, p) = asyncio.run(asyncio.wait_for(run(), 300))
    finally:
        eng.stop()
    text = bytes(t for t in g["tokens"] if t < 256).decode()
    assert text == bytes(t for t in alone["tokens"] if t < 256).decode()
    assert len(text) == 25 and text.endswith("e") and g["finish"] == "stop"
    assert p["tokens"] == plain_alone["tokens"]
    mixed = [s for s in steps if s.phase == "mixed"]
    assert len(mixed) >= 5
    assert all(s.mixed_chained is False for s in mixed)
    assert all("sync" in s.host_spans[0::3] for s in mixed)


def test_a_state_family_ends_with_the_same_slot_state_either_way():
    """Falcon-H1 keeps a recurrent state a slot: the chained loop feeds a
    row's recurrence the same tokens in the same order, a finish the host
    foresees (``max_tokens``) leaves the slot where its last fed token put
    it, and the three counters count what was kept."""
    from dynamo_tpu.models.falcon_h1 import FalconH1Config

    model = FalconH1Config.tiny(dtype=jnp.float32)

    def one(at_once):
        eng = TpuEngine(TpuEngineConfig(
            model=model, num_blocks=64, block_size=8, max_batch_size=2,
            max_context=160, prefill_buckets=(16,), seed=3, use_pallas=False,
            decode_steps=8, decode_pipeline=1, mixed_admission=True,
        ))
        if at_once:
            eng._reads_at_once = lambda seqs: True
        steps = []
        eng.stats_hook = steps.append

        async def run():
            first = asyncio.Event()
            a = asyncio.ensure_future(collect(eng, sreq("a", P_SHORT, 30), started=first))
            await asyncio.wait_for(first.wait(), 90)
            b = await collect(eng, sreq("b", P_ARRIVER, 5))   # six chunks of 16
            return [await a, b]

        try:
            recs = asyncio.run(asyncio.wait_for(run(), 300))
            state = {k: [np.asarray(x) for x in v] for k, v in eng.state.arrays.items()}
        finally:
            eng.stop()
        return recs, state, steps

    (ra, sa, ta), (rb, sb, tb) = one(False), one(True)
    assert [r["tokens"] for r in ra] == [r["tokens"] for r in rb]
    assert any(s.mixed_chained for s in ta) and not any(s.mixed_chained for s in tb)
    for name in sa:
        for x, y in zip(sa[name], sb[name]):
            np.testing.assert_allclose(x, y, atol=1e-6, rtol=1e-6)
    for field in ("ssm_rows_updated", "ssm_tokens_scanned", "ssm_decode_steps"):
        assert sum(getattr(s, field) or 0 for s in ta) == sum(getattr(s, field) or 0 for s in tb), field
