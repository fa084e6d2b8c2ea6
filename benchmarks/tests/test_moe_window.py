"""The windowed sparse-expert reference against a two-layer case worked out
by hand (explicit loops in numpy float64, a window that bites, YaRN on the
full layer), the family's rehearsal entries laid over the rehearsal's
manifest, and the four new readers on made-up records."""
import json
import math
import os
import types

import numpy as np
import pytest

from benchmarks import contract, costs_moe
from benchmarks.reference import moe_window_decoder as ref
from benchmarks.run import load_reader

HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(os.path.dirname(HERE), "rehearsal")

H, NH, NKV, D, E, K, I, V, W = 8, 4, 2, 4, 4, 2, 6, 11, 3
YARN = {"rope_type": "yarn", "rope_theta": 100.0, "factor": 4.0, "original_max_position_embeddings": 64,
        "beta_fast": 4, "beta_slow": 1, "truncate": False}
CFG = {"hidden_size": H, "num_hidden_layers": 2, "num_attention_heads": NH, "num_key_value_heads": NKV,
       "head_dim": D, "moe_intermediate_size": I, "num_experts": E, "num_experts_per_tok": K,
       "norm_topk_prob": True, "vocab_size": V, "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
       "qk_norm": True, "sliding_window": W, "layer_types": ["sliding_attention", "full_attention"],
       "rope_parameters": {"full_attention": YARN,
                           "sliding_attention": {"rope_type": "default", "rope_theta": 100.0}},
       "reference_tolerance": {"worst_nat": 1e-3, "mean_nat": 3e-4}}  # float32 against float64


def make_params(rng):
    n = lambda *s: rng.normal(0, 0.5, s).astype(np.float32)  # noqa: E731
    layer = lambda: {"attn_norm": 1 + 0.1 * n(H), "wq": n(H, NH * D), "wk": n(H, NKV * D),  # noqa: E731
                     "wv": n(H, NKV * D), "wo": n(NH * D, H), "q_norm": 1 + 0.1 * n(D),
                     "k_norm": 1 + 0.1 * n(D), "mlp_norm": 1 + 0.1 * n(H), "w_router": 2 * n(H, E),
                     "w_gate": n(E, H, I), "w_up": n(E, H, I), "w_down": n(E, I, H)}
    return {"embed": n(V, H), "layers": [layer(), layer()], "final_norm": 1 + 0.1 * n(H), "lm_head": n(H, V)}


def yarn_by_hand():
    """inv_freq of each rotated pair and the factor on cos and sin, written
    out from the YaRN paper's recipe for head_dim 4 (two pairs)."""
    base, factor, orig = 100.0, 4.0, 64.0
    dim = lambda rot: D * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))  # noqa: E731
    low, high = max(dim(4.0), 0), min(dim(1.0), D - 1)   # truncate false: not rounded
    inv = []
    for i in range(D // 2):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        plain = 1.0 / base ** (2 * i / D)
        inv.append(plain / factor * ramp + plain * (1 - ramp))
    return inv, 0.1 * math.log(factor) + 1.0


def by_hand(params, ids, window=W, yarn=True, renorm=True):
    P = {k: (np.asarray(v, np.float64) if k != "layers" else
             [{a: np.asarray(b, np.float64) for a, b in lp.items()} for lp in v])
         for k, v in params.items()}
    eps = 1e-5
    norm = lambda x, w: x / math.sqrt(float(np.mean(x * x)) + eps) * w  # noqa: E731
    plain = ([1.0 / 100.0 ** (2 * i / D) for i in range(D // 2)], 1.0)

    def rope(vec, pos, table):
        inv, att = table
        out = vec.copy()
        for i in range(D // 2):
            c, s = math.cos(pos * inv[i]) * att, math.sin(pos * inv[i]) * att
            a, b = vec[i], vec[i + D // 2]
            out[i], out[i + D // 2] = a * c - b * s, b * c + a * s
        return out

    xs = [P["embed"][t].copy() for t in ids]
    for lp, kind in zip(P["layers"], CFG["layer_types"]):
        sliding = kind == "sliding_attention"
        table = plain if sliding or not yarn else yarn_by_hand()
        hs = [norm(x, lp["attn_norm"]) for x in xs]
        qs = [[rope(norm((h @ lp["wq"])[j * D:(j + 1) * D], lp["q_norm"]), t, table) for j in range(NH)]
              for t, h in enumerate(hs)]
        ks = [[rope(norm((h @ lp["wk"])[j * D:(j + 1) * D], lp["k_norm"]), t, table) for j in range(NKV)]
              for t, h in enumerate(hs)]
        vs = [[(h @ lp["wv"])[j * D:(j + 1) * D] for j in range(NKV)] for h in hs]
        new = []
        for t in range(len(ids)):
            first = max(0, t - window + 1) if sliding and window else 0
            seen = range(first, t + 1)
            heads = []
            for j in range(NH):
                g = j // (NH // NKV)
                sc = np.array([qs[t][j] @ ks[u][g] / math.sqrt(D) for u in seen])
                w = np.exp(sc - sc.max())
                w /= w.sum()
                heads.append(sum(wu * vs[u][g] for wu, u in zip(w, seen)))
            x = xs[t] + np.concatenate(heads) @ lp["wo"]
            h2 = norm(x, lp["mlp_norm"])
            logits = h2 @ lp["w_router"]
            probs = np.exp(logits - logits.max())
            probs /= probs.sum()
            chosen = np.argsort(-probs)[:K]
            weights = probs[chosen] / (probs[chosen].sum() if renorm else 1.0)
            for wgt, e in zip(weights, chosen):
                gate = h2 @ lp["w_gate"][e]
                x = x + wgt * (((gate / (1 + np.exp(-gate))) * (h2 @ lp["w_up"][e])) @ lp["w_down"][e])
            new.append(x)
        xs = new
    out = []
    for x in xs:
        logits = norm(x, P["final_norm"]) @ P["lm_head"]
        out.append(logits - (logits.max() + math.log(np.exp(logits - logits.max()).sum())))
    return np.array(out)


IDS = [3, 7, 1, 10, 0, 5, 2, 9, 4]


def test_the_reference_agrees_with_the_two_layer_case_worked_by_hand():
    params = make_params(np.random.default_rng(4))
    want = by_hand(params, IDS)
    got = ref.logprobs(CFG, params, IDS, rows=list(range(len(IDS))))
    np.testing.assert_allclose(got, want, atol=3e-5)
    padded = ref.logprobs(CFG, params, IDS, rows=list(range(len(IDS))), pad_to=200)
    np.testing.assert_allclose(padded, want, atol=3e-5)
    # the window bites (9 tokens, window 3), YaRN turns, renormalising matters
    assert np.abs(by_hand(params, IDS, window=None) - want).max() > 1e-2
    assert np.abs(by_hand(params, IDS, yarn=False) - want).max() > 1e-2
    assert np.abs(by_hand(params, IDS, renorm=False) - want).max() > 1e-2


@pytest.mark.parametrize("switch, hand", [
    ("ignore_window", {"window": None}), ("no_yarn", {"yarn": False}), ("no_renorm", {"renorm": False}),
])
def test_each_switch_computes_the_mistake_it_names(switch, hand):
    params = make_params(np.random.default_rng(6))
    got = ref.logprobs(CFG, params, IDS, rows=list(range(len(IDS))), **{switch: True})
    np.testing.assert_allclose(got, by_hand(params, IDS, **hand), atol=3e-5)


def test_the_comparison_fails_each_mistake_and_passes_the_truth():
    params = make_params(np.random.default_rng(5))
    prompt = [3, 7, 1, 10, 0, 5, 2, 2, 9]
    seq, toks, lps = list(prompt), [], []
    for _ in range(4):
        lp = by_hand(params, seq)[-1]
        tok = int(lp.argmax())
        toks.append(tok)
        lps.append(float(lp[tok]))
        seq.append(tok)
    samples = [{"prompt": prompt, "tokens": toks, "logprobs": lps}]
    assert ref.compare(CFG, params, samples, pad_to=16)["ok"]
    for wrong in ({"skip_layer": 1}, {"ignore_window": True}, {"no_yarn": True}, {"no_renorm": True}):
        assert not ref.compare(CFG, params, samples, pad_to=16, **wrong)["ok"], wrong
    assert ref.compare(CFG, params, samples, pad_to=16, kv_bits=8)["worst_logprob_difference_nat"] > 0


def merge(manifest, entries):
    """The rehearsal's manifest with this family's entries laid over it."""
    m = json.loads(json.dumps(manifest))
    cell = entries["workloads"][0]["name"]
    m["configs"] += entries["configs"]
    m["workloads"] += entries["workloads"]
    for group in ("end_to_end", "per_layer"):
        for e in m[group]:
            if e["name"] in entries["append_cell_to"]:
                e["workloads"].append(cell)
    m["per_layer"] += entries["per_layer"]
    return m


def test_the_rehearsal_entries_make_a_manifest_the_contract_accepts():
    with open(os.path.join(REHEARSAL, "moe_window.entries.json")) as f:
        entries = json.load(f)
    m = merge(contract.load_manifest(os.path.join(REHEARSAL, "BENCHMARK.json")), entries)
    cell = contract.cell_of(m, "tiny-moe-window-closed")
    traced = {x["name"] for x in contract.metrics_of(m, cell["name"], True)}
    assert {"moe_experts_touched.tput", "moe_load_max_over_mean.tput", "decode_step_ms.tput"} <= traced
    assert {x["name"] for x in contract.metrics_of(m, cell["name"], False)} == {"output_tokens_per_s", "setup_s"}
    for x in traced:
        load_reader(x)  # every listed metric has its reader file
    root = os.path.dirname(os.path.dirname(HERE))
    for c in entries["configs"]:
        assert os.path.exists(os.path.join(root, c["file"]))
    assert os.path.exists(os.path.join(REHEARSAL, "traffic", f"{cell['traffic']}.json"))


def step(phase, routed=None, touched=None, load_max=None, queue_depth=0):
    return types.SimpleNamespace(phase=phase, moe_tokens_routed=routed, moe_experts_touched=touched,
                                 moe_load_max=load_max, queue_depth=queue_depth)


class Trace:
    def __init__(self, seconds, count):
        self.seconds, self.count = seconds, count

    def op_seconds(self, pattern):
        return self.seconds.get(pattern, 0.0)

    def op_count(self, pattern):
        return self.count.get(pattern, 0)


def make_ctx():
    cfg = {"num_hidden_layers": 4, "num_experts": 8, "num_experts_per_tok": 2, "hidden_size": 256,
           "moe_intermediate_size": 128, "num_key_value_heads": 1, "head_dim": 128, "sliding_window": 32,
           "layer_types": ["sliding_attention"] * 3 + ["full_attention"]}
    ctx = types.SimpleNamespace(cfg=cfg, trace=None, trace_host=(10.0, 15.0), requests_all=[],
                                engine={"decode_steps": 4, "tp": 1, "prefill_buckets": [32, 64]},
                                peaks={"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12})
    # two horizons of 3 rows (4 steps x 4 layers x 3 rows x top 2 = 96 routed) and things to leave out
    ctx.steps = ctx.steps_all = [
        (11.0, step("decode", 96, 70, 3)), (12.0, step("decode", 96, 58, 2)),
        (12.5, step("decode", 24, 20, 1, queue_depth=1)),        # a single-step decode while one waits
        (13.0, step("mixed", 2 * 4 * 40, 32, 14)), (14.0, step("prefill")),
        (20.0, step("decode", 96, 64, 3)),                        # outside the traced sub-window
    ]
    return ctx


def test_counter_readers_on_made_up_steps():
    ctx = make_ctx()
    touched = load_reader("moe_experts_touched.tput")(ctx)
    assert touched == pytest.approx((70 + 58 + 64) / 3 / 16)      # per layer per step, three horizons
    spread = load_reader("moe_load_max_over_mean.tput")(ctx)
    assert spread == pytest.approx(np.mean([3, 2, 3]) * 8 * 16 / 96)
    ctx.steps = [(11.0, step("decode"))]                          # a program without the counters
    assert load_reader("moe_experts_touched.tput")(ctx) is None
    assert load_reader("moe_load_max_over_mean.tput")(ctx) is None


def test_roofline_readers_on_a_made_up_trace():
    ctx = make_ctx()
    gmm, win = load_reader("moe_grouped_matmul_roofline"), load_reader("windowed_attention_roofline")
    assert gmm(ctx) is None and win(ctx) is None                  # no trace
    ctx.trace = Trace({}, {})
    assert gmm(ctx) is None and win(ctx) is None                  # a trace without the kernels
    ctx.trace = Trace({"moe_grouped_matmul": 0.02, "ragged_paged_attention_windowed": 0.001},
                      {"ragged_paged_attention_windowed": 30})
    per_expert = 3 * 256 * 128 * 2
    assert costs_moe.expert_bytes(ctx.cfg) == per_expert
    # the four counted steps inside 10-15 s: bytes bound the decode steps, FLOPs no step at these peaks
    least = sum(max(t * per_expert / 1e9, r * 2 * 3 * 256 * 128 / 1e12)
                for r, t in ((96, 70), (96, 58), (24, 20), (320, 32)))
    assert gmm(ctx) == pytest.approx(100 * least / 0.02)
    # one request decoding through the whole sub-window at 100+ tokens (clipped to the window of 32),
    # one whose 70 uncached tokens after 96 cached were prefilled inside it (chunks of 64 and 6)
    ctx.requests_all = [
        {"t_first": 9.0, "t_last_or_end": 16.0, "t_chunks": [9.0], "n_chunks": [1], "prompt_tokens": 100,
         "cached_tokens": 96, "t_ref": 8.0},
        {"t_first": 12.0, "t_last_or_end": 12.0, "t_chunks": [12.0], "n_chunks": [1], "prompt_tokens": 166,
         "cached_tokens": 96, "t_ref": 11.0},
    ]
    tokens = 30 * 32 + 3 * ((64 + 31) + (6 + 31))
    assert win(ctx) == pytest.approx(100 * tokens * 2 * 128 * 2 / 1e9 / 0.001)
    assert costs_moe.windowed_row_tokens(ctx.cfg, 10) == 10       # a context inside the window
