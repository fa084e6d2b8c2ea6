"""The latent-attention, learned-selection reference against a two-layer case
worked out by hand (explicit loops in numpy float64: a layer that selects
and a dense feed-forward, then a layer that shares the selection and holds
2 of 4 experts), the adapter's layouts, the costs file and the three new
readers on made-up records."""
import math
import types

import numpy as np
import pytest

from benchmarks import contract, costs_dsa
from benchmarks.adapters import mla_dsa as adapter
from benchmarks.reference import mla_dsa_decoder as ref
from benchmarks.run import load_reader

H, NH, QR, R, NOPE, ROPE, VD, NI, DI, TOPK, E, HELD, FIRST, K, I, FF, V = (
    8, 2, 6, 4, 4, 2, 3, 2, 4, 3, 4, 2, 1, 2, 5, 6, 11)
CFG = {"hidden_size": H, "num_hidden_layers": 2, "layer_offset": 1, "num_attention_heads": NH,
       "q_lora_rank": QR, "kv_lora_rank": R, "qk_nope_head_dim": NOPE, "qk_rope_head_dim": ROPE,
       "v_head_dim": VD, "index_n_heads": NI, "index_head_dim": DI, "index_topk": TOPK,
       "indexer_types": ["full", "full", "shared"], "mlp_layer_types": ["dense", "dense", "sparse"],
       "intermediate_size": FF, "moe_intermediate_size": I, "router_outputs": E, "n_routed_experts": HELD,
       "experts_held_first": FIRST, "num_experts_per_tok": K, "norm_topk_prob": True,
       "routed_scaling_factor": 2.5, "vocab_size": V, "rms_norm_eps": 1e-5,
       "rope_parameters": {"rope_theta": 100.0, "rope_type": "default"},
       "reference_tolerance": {"worst_nat": 1e-3, "mean_nat": 3e-4}}  # float32 against float64


def make_params(rng):
    n = lambda *s: rng.normal(0, 0.5, s).astype(np.float32)  # noqa: E731
    attn = lambda: {"attn_norm": 1 + 0.1 * n(H), "w_dq": n(H, QR), "q_norm": 1 + 0.1 * n(QR),  # noqa: E731
                    "w_uq": n(QR, NH, NOPE + ROPE), "w_dkv": n(H, R + ROPE), "kv_norm": 1 + 0.1 * n(R),
                    "w_uk": n(NH, R, NOPE), "w_uv": n(NH, R, VD), "wo": n(NH, VD, H),
                    "mlp_norm": 1 + 0.1 * n(H)}
    first = dict(attn(), w_iq=n(QR, NI, DI), w_ik=n(H, DI), ik_norm_w=1 + 0.1 * n(DI), ik_norm_b=0.1 * n(DI),
                 w_iw=n(H, NI), w_gate=n(H, FF), w_up=n(H, FF), w_down=n(FF, H))
    second = dict(attn(), w_router=2 * n(H, E), router_bias=0.3 * n(E), w_egate=n(HELD, H, I),
                  w_eup=n(HELD, H, I), w_edown=n(HELD, I, H), w_shared_gate=n(H, I),
                  w_shared_up=n(H, I), w_shared_down=n(I, H))
    return {"embed": n(V, H), "layers": [first, second], "final_norm": 1 + 0.1 * n(H), "lm_head": n(H, V)}


def by_hand(params, ids, dense_attention=False):
    f64 = lambda t: {k: np.asarray(v, np.float64) for k, v in t.items()}  # noqa: E731
    rms = lambda x, w: x / math.sqrt(float(np.mean(x * x)) + 1e-5) * w  # noqa: E731
    silu = lambda a: a / (1 + np.exp(-a))  # noqa: E731

    def rot(x, pos):  # interleaved pairs (2i, 2i + 1)
        out = x.copy()
        for i in range(len(x) // 2):
            ang = pos / 100.0 ** (2 * i / len(x))
            a, b = x[2 * i], x[2 * i + 1]
            out[2 * i], out[2 * i + 1] = a * math.cos(ang) - b * math.sin(ang), b * math.cos(ang) + a * math.sin(ang)
        return out

    T = len(ids)
    xs = [np.asarray(params["embed"], np.float64)[t] for t in ids]
    picked = None
    gaps = []
    for li, lp in enumerate(map(f64, params["layers"])):
        hs = [rms(x, lp["attn_norm"]) for x in xs]
        cqs = [rms(h @ lp["w_dq"], lp["q_norm"]) for h in hs]
        if li == 0:
            picked = []
            keys = []
            for s, h in enumerate(hs):
                k = h @ lp["w_ik"]
                k = (k - k.mean()) / math.sqrt(float(np.mean((k - k.mean()) ** 2)) + 1e-6) * lp["ik_norm_w"] + lp["ik_norm_b"]
                keys.append(np.concatenate([rot(k[:ROPE], s), k[ROPE:]]))
            for t, (h, cq) in enumerate(zip(hs, cqs)):
                w = (h @ lp["w_iw"]) / math.sqrt(NI) / math.sqrt(DI)
                score = np.zeros(t + 1)
                for j in range(NI):
                    q = np.einsum("r,rd->d", cq, lp["w_iq"][:, j])
                    q = np.concatenate([rot(q[:ROPE], t), q[ROPE:]])
                    for s in range(t + 1):
                        score[s] += w[j] * max(float(q @ keys[s]), 0.0)
                ranked = np.sort(score)[::-1]
                if t >= TOPK:  # exact top-k lets ties fall either way: a case with one decides nothing
                    gaps.append(float(ranked[TOPK - 1] - ranked[TOPK]))
                picked.append(set(np.argsort(-score)[:TOPK].tolist()))
        cs, pes = [], []
        for s, h in enumerate(hs):
            ckv = h @ lp["w_dkv"]
            cs.append(rms(ckv[:R], lp["kv_norm"]))
            pes.append(rot(ckv[R:], s))
        new = []
        for t in range(T):
            seen = list(range(t + 1)) if dense_attention else sorted(picked[t])
            y = np.zeros(H)
            for hd in range(NH):
                q = cqs[t] @ lp["w_uq"][:, hd]
                q = np.concatenate([q[:NOPE], rot(q[NOPE:], t)])
                sc = np.array([q @ np.concatenate([cs[s] @ lp["w_uk"][hd], pes[s]]) for s in seen]) / math.sqrt(NOPE + ROPE)
                p = np.exp(sc - sc.max())
                p /= p.sum()
                y += sum(pi * (cs[s] @ lp["w_uv"][hd]) for pi, s in zip(p, seen)) @ lp["wo"][hd]
            new.append(xs[t] + y)
        xs = []
        for x in new:
            h = rms(x, lp["mlp_norm"])
            if li == 0:
                xs.append(x + (silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"])
                continue
            s = 1 / (1 + np.exp(-(h @ lp["w_router"])))
            chosen = np.argsort(-(s + lp["router_bias"]))[:K]
            y = (silu(h @ lp["w_shared_gate"]) * (h @ lp["w_shared_up"])) @ lp["w_shared_down"]
            for e in chosen:
                if FIRST <= e < FIRST + HELD:   # the others live on other chips
                    g = 2.5 * s[e] / s[chosen].sum()
                    y = y + g * ((silu(h @ lp["w_egate"][e - FIRST]) * (h @ lp["w_eup"][e - FIRST])) @ lp["w_edown"][e - FIRST])
            xs.append(x + y)
    out = []
    for x in xs:
        logits = rms(x, np.asarray(params["final_norm"], np.float64)) @ np.asarray(params["lm_head"], np.float64)
        out.append(logits - logits.max() - math.log(np.exp(logits - logits.max()).sum()))
    return np.array(out), min(gaps)


@pytest.mark.parametrize("dense", [False, True])
def test_reference_matches_the_case_worked_out_by_hand(dense):
    for seed in range(4, 40):   # the first case whose every cut is clear of a tie (ReLU gives many zeros)
        rng = np.random.default_rng(seed)
        params = make_params(rng)
        ids = rng.integers(0, V, 9).tolist()
        want, gap = by_hand(params, ids, dense_attention=dense)
        if gap > 1e-3:
            break
    got = ref.logprobs(CFG, params, ids, list(range(9)), dense_attention=dense)
    np.testing.assert_allclose(got, want, atol=2e-4)
    if not dense:   # index_topk 3 of up to 9 keys: the selection bites
        assert np.abs(want - by_hand(params, ids, dense_attention=True)[0]).max() > 1e-2


def test_compare_reads_the_tolerance_and_the_switches_break_it():
    rng = np.random.default_rng(5)
    params = make_params(rng)
    ids = rng.integers(0, V, 8).tolist()
    lp = ref.logprobs(CFG, params, ids, list(range(4, 8)))
    sample = {"prompt": ids[:5], "tokens": [ids[5], ids[6], ids[7]],
              "logprobs": [float(lp[j, t]) for j, t in enumerate(ids[5:8])]}
    res = ref.compare(CFG, params, [sample], 16)
    assert res["tokens_compared"] == 3 and res["worst_logprob_difference_nat"] < 1e-5
    for wrong in ({"dense_attention": True}, {"no_shared_expert": True}, {"no_routed_scale": True},
                  {"skip_layer": 1}, {"topk_scale": 0.34}):
        assert ref.compare(CFG, params, [sample], 16, **wrong)["worst_logprob_difference_nat"] > 1e-3, wrong


def test_the_adapter_re_interleaves_what_a_rotation_reads():
    w = np.arange(2 * 10).reshape(2, 10)
    got = adapter._interleave(w, 4, 6)       # columns 4..9 from halves to pairs
    assert got[0].tolist() == [0, 1, 2, 3, 4, 7, 5, 8, 6, 9]
    assert adapter._interleave(w, 0, 2)[1].tolist() == w[1].tolist()


def test_costs_and_layers_of_the_published_configuration():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmarks", "configs", "glm-5.2-ep16-d6.json")) as f:
        cfg = json.load(f)
    assert costs_dsa.latent_row_bytes(cfg) == 1152
    assert costs_dsa.selected_keys(cfg, 100) == 100 and costs_dsa.selected_keys(cfg, 24576) == 2048
    assert [k for k, _ in costs_dsa.layers_run(cfg)] == ["full", "shared", "shared", "shared", "full", "shared"]
    assert costs_dsa.sparse_layers(cfg) == 5
    assert ref.layer_kinds(cfg) == costs_dsa.layers_run(cfg)
    # 8 decode rows x 6 layers x 2048 rows of 1152 bytes at 819 GB/s
    least = costs_dsa.sparse_attention_least_s(cfg, 8 * 6 * 2048, {"hbm_bytes_per_s": 819e9})
    assert least == pytest.approx(8 * 6 * 2048 * 1152 / 819e9)
    manifest = contract.load_manifest(os.path.join(root, "BENCHMARK.json"))
    names = {m["name"] for m in contract.metrics_of(manifest, "glm52-longdoc-sessions", True)}
    assert {"sparse_latent_attention_roofline", "dsa_selected_share.tput",
            "moe_held_experts_touched.tput"} <= names
    assert set(cfg["reduced"]) == set(cfg["reduced_from"])


def _step(phase, t, **kw):
    base = dict(phase=phase, queue_depth=0, dsa_keys_causal=None, dsa_keys_selected=None,
                dsa_keys_scored=None, moe_held_experts_touched=None)
    base.update(kw)
    return t, types.SimpleNamespace(**base)


def test_the_three_readers_on_made_up_records():
    cfg = {"index_topk": 2048, "kv_lora_rank": 512, "qk_rope_head_dim": 64, "num_hidden_layers": 6,
           "layer_offset": 0, "indexer_types": ["full"] * 6,
           "mlp_layer_types": ["dense"] + ["sparse"] * 5}
    steps = [
        _step("prefill", 0.5),
        _step("decode", 1.0, dsa_keys_causal=8 * 6 * 25000 * 8, dsa_keys_selected=8 * 6 * 2048 * 8,
              moe_held_experts_touched=5 * 8 * 3),
        _step("mixed", 2.0, dsa_keys_causal=7 * 6 * 25000, dsa_keys_selected=7 * 6 * 2048,
              moe_held_experts_touched=40),
        _step("decode", 3.0, queue_depth=1, dsa_keys_causal=6 * 25000, dsa_keys_selected=6 * 2048,
              moe_held_experts_touched=4),
    ]
    ctx = types.SimpleNamespace(cfg=cfg, steps=steps, steps_all=steps, engine={"decode_steps": 8},
                                peaks={"hbm_bytes_per_s": 819e9}, trace=None, trace_host=(0.0, 2.5),
                                requests_all=[])
    assert load_reader("dsa_selected_share.tput")(ctx) == pytest.approx(100 * 2048 / 25000)
    assert load_reader("moe_held_experts_touched.tput")(ctx) == pytest.approx(3.0)
    assert load_reader("sparse_latent_attention_roofline")(ctx) is None     # no trace
    ctx.trace = types.SimpleNamespace(op_seconds=lambda pattern: 0.01 if pattern == "sparse_latent_attention" else 0.0)
    # a 300-token question behind 24576 cached tokens, prefilled inside the sub-window
    ctx.requests_all = [{"cached_tokens": 24576, "prompt_tokens": 24876, "t_first": 1.5, "t_ref": 0.2}]
    keys = (8 * 8 + 7) * 6 * 2048 + 6 * 300 * 2048
    assert load_reader("sparse_latent_attention_roofline")(ctx) == pytest.approx(
        100 * keys * 1152 / 819e9 / 0.01)
    # a parent without the counters: nothing to read, nothing raised
    bare = [(t, types.SimpleNamespace(phase=s.phase, queue_depth=0)) for t, s in steps]
    ctx.steps = ctx.steps_all = bare
    for name in ("dsa_selected_share.tput", "moe_held_experts_touched.tput", "sparse_latent_attention_roofline"):
        assert load_reader(name)(ctx) is None
