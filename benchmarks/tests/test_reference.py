"""The plain reference against a two-layer case worked out by hand: explicit
loops over tokens, heads and dimensions in numpy float64."""
import math

import numpy as np

from benchmarks.reference import dense_decoder as ref

CFG = {"hidden_size": 8, "num_hidden_layers": 2, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 4, "intermediate_size": 12,
       "vocab_size": 11, "rope_theta": 100.0, "rms_norm_eps": 1e-5,
       "tie_word_embeddings": False, "reference_tolerance": {"worst_nat": 0.08, "mean_nat": 0.02}}


def make_params(rng):
    h, q, kv, f, v = 8, 16, 8, 12, 11
    n = lambda *s: rng.normal(0, 0.5, s).astype(np.float32)  # noqa: E731
    layer = lambda: {"attn_norm": 1 + 0.1 * n(h), "wq": n(h, q), "wk": n(h, kv), "wv": n(h, kv),  # noqa: E731
                     "wo": n(q, h), "mlp_norm": 1 + 0.1 * n(h), "w_gate": n(h, f), "w_up": n(h, f),
                     "w_down": n(f, h)}
    return {"embed": n(v, h), "layers": [layer(), layer()], "final_norm": 1 + 0.1 * n(h), "lm_head": n(h, v)}


def by_hand(params, ids):
    P = {k: (np.asarray(v, np.float64) if k != "layers" else
             [{a: np.asarray(b, np.float64) for a, b in lp.items()} for lp in v])
         for k, v in params.items()}
    nh, nkv, d, eps, theta = 4, 2, 4, 1e-5, 100.0
    norm = lambda x, w: x / math.sqrt(float(np.mean(x * x)) + eps) * w  # noqa: E731

    def rope(vec, pos):
        out = vec.copy()
        for i in range(d // 2):
            ang = pos / (theta ** (i / (d // 2)))
            a, b = vec[i], vec[i + d // 2]
            out[i] = a * math.cos(ang) - b * math.sin(ang)
            out[i + d // 2] = b * math.cos(ang) + a * math.sin(ang)
        return out

    xs = [P["embed"][t].copy() for t in ids]
    for lp in P["layers"]:
        hs = [norm(x, lp["attn_norm"]) for x in xs]
        qs = [[rope((h @ lp["wq"])[j * d:(j + 1) * d], t) for j in range(nh)] for t, h in enumerate(hs)]
        ks = [[rope((h @ lp["wk"])[j * d:(j + 1) * d], t) for j in range(nkv)] for t, h in enumerate(hs)]
        vs = [[(h @ lp["wv"])[j * d:(j + 1) * d] for j in range(nkv)] for h in hs]
        new = []
        for t in range(len(ids)):
            heads = []
            for j in range(nh):
                g = j // (nh // nkv)
                sc = np.array([qs[t][j] @ ks[u][g] / math.sqrt(d) for u in range(t + 1)])
                w = np.exp(sc - sc.max())
                w /= w.sum()
                heads.append(sum(w[u] * vs[u][g] for u in range(t + 1)))
            x = xs[t] + np.concatenate(heads) @ lp["wo"]
            h2 = norm(x, lp["mlp_norm"])
            gate = h2 @ lp["w_gate"]
            x = x + ((gate / (1 + np.exp(-gate))) * (h2 @ lp["w_up"])) @ lp["w_down"]
            new.append(x)
        xs = new
    out = []
    for x in xs:
        logits = norm(x, P["final_norm"]) @ P["lm_head"]
        out.append(logits - (logits.max() + math.log(np.exp(logits - logits.max()).sum())))
    return np.array(out)


def test_the_reference_agrees_with_the_two_layer_case_worked_by_hand():
    rng = np.random.default_rng(4)
    params = make_params(rng)
    ids = [3, 7, 1, 10, 0, 5]
    want = by_hand(params, ids)
    got = ref.logprobs(CFG, params, ids, rows=list(range(len(ids))))
    np.testing.assert_allclose(got, want, atol=2e-5)
    padded = ref.logprobs(CFG, params, ids, rows=list(range(len(ids))), pad_to=16)
    np.testing.assert_allclose(padded, want, atol=2e-5)


def test_the_comparison_fails_a_skipped_layer_and_an_8_bit_cache_and_passes_the_truth():
    rng = np.random.default_rng(5)
    params = make_params(rng)
    prompt = [3, 7, 1, 10, 0, 5, 2, 2, 9]
    seq = list(prompt)
    toks, lps = [], []
    for _ in range(4):
        lp = by_hand(params, seq)[-1]
        tok = int(lp.argmax())
        toks.append(tok)
        lps.append(float(lp[tok]))
        seq.append(tok)
    samples = [{"prompt": prompt, "tokens": toks, "logprobs": lps}]
    assert ref.compare(CFG, params, samples, pad_to=16)["ok"]
    assert not ref.compare(CFG, params, samples, pad_to=16, skip_layer=1)["ok"]
    assert not ref.compare(CFG, params, [{**samples[0], "logprobs": [x - 0.2 for x in lps]}], pad_to=16)["ok"]
    wrong = ref.compare(CFG, params, samples, pad_to=16, kv_bits=8)
    assert wrong["worst_logprob_difference_nat"] > 0
