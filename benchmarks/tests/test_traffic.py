import glob
import os

import numpy as np
import pytest

from benchmarks import traffic

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIXES = sorted(glob.glob(os.path.join(HERE, "traffic", "*.json")) +
               glob.glob(os.path.join(HERE, "rehearsal", "traffic", "*.json")))


def requests_of(spec, seed, n=200):
    tr = traffic.Traffic(spec, seed, 40.0, 32768)
    if tr.loop == "open":
        reqs = tr.open_schedule()
    else:
        reqs = [tr.next_request(i % tr.clients) for i in range(n)]
    return tr, reqs


@pytest.mark.parametrize("path", MIXES, ids=[os.path.basename(p) for p in MIXES])
def test_the_same_seed_gives_the_same_requests(path):
    spec = traffic.load(path)
    a_tr, a = requests_of(spec, 3_000_000_019)
    b_tr, b = requests_of(spec, 3_000_000_019)
    assert a == b
    assert [a_tr.tokens(r) for r in a[:5]] == [b_tr.tokens(r) for r in b[:5]]


@pytest.mark.parametrize("path", MIXES, ids=[os.path.basename(p) for p in MIXES])
def test_another_seed_gives_the_same_sizes_in_another_order(path):
    spec = traffic.load(path)
    a_tr, a = requests_of(spec, 1, n=traffic.Traffic(spec, 1, 40.0, 32768)._n)
    b_tr, b = requests_of(spec, 2, n=len(a))
    assert sorted(r.fresh_tokens for r in a) == sorted(r.fresh_tokens for r in b)
    assert sorted(r.output_tokens for r in a) == sorted(r.output_tokens for r in b)
    assert a_tr.tokens(a[0]) != b_tr.tokens(b[0])
    if spec.get("order") == "fixed":     # the order is the file's; the seed draws only the tokens
        assert [(r.due_s, r.fresh_tokens, r.output_tokens) for r in a] == \
               [(r.due_s, r.fresh_tokens, r.output_tokens) for r in b]
    elif a_tr.loop == "open":
        gaps = lambda rs: sorted(np.round(np.diff([r.due_s for r in rs] + [40.0]), 9))  # noqa: E731
        assert gaps(a) == gaps(b)
        assert [r.due_s for r in a] != [r.due_s for r in b]


def test_an_open_loop_has_round_rate_times_seconds_requests_all_due_in_the_window():
    spec = {"loop": "open", "rate_per_s": 6.5, "arrival": {"process": "gamma", "cv": 2.5},
            "prompt": {"dist": "fixed", "value": 10}, "output": {"dist": "fixed", "value": 5}}
    reqs = traffic.Traffic(spec, 7, 40.0, 1000).open_schedule()
    assert len(reqs) == 260
    dues = [r.due_s for r in reqs]
    assert dues[0] == 0.0 and dues == sorted(dues) and dues[-1] < 40.0


def test_lengths_stay_inside_their_clips_and_a_gamma_is_burstier_than_a_poisson():
    rng = np.random.default_rng(0)
    d = {"dist": "lognormal", "median": 384, "sigma": 0.8, "min": 64, "max": 2048}
    x = traffic.draw_lengths(d, 5000, rng)
    assert x.min() >= 64 and x.max() <= 2048 and 330 < np.median(x) < 440
    p = traffic.draw_gaps({"process": "poisson"}, 20000, rng)
    g = traffic.draw_gaps({"process": "gamma", "cv": 2.5}, 20000, rng)
    assert abs(p.mean() - 1) < 0.05 and abs(g.mean() - 1) < 0.1
    assert 0.9 < p.std() / p.mean() < 1.1 and 2.2 < g.std() / g.mean() < 2.8


def test_a_shared_prefix_leads_every_prompt_of_its_group():
    spec = {"loop": "closed", "clients": 3, "pool": 6,
            "prompt": {"dist": "uniform", "min": 4, "max": 9}, "output": {"dist": "fixed", "value": 3},
            "shared_prefix": {"groups": 3, "tokens": 32, "assign": "client", "prefill_in_setup": True}}
    tr = traffic.Traffic(spec, 11, 10.0, 500)
    for k in range(3):
        r = tr.next_request(k)
        toks = tr.tokens(r)
        assert toks[:32] == tr.prefix(k).tolist() and len(toks) == 32 + r.fresh_tokens
    assert tr.longest_prompt() == 32 + 9


def test_the_reference_sample_has_one_prompt_longer_than_a_chunk():
    ps = list(traffic.iter_sample_prompts(5, 1000, 4, 200, 600, longer_than=512))
    assert len(ps) == 4 and all(200 <= len(p) <= 600 for p in ps) and max(map(len, ps)) > 512
    assert ps == list(traffic.iter_sample_prompts(5, 1000, 4, 200, 600, longer_than=512))
