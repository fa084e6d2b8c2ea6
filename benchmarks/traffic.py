"""One general traffic generator, driven by a data file.

A traffic mix is ``benchmarks/traffic/<name>.json``; nothing here names a
mix. The arrival and length arithmetic is a copy in spirit of
``dynamo_tpu/profiler/loadgen.py`` (``poisson_trace`` / ``bursty_trace`` /
``prefix_prompt``) and ``dynamo_tpu/sim/traces.py`` (``heavy_tail``), kept
here so that later PRs may change the program and not the yardstick.

Steadiness rule (the builder's contract): every ``--seed`` gets the SAME
multiset of prompt lengths, output lengths and arrival gaps, in another
order, with other token ids. The multisets are drawn once from the file's
``pool_seed``; ``--seed`` only permutes them and draws the tokens. In an
open loop the gaps are scaled so that they sum to the window, so every seed
has exactly ``round(rate * seconds)`` requests due in ``[0, seconds)``.

File keys
---------
``loop``            ``"open"`` or ``"closed"``.
``rate_per_s``      open loop: offered rate, fixed (never searched).
``arrival``         open loop: ``{"process": "poisson"}`` or
                    ``{"process": "gamma", "cv": 2.5}`` (burstier than Poisson
                    at the same mean rate).
``clients``         closed loop: callers that each wait for their reply.
``pool``            closed loop: how many (prompt, output) sizes make one cycle.
``prompt``/``output``  a distribution of token counts:
                    ``{"dist": "fixed", "value": n}``,
                    ``{"dist": "uniform", "min": a, "max": b}``,
                    ``{"dist": "loguniform", "min": a, "max": b}``,
                    ``{"dist": "lognormal", "median": m, "sigma": s,
                    "min": a, "max": b}`` (clipped).
``shared_prefix``   optional ``{"groups": g, "tokens": n, "assign":
                    "client"|"round_robin", "prefill_in_setup": true}``: each
                    request's prompt is its group's n-token prefix followed by
                    the fresh ``prompt`` tokens. With ``prefill_in_setup`` the
                    harness sends each prefix once during set-up, so the window
                    starts with the prefixes in the prefix cache.
``pool_seed``       seed of the multisets (default 0).
``order``           ``"per_seed"`` (default): ``--seed`` permutes the
                    multisets. ``"fixed"``: the order too comes from
                    ``pool_seed`` and ``--seed`` draws only the token ids (and
                    the weights): for tails, which the order of arrivals alone
                    moves by tens of percent (PERF.md section 6).
``drain_s``         how long in-flight requests may finish after the window.
``engine``          what the mix asks of the engine: ``max_batch_size``,
                    ``prefill_buckets``, ``max_context``.
"""

from __future__ import annotations

import dataclasses
import json
import math
from typing import Any, Dict, Iterator, List, Optional

import numpy as np

_TOKEN_BANK = 1 << 20  # random token ids a request's fresh tokens are cut from


@dataclasses.dataclass
class Request:
    index: int
    due_s: Optional[float]       # open loop: seconds after the window opens
    group: Optional[int]         # shared-prefix group, or None
    fresh_tokens: int            # prompt tokens after the shared prefix
    output_tokens: int
    bank_offset: int             # where in the token bank the fresh tokens start


def load(path: str) -> Dict[str, Any]:
    with open(path) as f:
        spec = json.load(f)
    if spec.get("loop") not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be 'open' or 'closed'")
    return spec


def draw_lengths(dist: Dict[str, Any], n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` whole token counts from ``dist``."""
    kind = dist["dist"]
    if kind == "fixed":
        out = np.full(n, dist["value"], float)
    elif kind == "uniform":
        out = rng.uniform(dist["min"], dist["max"] + 1, n)
    elif kind == "loguniform":
        out = np.exp(rng.uniform(math.log(dist["min"]), math.log(dist["max"]), n))
    elif kind == "lognormal":
        out = np.exp(rng.normal(math.log(dist["median"]), dist["sigma"], n))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo = dist.get("min", 1)
    hi = dist.get("max", None)
    out = np.floor(out)
    out = np.clip(out, lo, hi if hi is not None else out.max())
    return out.astype(np.int64)


def draw_gaps(arrival: Dict[str, Any], n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` inter-arrival gaps of mean 1 (the caller scales them)."""
    proc = arrival.get("process", "poisson")
    if proc == "poisson":
        return rng.exponential(1.0, n)
    if proc == "gamma":
        cv = float(arrival["cv"])
        shape = 1.0 / (cv * cv)
        return rng.gamma(shape, 1.0 / shape, n)
    raise ValueError(f"unknown arrival process {proc!r}")


class Traffic:
    """The requests of one run: ``spec`` + ``seed`` + window length."""

    def __init__(self, spec: Dict[str, Any], seed: int, seconds: float, vocab: int):
        self.spec = spec
        self.seconds = float(seconds)
        self.vocab = int(vocab)
        self.loop = spec["loop"]
        self.clients = int(spec.get("clients", 0))
        self.drain_s = float(spec.get("drain_s", 10.0))
        sp = spec.get("shared_prefix")
        self.groups = int(sp["groups"]) if sp else 0
        self.prefix_tokens = int(sp["tokens"]) if sp else 0
        self.assign = sp.get("assign", "round_robin") if sp else None
        self.prefill_in_setup = bool(sp.get("prefill_in_setup")) if sp else False
        self._pool_rng = np.random.default_rng(int(spec.get("pool_seed", 0)))
        # numpy takes any non-negative whole number as a seed (the driver's
        # are above 2**31)
        if spec.get("order", "per_seed") not in ("per_seed", "fixed"):
            raise ValueError("order must be 'per_seed' or 'fixed'")
        order_seed = int(spec.get("pool_seed", 0)) if spec.get("order") == "fixed" else int(seed)
        self._rng = np.random.default_rng([order_seed, 0x7A11])
        tok_rng = np.random.default_rng([int(seed), 0x70C5])
        self._bank = tok_rng.integers(0, self.vocab, _TOKEN_BANK, dtype=np.int32)
        self._prefixes = [
            tok_rng.integers(0, self.vocab, self.prefix_tokens, dtype=np.int32)
            for _ in range(self.groups)
        ]
        if self.loop == "open":
            n = max(1, round(float(spec["rate_per_s"]) * self.seconds))
        else:
            n = int(spec.get("pool", 64))
        self._n = n
        # multisets first (pool_seed), permutations second (--seed)
        self._prompts = draw_lengths(spec["prompt"], n, self._pool_rng)
        self._outputs = draw_lengths(spec["output"], n, self._pool_rng)
        gaps = None
        if self.loop == "open":
            gaps = draw_gaps(spec.get("arrival", {}), n, self._pool_rng)
            gaps = gaps * (self.seconds / gaps.sum())
        self._gaps = gaps
        self._issued = 0
        self._cycle: List[Request] = []
        self.max_fresh = int(self._prompts.max())
        self.max_output = int(self._outputs.max())

    # -- building ----------------------------------------------------------
    def _one_cycle(self, base_index: int) -> List[Request]:
        prompts = self._rng.permutation(self._prompts)
        outputs = self._rng.permutation(self._outputs)
        offsets = self._rng.integers(0, _TOKEN_BANK - self.max_fresh, self._n)
        dues: List[Optional[float]] = [None] * self._n
        if self._gaps is not None:
            gaps = self._rng.permutation(self._gaps)
            # a request is due at the START of its gap, so the first is due
            # at 0 and the last before the window closes
            dues = list(np.concatenate([[0.0], np.cumsum(gaps)[:-1]]))
        out = []
        for i in range(self._n):
            idx = base_index + i
            group = None
            if self.groups and self.assign == "round_robin":
                group = idx % self.groups
            out.append(Request(
                index=idx, due_s=None if dues[i] is None else float(dues[i]),
                group=group, fresh_tokens=int(prompts[i]),
                output_tokens=int(outputs[i]), bank_offset=int(offsets[i]),
            ))
        return out

    def open_schedule(self) -> List[Request]:
        """Open loop: every request due in ``[0, seconds)``, in due order."""
        if self.loop != "open":
            raise ValueError("open_schedule() on a closed-loop mix")
        return self._one_cycle(0)

    def next_request(self, client: int) -> Request:
        """Closed loop: the next request of the endless shuffled cycle, for
        ``client`` (which fixes the prefix group under ``assign: client``)."""
        if not self._cycle:
            self._cycle = self._one_cycle(self._issued)
            self._cycle.reverse()
        req = self._cycle.pop()
        self._issued += 1
        if self.groups and self.assign == "client":
            req.group = client % self.groups
        return req

    # -- tokens ------------------------------------------------------------
    def prefix(self, group: int) -> np.ndarray:
        return self._prefixes[group]

    def tokens(self, req: Request) -> List[int]:
        fresh = self._bank[req.bank_offset: req.bank_offset + req.fresh_tokens]
        if req.group is None:
            return fresh.tolist()
        return np.concatenate([self._prefixes[req.group], fresh]).tolist()

    def longest_prompt(self) -> int:
        return self.prefix_tokens + self.max_fresh

    def describe(self) -> Dict[str, Any]:
        d = {
            "loop": self.loop,
            "sizes_in_cycle": self._n,
            "prompt_tokens": _summary(self._prompts + self.prefix_tokens),
            "output_tokens": _summary(self._outputs),
        }
        if self._gaps is not None:
            d["gap_s"] = _summary(self._gaps)
        return d


def _summary(a: np.ndarray) -> Dict[str, float]:
    return {
        "min": float(a.min()), "median": float(np.median(a)),
        "mean": float(a.mean()), "max": float(a.max()),
    }


def iter_sample_prompts(seed: int, vocab: int, n: int, lo: int, hi: int,
                        longer_than: int) -> Iterator[List[int]]:
    """The seeded sample of the reference comparison: ``n`` prompts of
    ``lo..hi`` tokens, the last forced above ``longer_than`` (a prefill
    chunk) when ``hi`` allows it."""
    rng = np.random.default_rng([int(seed), 0x5A3B])
    lens = sorted(int(x) for x in rng.integers(lo, hi + 1, n))
    if longer_than < hi:
        lens[-1] = max(lens[-1], int(rng.integers(longer_than + 1, hi + 1)))
    for L in lens:
        yield rng.integers(0, vocab, L, dtype=np.int32).tolist()
