#!/usr/bin/env python3
"""chip_smoke.py — prove that the serving path still starts and answers on the chip.

    python3 chip_smoke.py             one chip: serve qwen3-0.6b, then check kernels
    python3 chip_smoke.py --chips 4   four chips: tp=4 against tp=1, and llama3-8b tp=4

Run it where JAX finds a TPU (the builder's chip tool); without one it exits
non-zero and prints no result line. This process never initialises a JAX
backend — a chip belongs to one process at a time — so everything that needs
the chip runs in a child, one child on the chip at a time:

1. SERVING. The processes a user starts (README "Quick start"): a discovery
   store, ``python -m dynamo_tpu.engine --platform tpu --preset qwen3-0.6b``
   (full published width and depth, random bf16 weights from the engine's fixed
   seed) and ``python -m dynamo_tpu.frontend`` (kept off the chip with
   JAX_PLATFORMS=cpu). OpenAI requests go over HTTP to the frontend: chat and
   completions, streaming and not, greedy and sampled, prompts in two prefill
   buckets and one multi-chunk prefill, then long prompts admitted on top of
   resident decodes so the fused mixed step is dispatched.
2. KERNELS. After the servers have released the chip, a child runs every
   Pallas kernel of that path compiled at qwen3-0.6b's shapes against its
   pure-JAX twin on the same chip.

With ``--chips 4`` only the sharded path and what it is compared with run:
qwen3-0.6b at --tp 1 and --tp 4 (same seed, same prompts), then
``--preset llama3-8b --tp 4``, each through the same engine entry point.

Any failed check, a child that dies, or a platform other than ``tpu`` ends
the run non-zero. The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
Child logs go to ``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, Iterator, List, Optional

from dynamo_tpu.runtime.device import compile_cache_dir  # imports no JAX

REPO = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
MODEL = "smoke"
SEED = 0
PLATFORM = "tpu"

# kernel-against-twin tolerance, per element: 2^-6 * max(1, |twin|). Both
# sides accumulate in float32 from the same bf16 inputs (the twin at
# "highest" matmul precision) and round once to bf16, whose spacing is 2^-7
# relative at the bottom of a binade: the bound is two such steps, so two
# roundings that fall either side of a boundary pass and a wrong key or a
# wrong mask (errors of order 1) cannot.
KERNEL_TOL = 2.0 ** -6
# tp=4 against tp=1, per prompt: greedy tokens agree until a near-tie. The
# psum changes the order of bf16 additions, so exact agreement for ever is
# not promised (random weights make flat logits and frequent ties); a wrong
# shard would move logprobs by nats. While the tokens agree their logprobs
# differ by at most this much, and where they first part each side ranks
# the other's token in its top 5 within this much of its own choice.
TP_LOGPROB_TOL = 0.25
# bytes in use on the fullest device over the emptiest, llama3-8b at tp=4
TP_BALANCE_RATIO = 1.25


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ------------------------------------------------------------------ children
class Child:
    """One child process with its output in a log file."""

    def __init__(self, name: str, argv: List[str], env: Dict[str, str]):
        os.makedirs(LOG_DIR, exist_ok=True)
        self.name = name
        self.log_path = os.path.join(LOG_DIR, f"{name}.log")
        self._log = open(self.log_path, "wb")
        self.started = time.monotonic()
        self.proc = subprocess.Popen(
            argv, cwd=REPO, env=env, stdout=self._log,
            stderr=subprocess.STDOUT,
        )

    def log_text(self) -> str:
        with open(self.log_path, "r", errors="replace") as f:
            return f.read()

    def wait_for(self, marker: str, timeout_s: float) -> float:
        """Seconds from process start until ``marker`` shows in the log."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if marker in self.log_text():
                return time.monotonic() - self.started
            if self.proc.poll() is not None:
                self.fail(f"exited with {self.proc.returncode} before {marker!r}")
            time.sleep(0.2)
        self.fail(f"no {marker!r} within {timeout_s:.0f}s")

    def fail(self, why: str) -> None:
        sys.stderr.write(
            f"--- {self.name}: {why}; end of {self.log_path}:\n"
            + self.log_text()[-6000:] + "\n"
        )
        raise SmokeFailure(f"{self.name}: {why}")

    def alive(self) -> bool:
        return self.proc.poll() is None

    def wait(self, timeout_s: float) -> int:
        """The exit code (SIGKILL past the timeout)."""
        try:
            code = self.proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
            code = -9
        self._log.close()
        return code

    def terminate(self, timeout_s: float = 60.0) -> int:
        """SIGTERM, then the exit code."""
        if self.alive():
            self.proc.send_signal(signal.SIGTERM)
        return self.wait(timeout_s)

    def maps_libtpu(self) -> bool:
        """Whether the TPU runtime is mapped into the process — it is once a
        TPU backend was initialised, and never before."""
        with open(f"/proc/{self.proc.pid}/maps", "r") as f:
            return "libtpu" in f.read()


def child_env(on_chip: bool) -> Dict[str, str]:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["TPU_LOG_DIR"] = env.get("TPU_LOG_DIR", "disabled")
    if on_chip:
        # the worker forces its platform itself (--platform tpu)
        env.pop("JAX_PLATFORMS", None)
    else:
        env["JAX_PLATFORMS"] = "cpu"
    return env


# ---------------------------------------------------------------------- HTTP
def http_get(url: str, timeout_s: float = 30.0) -> bytes:
    with urllib.request.urlopen(url, timeout=timeout_s) as r:
        check(r.status == 200, f"GET {url} -> {r.status}")
        return r.read()


def sse_events(resp) -> Iterator[Dict[str, Any]]:
    for raw in resp:
        line = raw.decode("utf-8").strip()
        if not line.startswith("data:"):
            continue
        data = line[5:].strip()
        if data == "[DONE]":
            return
        yield json.loads(data)


class Answer:
    """One finished OpenAI response, streaming or not, in one shape."""

    def __init__(self) -> None:
        self.text = ""
        self.finish_reason: Optional[str] = None
        self.usage: Dict[str, Any] = {}
        self.tokens: List[str] = []     # per-token strings (logprobs on)
        self.logprobs: List[float] = []
        self.top: List[Dict[str, float]] = []   # per-token alternatives
        self.seconds = 0.0

    def take_choice(self, choice: Dict[str, Any], chat: bool) -> None:
        if chat:
            part = choice.get("delta") or choice.get("message") or {}
            self.text += part.get("content") or ""
            for ent in (choice.get("logprobs") or {}).get("content") or []:
                self.tokens.append(ent["token"])
                self.logprobs.append(ent["logprob"])
        else:
            self.text += choice.get("text") or ""
            lp = choice.get("logprobs") or {}
            self.tokens += lp.get("tokens") or []
            self.logprobs += lp.get("token_logprobs") or []
            self.top += lp.get("top_logprobs") or []
        if choice.get("finish_reason"):
            self.finish_reason = choice["finish_reason"]


def ask(base: str, path: str, body: Dict[str, Any],
        on_first=None, timeout_s: float = 900.0) -> Answer:
    """POST an OpenAI request; any status but 200 fails. ``on_first`` fires
    when the first streamed chunk arrives."""
    chat = path.endswith("/chat/completions")
    body = dict(body, model=MODEL)
    if body.get("stream"):
        body["stream_options"] = {"include_usage": True}
    req = urllib.request.Request(
        base + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    ans = Answer()
    t0 = time.monotonic()
    try:
        resp = urllib.request.urlopen(req, timeout=timeout_s)
    except urllib.error.HTTPError as e:
        raise SmokeFailure(
            f"POST {path} -> {e.code}: {e.read()[:500]!r}"
        ) from None
    with resp:
        check(resp.status == 200, f"POST {path} -> {resp.status}")
        if body.get("stream"):
            for ev in sse_events(resp):
                check("error" not in ev, f"{path} streamed an error: {ev}")
                for choice in ev.get("choices") or []:
                    ans.take_choice(choice, chat)
                if ev.get("usage"):
                    ans.usage = ev["usage"]
                if on_first is not None:
                    on_first()
                    on_first = None
        else:
            doc = json.loads(resp.read())
            for choice in doc["choices"]:
                ans.take_choice(choice, chat)
            ans.usage = doc.get("usage") or {}
    ans.seconds = time.monotonic() - t0
    return ans


def check_answer(ans: Answer, what: str, *, max_tokens: int,
                 prompt_tokens: Optional[int] = None,
                 exact_length: bool = True) -> None:
    check(bool(ans.text), f"{what}: empty text")
    u = ans.usage
    check(bool(u), f"{what}: no usage")
    if exact_length:
        check(ans.finish_reason == "length",
              f"{what}: finish_reason {ans.finish_reason!r}, want 'length'")
        check(u["completion_tokens"] == max_tokens,
              f"{what}: {u['completion_tokens']} completion tokens, "
              f"asked {max_tokens}")
    else:
        check(ans.finish_reason in ("length", "stop"),
              f"{what}: finish_reason {ans.finish_reason!r}")
        check(1 <= u["completion_tokens"] <= max_tokens,
              f"{what}: {u['completion_tokens']} completion tokens of "
              f"{max_tokens}")
        if ans.finish_reason == "length":
            check(u["completion_tokens"] == max_tokens,
                  f"{what}: finished by length short of max_tokens")
    if prompt_tokens is not None:
        check(u["prompt_tokens"] == prompt_tokens,
              f"{what}: {u['prompt_tokens']} prompt tokens, sent "
              f"{prompt_tokens}")
    check(u["total_tokens"] == u["prompt_tokens"] + u["completion_tokens"],
          f"{what}: usage does not add up: {u}")


def token_prompt(n: int, salt: int) -> List[int]:
    """n token ids from the seed (the byte tokenizer's plain range)."""
    return [(SEED * 7919 + salt * 131 + j * 7) % 251 + 1 for j in range(n)]


def text_prompt(n_bytes: int, salt: int) -> str:
    words = ["paged", "cache", "kernel", "decode", "prefill", "router",
             "block", "shard", "token", "mesh", "batch", "chunk"]
    out, j = [], SEED + salt
    while sum(len(w) + 1 for w in out) < n_bytes:
        out.append(words[(j * 5 + salt) % len(words)])
        j += 1
    return " ".join(out)[:n_bytes]


# ------------------------------------------------------------------- serving
class Stack:
    """Discovery store + one engine worker on the chip + the frontend. A
    context manager: whatever is still running on the way out is stopped,
    also when the stack never came up."""

    def __init__(self, tag: str, worker_args: List[str],
                 ready_timeout_s: float):
        self.tag = tag
        self.children: List[Child] = []
        try:
            self._start_all(worker_args, ready_timeout_s)
        except BaseException:
            self.close()
            raise

    def __enter__(self) -> "Stack":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _start_all(self, worker_args: List[str],
                   ready_timeout_s: float) -> None:
        py = sys.executable
        store_port, self.status_port, http_port = (
            free_port(), free_port(), free_port()
        )
        store_args = ["--store", "tcp", "--store-path",
                      f"127.0.0.1:{store_port}"]
        self.store = self._start(
            "store", [py, "-m", "dynamo_tpu.runtime.discovery.netstore",
                      "--port", str(store_port)], on_chip=False)
        self.store.wait_for("KVSTORE_READY", 60)
        self.worker = self._start(
            "worker", [py, "-m", "dynamo_tpu.engine", "--platform", PLATFORM,
                       "--model", MODEL, "--status-port",
                       str(self.status_port)] + store_args + worker_args,
            on_chip=True)
        self.frontend = self._start(
            "frontend", [py, "-m", "dynamo_tpu.frontend", "--host",
                         "127.0.0.1", "--port", str(http_port)] + store_args,
            on_chip=False)
        self.base = f"http://127.0.0.1:{http_port}"
        self.status = f"http://127.0.0.1:{self.status_port}"
        self.cold_start_s = self.worker.wait_for(
            "TPU_ENGINE_READY", ready_timeout_s
        )
        deadline = time.monotonic() + 60
        while MODEL not in self._models():
            check(time.monotonic() < deadline,
                  "frontend never listed the worker's model")
            check(self.frontend.alive(), "frontend died")
            time.sleep(0.2)

    def _start(self, name: str, argv: List[str], on_chip: bool) -> Child:
        child = Child(f"{self.tag}-{name}", argv, child_env(on_chip))
        self.children.append(child)
        return child

    def _models(self) -> List[str]:
        try:
            doc = json.loads(http_get(self.base + "/v1/models", 5))
        except (urllib.error.URLError, ConnectionError, SmokeFailure):
            return []
        return [m["id"] for m in doc.get("data", [])]

    def metadata(self) -> Dict[str, Any]:
        return json.loads(http_get(self.status + "/metadata"))

    def step_counts(self) -> Dict[str, int]:
        """phase -> engine steps, from the worker's /metrics."""
        counts: Dict[str, int] = {}
        prefix = "dtpu_engine_step_duration_seconds_count{"
        for line in http_get(self.status + "/metrics").decode().splitlines():
            if line.startswith(prefix) and 'phase="' in line:
                phase = line.split('phase="', 1)[1].split('"', 1)[0]
                counts[phase] = counts.get(phase, 0) + int(
                    float(line.rsplit(" ", 1)[1])
                )
        return counts

    def check_processes(self) -> None:
        """Only the worker may have brought up a TPU backend."""
        check(self.worker.maps_libtpu(),
              "the worker never loaded the TPU runtime")
        for child in (self.store, self.frontend):
            check(not child.maps_libtpu(),
                  f"{child.name} loaded the TPU runtime: it must stay off "
                  "the chip")

    def stop(self) -> None:
        """SIGTERM every child; each must be alive until then and exit 0."""
        dead = [c.name for c in self.children if not c.alive()]
        codes = self.close()
        check(not dead, f"children died before shutdown: {dead}")
        bad = {n: c for n, c in codes.items() if c != 0}
        check(not bad, f"children did not exit 0 on SIGTERM: {bad}")

    def close(self) -> Dict[str, int]:
        """Stop whatever still runs, frontend first; name -> exit code."""
        codes = {c.name: c.terminate() for c in reversed(self.children)}
        self.children = []
        return codes


def report_worker(stack: Stack) -> Dict[str, Any]:
    """Print what the worker says it runs on, and hold it to the chip."""
    meta = stack.metadata()
    eng = meta["engine"]
    dev = eng["device"]
    say(f"[{stack.tag}] worker device: {json.dumps(dev)}")
    say(f"[{stack.tag}] use_pallas: {json.dumps(eng['use_pallas'])}  "
        f"mixed_enabled: {json.dumps(eng['mixed_enabled'])}  "
        f"kernels_interpreted: {json.dumps(eng['kernels_interpreted'])}  "
        f"decode_steps: {eng['decode_steps']}  "
        f"decode_pipeline: {eng['decode_pipeline']}")
    check(dev["platform"] == PLATFORM, f"worker platform {dev['platform']!r}")
    check(eng["use_pallas"] is True, "the Pallas kernels resolved off")
    check(eng["kernels_interpreted"] is False,
          "Pallas kernels run in the interpreter")
    return meta


def serving_phase() -> Dict[str, Any]:
    with Stack(
        "serve",
        ["--preset", "qwen3-0.6b", "--prefill-chunk", "512",
         "--max-context", "2048"],
        ready_timeout_s=600,
    ) as stack:
        say(f"[serve] cold start (process start -> TPU_ENGINE_READY): "
            f"{stack.cold_start_s:.1f} s")
        meta = report_worker(stack)
        check(meta["engine"]["mixed_enabled"] is True,
              "the fused mixed step resolved off")
        base = stack.base
        comp, chat = "/v1/completions", "/v1/chat/completions"
        greedy = {"temperature": 0.0, "ignore_eos": True, "max_tokens": 64}

        # -- one at a time: two prefill buckets, one multi-chunk prefill --
        short = token_prompt(40, 1)                   # bucket 64
        first = ask(base, comp, dict(greedy, prompt=short))
        check_answer(first, "first completion", max_tokens=64,
                     prompt_tokens=40)
        say(f"[serve] first request (compiles): {first.seconds:.1f} s")
        again = ask(base, comp, dict(greedy, prompt=short))
        check_answer(again, "repeated completion", max_tokens=64,
                     prompt_tokens=40)
        say(f"[serve] same request, warm: {again.seconds:.2f} s")
        check(again.text == first.text,
              "the same greedy request gave two different texts")
        check((again.usage.get("cached_tokens") or 0) > 0,
              f"repeated prompt reported no cached tokens: {again.usage}")

        mid = text_prompt(180, 2)                     # bucket 256 w/ template
        streamed = ask(base, chat, dict(
            greedy, messages=[{"role": "user", "content": mid}], stream=True,
        ))
        check_answer(streamed, "streamed chat", max_tokens=64)
        check(streamed.usage["prompt_tokens"] >= 180,
              f"chat prompt shorter than its content: {streamed.usage}")
        fork = ask(base, chat, dict(
            greedy,
            messages=[{"role": "user", "content": mid + " and then some"}],
        ))
        check_answer(fork, "chat sharing a prefix", max_tokens=64)
        check((fork.usage.get("cached_tokens") or 0) > 0,
              f"shared prefix reported no cached tokens: {fork.usage}")

        sampled = ask(base, chat, {
            "messages": [{"role": "user", "content": text_prompt(60, 3)}],
            "temperature": 0.8, "top_p": 0.9, "top_k": 50, "seed": 7,
            "max_tokens": 64,
        })
        check_answer(sampled, "sampled chat", max_tokens=64,
                     exact_length=False)

        long_ = token_prompt(1100, 4)                 # chunks 512 + 512 + 76
        chunked = ask(base, comp, dict(greedy, prompt=long_, stream=True))
        check_answer(chunked, "multi-chunk streamed completion",
                     max_tokens=64, prompt_tokens=1100)

        # -- together: long prefills admitted on top of resident decodes --
        residents = [
            dict(greedy, prompt=token_prompt(48, 10 + i), stream=True,
                 max_tokens=1024, **extra)
            for i, extra in enumerate((
                {}, {}, {"temperature": 0.7, "top_p": 0.95, "seed": 3},
            ))
        ]
        riders = [
            dict(greedy, prompt=token_prompt(1100, 20)),
            dict(greedy, prompt=token_prompt(700, 21)),
        ]
        with ThreadPoolExecutor(len(residents) + len(riders)) as pool:
            decoding = [threading.Event() for _ in residents]
            res_f = [
                pool.submit(ask, base, comp, body, ev.set)
                for body, ev in zip(residents, decoding)
            ]
            for ev, fut in zip(decoding, res_f):
                while not ev.wait(0.2):
                    if fut.done():
                        fut.result()  # raises what the request raised
                        raise SmokeFailure(
                            "a resident stream ended before its first chunk"
                        )
            ride_f = [pool.submit(ask, base, comp, body) for body in riders]
            for i, fut in enumerate(ride_f):
                check_answer(fut.result(), f"rider {i}", max_tokens=64,
                             prompt_tokens=len(riders[i]["prompt"]))
            for i, fut in enumerate(res_f):
                check_answer(fut.result(), f"resident {i}", max_tokens=1024,
                             prompt_tokens=48)

        steps = stack.step_counts()
        say(f"[serve] engine steps by phase: {json.dumps(steps, sort_keys=True)}")
        for phase in ("prefill", "decode", "mixed"):
            check(steps.get(phase, 0) > 0,
                  f"no {phase!r} step was dispatched: {steps}")
        http_get(stack.status + "/health")  # 503 when a target is unhealthy
        meta = stack.metadata()
        say(f"[serve] compile cache: {json.dumps(meta['compile_cache'])}")
        stack.check_processes()
        say("[serve] worker healthy; frontend and store never loaded the "
            "TPU runtime")
        stack.stop()
        say("[serve] every child exited 0 on SIGTERM")
        return meta["engine"]["device"]


# ------------------------------------------------------------------- kernels
def kernel_phase() -> Dict[str, Any]:
    """The kernel-against-reference child: see ``_kernel_child``."""
    child = Child(
        "kernels",
        [sys.executable, "-c",
         "import chip_smoke; chip_smoke._kernel_child()"],
        child_env(on_chip=True),
    )
    code = child.wait(900)
    text = child.log_text()
    lines = [ln for ln in text.splitlines() if ln.startswith("KERNEL ")]
    for ln in lines:
        say("[kernels] " + ln[len("KERNEL "):])
    if code != 0:
        child.fail(f"exited with {code}")
    result = [ln for ln in text.splitlines() if ln.startswith("KERNELS_OK ")]
    check(len(result) == 1, "the kernel child printed no result")
    return json.loads(result[0][len("KERNELS_OK "):])


def _best_ms_a_launch(run, n: int, args) -> float:
    """``run(*args)`` is one program of ``n`` chained launches, already
    jitted: ms a launch, the best of three runs after the one that compiles."""
    import jax

    jax.block_until_ready(run(*args))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(run(*args))
        best = min(best, (time.perf_counter() - t0) / n)
    return best * 1e3


def selection_case() -> None:
    """ISSUE 57's step 0, for the next reader to repeat: the indexer's exact
    selection (``ops/attention.dsa_select``: a cut by counting, a list by
    compaction) beside the ``lax.top_k`` it replaced (a full stable sort a
    row on a TPU), at the two selecting cells' shapes: dots3's [2 064,
    37 376] (a chunk of 2 048 + 16 decode rows), [528, 37 376], [16, 37 376]
    (a decode step) and GLM's [520, 25 600], [8, 25 600]; 2 048 selected,
    float32 scores (every seventh rounded to eighths: cuts inside runs of
    equal values too) under the causal mask a chunk has, its padding
    invalid. Each side's launches chained in ONE program (a launch's rows
    wait for the one before), ms a launch; the two sides' sets compared row
    by row on the chip's own outputs. Alone: ``chiprun -- python -c "import
    chip_smoke; chip_smoke.selection_case()"`` (PERF.md section 6, PR 57,
    has the table)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops import attention as att

    K = 2048
    rng = np.random.default_rng(SEED)

    def top_k(scores, q_pos, q_valid):
        # the selection as it was until PR 57, line for line
        seen = (jnp.arange(scores.shape[1])[None, :] <= q_pos[:, None]) & q_valid[:, None]
        _, idx = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), K)
        idx = idx.astype(jnp.int32)
        ok = (idx <= q_pos[:, None]) & q_valid[:, None]
        return jnp.where(ok, idx, att.SEL_NONE)

    def chained(select, n):
        def run(scores, q_pos, q_valid):
            def body(_, carry):
                pos, acc = carry
                sel = select(scores, pos, q_valid)
                # never true, and the compiler cannot know: the next launch
                # waits for this one's rows
                return (pos - (sel[:, 0] < -5).astype(jnp.int32),
                        acc + jnp.sum(sel[:, :8], axis=1))
            return jax.lax.fori_loop(0, n, body, (q_pos, jnp.zeros_like(q_pos)))
        return jax.jit(run)

    def ms_a_launch(select, n, args):
        return _best_ms_a_launch(chained(select, n), n, args)

    for Q, T, n_chunk in ((2064, 37376, 2048), (528, 37376, 512), (16, 37376, 0),
                          (520, 25600, 512), (8, 25600, 0)):
        scores = rng.standard_normal((Q, T)).astype(np.float32)
        scores[:, ::7] = np.round(scores[:, ::7] * 8) / 8
        q_pos = np.concatenate([
            T - n_chunk - 611 + np.arange(n_chunk),
            rng.integers(T - 5000, T, size=Q - n_chunk),
        ]).astype(np.int32)
        q_valid = np.ones(Q, bool)
        q_valid[max(n_chunk - 37, 0):n_chunk] = False
        args = (jnp.asarray(scores), jnp.asarray(q_pos), jnp.asarray(q_valid))
        got = np.asarray(jax.jit(att.dsa_select, static_argnums=3)(*args, K))
        want = np.sort(np.asarray(jax.jit(top_k)(*args)), axis=1)
        kept = (want >= 0).sum(axis=1)
        for g, w, n in zip(got, want, kept):
            if g[:n].tolist() != w[len(w) - n:].tolist() or (g[n:] != att.SEL_NONE).any():
                raise SystemExit(f"dsa_select [{Q}, {T}]: not lax.top_k's set, "
                                 f"ascending, SEL_NONE after")
        n = 20 if Q <= 16 else 4
        t_sort = ms_a_launch(top_k, n, args)
        t_new = ms_a_launch(lambda s, p, v: att.dsa_select(s, p, v, K), n, args)
        print(f"KERNEL dsa_select [{Q}, {T}] keep {K}: lax.top_k's sets, "
              f"ascending ({int(kept.sum())} positions); {t_new:.3f} ms a "
              f"launch, lax.top_k {t_sort:.3f} ms ({t_sort / t_new:.2f} x), "
              f"{t_new / Q * 1e3:.2f} us a row", flush=True)


def index_scores_case() -> None:
    """ISSUE 59's step 0, for the next reader to repeat: the indexer's scoring
    alone (``ops/attention.dsa_index_scores``), head by head over ALL of a
    chunk's queries as it ran until PR 59 (the scan carries the ``[Q, T]``
    float32 sum through HBM, a read and a write of it a head) beside slabs of
    512 and of 256 queries (a slab's sum stays on chip through the heads), at
    the agent cell's two buckets and the width between (2 048, 1 024, 512
    queries x 64 heads against 37 376 keys) and GLM's widest (512 x 32 against
    25 600). The form is chosen by patching ``INDEX_SLAB_BYTES`` around the
    trace, the way a test does; the slabbed scores are compared bit for bit
    with the whole chunk's on the chip. Then a chunk launch's scores AND
    selection at the first shape: ``dsa_select`` over the whole chunk's
    scores, and inside the slab loop as the seam runs it (a slab's scores
    never leave the chip for the counting passes), lists compared. Then the
    seam's whole mixed attend (index keys, scores and selection a slab,
    ``sparse_latent_attention`` with its staged pages) at 2 048 + 16 rows.
    Launches chained in ONE program, ms a launch. What this case cannot see:
    whether a slab stays on chip beside what else a STEP keeps there (slabs
    of 512 rows do here and do not in the agent cell's step). Alone:
    ``chiprun -- python -c "import chip_smoke;
    chip_smoke.index_scores_case()"`` (PERF.md section 6, PR 59, has the
    table)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops import attention as att
    from dynamo_tpu.ops.paged_attention import PagedAttention
    from dynamo_tpu.parallel.mesh import single_device_mesh

    rng = np.random.default_rng(SEED)
    bf = jnp.bfloat16
    constant = att.INDEX_SLAB_BYTES

    def rnd(*shape, dtype=bf):
        return jnp.asarray(rng.standard_normal(shape, np.float32), dtype)

    def with_slab(rows, T, fn):
        """``fn`` traced with slabs of ``rows`` queries (None: one slab)."""
        def run(*a):
            att.INDEX_SLAB_BYTES = 2 ** 62 if rows is None else rows * T * 4
            try:
                return fn(*a)
            finally:
                att.INDEX_SLAB_BYTES = constant
        return run

    def ms_a_launch(fn, n, args):
        """``fn(iw, *rest) -> an array``: ``n`` launches, each one's weights
        waiting for the one before (never changed, and the compiler cannot
        know)."""
        def run(iw, *rest):
            def body(_, iw):
                out = fn(iw, *rest)
                return iw + (out.reshape(-1)[0] > 3e38).astype(iw.dtype)
            return jax.lax.fori_loop(0, n, body, iw)
        return _best_ms_a_launch(jax.jit(run), n, args)

    forms = (("whole", None), ("slabs of 512", 512), ("slabs of 256", 256))
    for Q, n, T in ((2048, 64, 37376), (1024, 64, 37376), (512, 64, 37376),
                    (512, 32, 25600)):
        iq, iw, keys = rnd(Q, n, 128), rnd(Q, n, dtype=jnp.float32), rnd(T, 128)
        score = lambda iw, iq, keys: att.dsa_index_scores(iq, iw, keys)  # noqa: E731
        whole = jax.jit(with_slab(None, T, score))(iw, iq, keys)
        said = []
        for name, rows in forms:
            if rows is not None and rows >= Q:
                continue                                 # one slab: the first form
            fn = with_slab(rows, T, score)
            if not bool(jnp.array_equal(jax.jit(fn)(iw, iq, keys), whole)):
                raise SystemExit(f"dsa_index_scores [{Q}, {n}] x [{T}] in "
                                 f"{name}: not the whole chunk's scores bit for bit")
            said.append(f"{name} {ms_a_launch(fn, 4, (iw, iq, keys)):.3f} ms")
        print(f"KERNEL dsa_index_scores [{Q}, {n}, 128] x [{T}, 128]: slabs bit "
              f"for bit the whole chunk's; {', '.join(said)} "
              f"(constant: slabs of {att.index_slab_rows(Q, T)})", flush=True)

    # a chunk launch's scores and selection, the selection outside and inside
    # the slab loop
    Q, n, T, K = 2048, 64, 37376, 2048
    iq, iw, keys = rnd(Q, n, 128), rnd(Q, n, dtype=jnp.float32), rnd(T, 128)
    q_pos = jnp.asarray(T - Q - 611 + np.arange(Q), jnp.int32)
    q_valid = jnp.asarray(np.arange(Q) < Q - 37)

    def outside(iw, iq, keys):
        return att.dsa_select(att.dsa_index_scores(iq, iw, keys), q_pos, q_valid, K)

    def inside(rows):
        return lambda iw, iq, keys: att.by_slabs(
            lambda a, b, pos, valid: att.dsa_select(
                att.dsa_index_scores(a, b, keys), pos, valid, K),
            rows, iq, iw, q_pos, q_valid)

    want = jax.jit(with_slab(None, T, outside))(iw, iq, keys)
    said = []
    for name, rows in forms:
        ways = [("selection outside", with_slab(rows, T, outside))]
        if rows is not None:
            ways.append(("selection inside", with_slab(None, T, inside(rows))))
        for way, fn in ways:
            if not bool(jnp.array_equal(jax.jit(fn)(iw, iq, keys), want)):
                raise SystemExit(f"scores and selection in {name}, {way}: not "
                                 "the whole chunk's lists")
            said.append(f"{name}, {way} {ms_a_launch(fn, 4, (iw, iq, keys)):.3f} ms")
    print(f"KERNEL dsa_index_scores + dsa_select [{Q}, {n}, 128] x [{T}, 128] "
          f"keep {K}: the same lists; {'; '.join(said)}", flush=True)

    # the seam's mixed attend of a full layer of dots3-note at the agent
    # cell's widest step: 2 048 chunk queries + 16 decode rows, 37 376 keys
    R, MB, NB, H = 17, 2336, 37888, 128
    seam = PagedAttention(single_device_mesh(), True)
    Tq = Q + R - 1
    q = rnd(Tq, H, 640)
    kc, vc = rnd(NB, 16, 4, 128), rnd(NB, 16, 2, 128)
    tables = jnp.asarray(np.stack(
        [rng.permutation(np.arange(1, NB))[:MB] for _ in range(R)]), jnp.int32)
    iq, iw = rnd(Tq, n, 128), rnd(Tq, n, dtype=jnp.float32)
    q_starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                Q + jnp.arange(R - 1, dtype=jnp.int32)])
    q_lens = jnp.concatenate([jnp.full((1,), Q, jnp.int32),
                              jnp.ones((R - 1,), jnp.int32)])
    seq_lens = jnp.asarray(np.r_[T - 611, rng.integers(T - 5000, T, R - 1)], jnp.int32)

    def attend(iw, iq, q, kc, vc):
        return seam.ragged(
            q, kc, vc, tables, q_starts, q_lens, seq_lens,
            dsa=att.DsaQuery(scale=1 / 16, topk=K, index_q=iq, index_w=iw))

    args = (iw, iq, q, kc, vc)
    want = jax.jit(with_slab(None, T, attend))(*args)
    said = []
    for name, rows in forms:
        fn = with_slab(rows, T, attend)
        if not bool(jnp.array_equal(jax.jit(fn)(*args), want)):
            raise SystemExit(f"the mixed attend in {name}: not the whole chunk's output")
        said.append(f"{name} {ms_a_launch(fn, 3, args):.3f} ms")
    print(f"KERNEL mixed attend [{Q} + {R - 1} rows, {H} heads] x [{T}] keys, "
          f"dots3-note's full layer: the same output; {', '.join(said)}", flush=True)


def _kernel_child() -> None:
    """Runs IN THE CHILD that owns the chip: every Pallas kernel of the
    serving path, compiled (never interpreted) at qwen3-0.6b's shapes,
    against its pure-JAX twin on the same device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops import attention as att
    from dynamo_tpu.ops import block_copy as bc
    from dynamo_tpu.ops import pallas_moe as pmoe
    from dynamo_tpu.ops import pallas_unified as pun
    from dynamo_tpu.ops.paged_attention import PagedAttention
    from dynamo_tpu.parallel.mesh import single_device_mesh
    from dynamo_tpu.runtime.device import (
        device_info,
        enable_compile_cache,
        on_tpu,
    )

    jax.config.update("jax_platforms", PLATFORM)
    enable_compile_cache()
    dev = device_info()
    print(f"KERNEL device: {json.dumps(dev)}", flush=True)
    if not on_tpu():
        raise SystemExit("kernel phase needs the TPU backend")

    NB, BS, KVH, H, D, MB, B = 2048, 16, 8, 16, 128, 128, 8
    bf = jnp.bfloat16
    rng = np.random.default_rng(SEED)

    def rnd(*shape):
        return jnp.asarray(rng.standard_normal(shape), bf)

    k_cache, v_cache = rnd(NB, BS, KVH, D), rnd(NB, BS, KVH, D)
    # every row owns its own pages, in a shuffled order
    perm = rng.permutation(NB - 1)[: (B + 1) * MB] + 1
    tables = jnp.asarray(perm.reshape(B + 1, MB), jnp.int32)

    def highest(fn):
        def run(*a, **kw):
            with jax.default_matmul_precision("highest"):
                return fn(*a, **kw)
        return jax.jit(run, static_argnames=("softcap",))

    # the attention seam on its two sides: compiled kernels, pure-JAX twins
    mesh = single_device_mesh()
    kernels, twins = PagedAttention(mesh, True), PagedAttention(mesh, False)
    worst = 0.0

    def compare(name, got, ref):
        nonlocal worst
        got = np.asarray(got, np.float32)
        ref = np.asarray(ref, np.float32)
        if not np.all(np.isfinite(got)):
            raise SystemExit(f"{name}: non-finite output")
        err = np.abs(got - ref)
        share = float(np.max(err / (KERNEL_TOL * np.maximum(1.0, np.abs(ref)))))
        worst = max(worst, share)
        print(f"KERNEL {name}: max |kernel - twin| = {float(err.max()):.3e} "
              f"(max |twin| = {float(np.abs(ref).max()):.2f}), "
              f"{share:.2f} of the tolerance 2^-6 * max(1, |twin|)",
              flush=True)
        if share > 1.0:
            raise SystemExit(f"{name}: over tolerance ({share:.2f} of it)")

    # paged decode: ragged lengths incl. one token, a block edge, full context
    lens = jnp.asarray([1, 16, 17, 333, 1024, 1500, 2047, 2048], jnp.int32)
    q = rnd(B, H, D)
    compare(
        "decode question",
        kernels.decode(q, k_cache, v_cache, tables[:B], lens),
        highest(twins.decode)(q, k_cache, v_cache, tables[:B], lens),
    )

    # ...and the long-cache cell's contexts (BENCHMARK.json): 544 pages a
    # row, one token short of them, a row that is padding, a one-token row
    long_lens = np.asarray([8703, 0, 8704, 1, 4100], np.int32)
    long_mb = 544
    long_tables = np.zeros((len(long_lens), long_mb), np.int32)
    pages = iter(rng.permutation(NB - 1) + 1)
    for row, n in enumerate(long_lens):
        for j in range(-(-int(n) // BS)):
            long_tables[row, j] = next(pages)
    ql = rnd(len(long_lens), H, D)
    long_args = (ql, k_cache, v_cache, jnp.asarray(long_tables),
                 jnp.asarray(long_lens))
    got = np.asarray(kernels.decode(*long_args), np.float32)
    ref = np.asarray(highest(twins.decode)(*long_args), np.float32)
    live = long_lens > 0
    if got[~live].any():
        raise SystemExit("decode question: an empty row is not zeros")
    compare("decode question 8k ragged, one empty row",
            got[live], ref[live])

    # the seam's chunk question, as a lone prefill asks it: a 512-token chunk
    # continuing a 1024-token prefix, one ragged row against the dense
    # extend over the gathered context
    S, start = 512, 1024
    qs = rnd(S, H, D)
    pos = jnp.arange(start, start + S, dtype=jnp.int32)
    chunk_args = (qs, k_cache, v_cache, tables[0],
                  jnp.asarray(start, jnp.int32),
                  jnp.asarray(start + S, jnp.int32), pos)
    compare(
        "chunk question (one ragged row)",
        kernels.chunk(*chunk_args),
        highest(twins.chunk)(*chunk_args),
    )

    # unified ragged: the mixed step's shape — one 512-token chunk at a
    # 1024-token prefix + eight decode rows (one idle) in one launch
    R = B + 1
    q_lens = jnp.asarray([S, 1, 1, 1, 0, 1, 1, 1, 1], jnp.int32)
    seq_lens = jnp.asarray(
        [start + S, 1, 17, 333, 0, 1024, 1500, 2047, 2048], jnp.int32)
    q_starts = jnp.asarray([0] + [S + i for i in range(B)], jnp.int32)
    qu = rnd(S + B, H, D)
    u_args = (qu, k_cache, v_cache, tables[:R], q_starts, q_lens, seq_lens)
    ref_unified = highest(att.ragged_paged_attention)
    compare(
        "ragged_paged_attention plain",
        pun.ragged_paged_attention(*u_args),
        ref_unified(*u_args),
    )
    windows = jnp.asarray([128, 128, 0, 64, 0, 128, 1000, 128, 0], jnp.int32)
    compare(
        "ragged_paged_attention windowed rows",
        pun.ragged_paged_attention(*u_args, windows=windows),
        ref_unified(*u_args, windows=windows),
    )
    sinks = jnp.asarray(rng.standard_normal(H), jnp.float32)
    compare(
        "ragged_paged_attention windows + sinks + softcap",
        pun.ragged_paged_attention(
            *u_args, windows=windows, sinks=sinks, softcap=30.0),
        ref_unified(*u_args, windows=windows, sinks=sinks, softcap=30.0),
    )

    # grouped expert multiplication (the one-chip MoE layer): 300 sorted
    # rows over 8 experts, one empty, groups that straddle a 128-row tile;
    # the SwiGLU front half (two stacks) and the down projection (one)
    sizes = jnp.asarray([70, 0, 7, 90, 1, 100, 2, 30], jnp.int32)
    rows = rnd(300, 512)
    w_gate, w_up = (rnd(8, 512, 384) * 0.044 for _ in range(2))
    w_down = rnd(8, 384, 512) * 0.051
    ref_grouped = highest(pmoe.grouped_matmul_reference)
    act = pmoe.grouped_matmul(rows, (w_gate, w_up), sizes)
    compare("moe_grouped_matmul gate, up and SwiGLU",
            act, ref_grouped(rows, (w_gate, w_up), sizes))
    compare("moe_grouped_matmul down",
            pmoe.grouped_matmul(act, (w_down,), sizes),
            ref_grouped(act, (w_down,), sizes))

    # latent attention over selected keys (models/mla.py with an indexer) at
    # GLM-5.2's widths: 64 absorbed heads over a 512-lane latent + rotary
    # key in rows of 128 lanes; a 128-token chunk at a 24 576-token context
    # and decode rows of two other tables, each query over its own selected
    # token rows: 2 048 of 24k keys (whole chunks, one wait each), and every
    # shape of tail (all of 300 keys; 1, 17, 255, 256, 257 and 2 047 keys;
    # an empty row). Once with the chunk's pages staged in VMEM, once with
    # every query gathering: the same bytes in the same buffer, so bitwise
    # the same; then 200 launches back to back: a miscounted semaphore shows
    # on the chip only, as a hang or as a stale row
    from dynamo_tpu.ops import pallas_sparse as psp

    LNB, LMB, ctx, Sq, topk = 4864, 1600, 24576, 128, 2048
    lat, aux = rnd(LNB, BS, 4, 128), rnd(LNB, BS, 4, 128)
    ltables = jnp.asarray(
        rng.permutation(LNB - 1)[: 3 * LMB].reshape(3, LMB) + 1, jnp.int32
    )
    tails = [ctx, 300, 1, 17, 255, 256, 257, 2047, 0]
    contexts = [ctx + i + 1 for i in range(Sq)] + tails
    sel = np.full((len(contexts), topk), -1, np.int32)
    for i, n in enumerate(contexts):
        if n:
            sel[i, : min(n, topk)] = rng.permutation(n)[:topk]
    sel_rows = jnp.asarray(
        [0] * Sq + [1 + i % 2 for i in range(len(tails))], jnp.int32
    )
    ql = rnd(len(contexts), 64, 640)
    sparse_args = (ql, lat, aux, ltables, sel_rows, jnp.asarray(sel))
    got = psp.sparse_latent_attention(*sparse_args, scale=1 / 16, n_chunk=Sq)
    if np.asarray(got, np.float32)[-1].any():
        raise SystemExit("sparse_latent_attention: an empty row is not zeros")
    compare(
        "sparse_latent_attention 24k keys, staged chunk + decode rows, tails",
        got, highest(att.sparse_latent_attention)(*sparse_args, 1 / 16),
    )
    gathered = psp.sparse_latent_attention(*sparse_args, scale=1 / 16)
    if not bool(jnp.all(got == gathered)):
        raise SystemExit("sparse_latent_attention: staged pages and gathered "
                         "tokens give different bits")
    stale = sum(
        jnp.any(psp.sparse_latent_attention(
            *sparse_args, scale=1 / 16, n_chunk=n) != got)
        for n in (Sq, 0) * 100
    )
    if int(stale):
        raise SystemExit(f"sparse_latent_attention: {int(stale)} of 200 "
                         "launches back to back differ from the first")
    print("KERNEL sparse_latent_attention: staged and gathered bitwise equal, "
          "200 launches back to back bitwise the first", flush=True)

    # latent attention over EVERY causal key (models/mla.py without an
    # indexer) at A.X-K1's and DeepSeek-V3's widths: 64 absorbed heads over
    # 512 + 64 lanes in rows of 128, 16-token pages, page-contiguous: 8
    # decode rows over 25k keys (tails of 1, 15 and 17 tokens past a page,
    # an empty row), a 512-query chunk at a 25k context's tail, and a mixed
    # step of a 320-query chunk + 8 decode rows in ONE launch; each against
    # the highest-precision twin, and its nanoseconds a (query, key) pair.
    # Each TWICE: every table a run of consecutive pages (a whole chunk is one
    # descriptor an array), then the same pages at shuffled places of a second
    # pool (two descriptors a page): bitwise the same answer. Beside each time
    # (20 dispatches back to back, the best of 3) the GB/s of what the launch
    # copies (1 536 B a token of every page a program walks: the latent's four
    # rows and the second array's first tile, PR 55) and of what it needs
    # (1 152 B a key of each row's context, once a row: benchmarks/costs_mla.py)
    from dynamo_tpu.ops import pallas_latent as plat

    def back_to_back(fn, *args):
        best = float("inf")
        for _ in range(3):
            jax.block_until_ready(fn(*args))
            t0 = time.perf_counter()
            jax.block_until_ready([fn(*args) for _ in range(20)])
            best = min(best, (time.perf_counter() - t0) / 20)
        return best

    DNB, DMB, dctx = 14401, 1600, 25000
    dlat, daux = rnd(DNB, BS, 4, 128), rnd(DNB, BS, 4, 128)
    run_tables = 1 + np.arange(9 * DMB).reshape(9, DMB)
    place = np.concatenate([[0], 1 + rng.permutation(DNB - 1)])
    back = jnp.asarray(np.argsort(place))
    layouts = [
        ("runs", dlat, daux, jnp.asarray(run_tables, jnp.int32)),
        ("shuffled", dlat[back], daux[back],
         jnp.asarray(place[run_tables], jnp.int32)),
    ]
    ref_latent = highest(att.paged_latent_attention)
    dscale = 0.13086

    def latent_case(name, n_chunk, q_len0, lens):
        first = 1 if n_chunk else 0
        n_one = len(lens) - first
        q_lens = jnp.asarray(
            [q_len0] * first + [int(n > 0) for n in lens[first:]], jnp.int32
        )
        pairs = sum(lens[first:]) + q_len0 * (lens[0] if first else 0) - (
            q_len0 * (q_len0 - 1) // 2
        )
        # a program walks its row's pages up to its last query's position: a
        # decode row its context, a tile of the chunk the context below it
        walked = [-(-n // BS) for n in lens[first:]] + [
            -(-(lens[0] - q_len0 + min(t0 + plat.Q_TILE, q_len0)) // BS)
            for t0 in range(0, q_len0, plat.Q_TILE)
        ]
        copied_gb = sum(walked) * BS * 1536 / 1e9
        needed_gb = sum(lens) * 1152 / 1e9
        lens = jnp.asarray(lens, jnp.int32)
        qd = rnd(n_chunk + n_one, 64, 640)
        outs = []
        for kind, lat_pool, aux_pool, tables in layouts:
            tb = tables[: len(lens)]
            whole, as_runs = plat.chunk_reads(lat_pool, tb, q_lens, lens)
            if int(as_runs) != (int(whole) if kind == "runs" else 0):
                raise SystemExit(f"{name}, {kind}: {int(as_runs)} of "
                                 f"{int(whole)} whole chunks are runs")
            run = lambda: plat.paged_latent_attention(  # noqa: E731
                qd, lat_pool, aux_pool, tb, q_lens, lens, scale=dscale,
                n_chunk=n_chunk)
            got = run()
            # the twin scores the whole packed buffer for every row: the
            # chunk and the one-token rows are asked of it apart
            parts = []
            if n_chunk:
                parts.append(ref_latent(
                    qd[:n_chunk], lat_pool, aux_pool, tb[:1],
                    jnp.zeros((1,), jnp.int32), q_lens[:1], lens[:1], dscale))
            if n_one:
                parts.append(ref_latent(
                    qd[n_chunk:], lat_pool, aux_pool, tb[first:],
                    jnp.arange(n_one), q_lens[first:], lens[first:], dscale))
            compare(f"{name}, {kind}", got, jnp.concatenate(parts, axis=0))
            empty = n_chunk + np.flatnonzero(np.asarray(lens[first:]) == 0)
            if np.asarray(got, np.float32)[empty].any():
                raise SystemExit(f"{name}, {kind}: an empty row is not zeros")
            took = back_to_back(run)
            print(f"KERNEL {name}, {kind} ({int(as_runs)} of {int(whole)} "
                  f"whole chunks one descriptor an array): {took * 1e3:.3f} "
                  f"ms a launch, {took * 1e9 / pairs:.2f} ns a (query, key) "
                  f"pair over {pairs} pairs; {copied_gb / took:.0f} GB/s "
                  f"copied (1 536 B a token), {needed_gb / took:.0f} GB/s "
                  f"needed (1 152 B a key)", flush=True)
            outs.append(got)
        if not bool(jnp.all(outs[0] == outs[1])):
            raise SystemExit(f"{name}: runs and shuffled pages give "
                             "different bits")
        print(f"KERNEL {name}: runs and shuffled pages bitwise equal",
              flush=True)

    latent_case(
        "paged_latent_attention 8 decode rows over 25k keys, tails, an "
        "empty row", 0, 0,
        [dctx, dctx + 1, dctx + 15, dctx + 17, 24576, 1, 0, dctx],
    )
    latent_case("paged_latent_attention a 512-query chunk at 25k keys",
                512, 512, [dctx])
    latent_case(
        "paged_latent_attention mixed: a 320-query chunk (301 real) + 8 "
        "decode rows, one launch", 320, 301,
        [dctx, dctx, 24577, 17, 0, dctx + 15, 1, 24591, dctx],
    )

    # the same launch UNDER A WINDOW (models/dots3_note.py's sliding layers)
    # at dots3-note-prev's widths and the agent cell's shapes: 64 absorbed
    # heads over 1024 + 64 lanes, the latent 8 rows of 128, the second array
    # its ONE tile (2 rows), a windowed group's pool of 3 121 pages and its
    # run of a row's table (162 pages: 513 keys, a 2 048-token chunk and a
    # page), window 513: 16 decode rows (at the window's edge, past it, at
    # the run's end, one key, an empty row), a lone 2 048-query chunk and a
    # mixed step of a 512-query chunk + 16 rows; each against the
    # highest-precision twin, over runs and over shuffled pages (bitwise the
    # same), with its ms a launch and its share of the roofline
    # (benchmarks/costs_dots3.py: a decode row min(len, 513) keys x 2 176 B;
    # a chunk its visible pairs x 64 heads x (1088 + 1024) x 2 FLOP)
    WNB, WMB, WIN = 3121, 162, 513
    wlat, waux = rnd(WNB, BS, 8, 128), rnd(WNB, BS, 2, 128)
    wrun = 1 + np.arange(17 * WMB).reshape(17, WMB)
    wplace = np.concatenate([[0], 1 + rng.permutation(WNB - 1)])
    wback = jnp.asarray(np.argsort(wplace))
    wlayouts = [
        ("runs", wlat, waux, jnp.asarray(wrun, jnp.int32)),
        ("shuffled", wlat[wback], waux[wback],
         jnp.asarray(wplace[wrun], jnp.int32)),
    ]
    wscale = 0.0625
    peak_flops, peak_bytes = 197e12, 819e9

    def windowed_case(name, n_chunk, q_len0, lens):
        first = 1 if n_chunk else 0
        n_one = len(lens) - first
        q_lens = jnp.asarray(
            [q_len0] * first + [int(n > 0) for n in lens[first:]], jnp.int32)
        row_keys = sum(min(n, WIN) for n in lens[first:])
        pairs = sum(min(lens[0] - q_len0 + i + 1, WIN) for i in range(q_len0)
                    ) if first else 0
        least = row_keys * 2176 / peak_bytes + pairs * 64 * 2112 * 2 / peak_flops
        lens_j = jnp.asarray(lens, jnp.int32)
        qd = rnd(n_chunk + n_one, 64, 1152)
        outs = []
        for kind, lat_pool, aux_pool, tables in wlayouts:
            tb = tables[: len(lens)]
            run = lambda: plat.paged_latent_attention(  # noqa: E731
                qd, lat_pool, aux_pool, tb, q_lens, lens_j, scale=wscale,
                n_chunk=n_chunk, window=WIN, name=plat.WINDOWED_KERNEL_NAME)
            got = run()
            parts = []
            if n_chunk:
                parts.append(ref_latent(
                    qd[:n_chunk], lat_pool, aux_pool, tb[:1],
                    jnp.zeros((1,), jnp.int32), q_lens[:1], lens_j[:1],
                    wscale, window=WIN))
            if n_one:
                parts.append(ref_latent(
                    qd[n_chunk:], lat_pool, aux_pool, tb[first:],
                    jnp.arange(n_one), q_lens[first:], lens_j[first:],
                    wscale, window=WIN))
            want = jnp.concatenate(parts, axis=0)
            # the chunk's real queries and the rows (padding is not compared)
            real = jnp.asarray([i for i in range(n_chunk + n_one)
                                if i >= n_chunk or i < q_len0])
            compare(f"{name}, {kind}", got[real], want[real])
            took = back_to_back(run)
            print(f"KERNEL {name}, {kind}: {took * 1e3:.3f} ms a launch, "
                  f"{100 * least / took:.1f}% of its roofline "
                  f"({row_keys} row keys, {pairs} chunk pairs)", flush=True)
            outs.append(got)
        if not bool(jnp.all(outs[0] == outs[1])):
            raise SystemExit(f"{name}: runs and shuffled pages give "
                             "different bits")

    windowed_case(
        "windowed_latent_attention 16 decode rows, window 513", 0, 0,
        [600, 513, 514, 512, 528, 2590, 1, 0, 1100, 1537, 2048, 529, 777,
         1025, 2561, 1024],
    )
    windowed_case("windowed_latent_attention a 2048-query chunk behind 513 "
                  "keys", 2048, 2048, [2561])
    windowed_case(
        "windowed_latent_attention mixed: a 512-query chunk (500 real) + 16 "
        "decode rows", 512, 500,
        [1030, 600, 513, 514, 512, 528, 2590, 1, 0, 1100, 1537, 2048, 529,
         777, 1025, 2561, 1024],
    )

    # the selection's read of the index keys (PR 48) at the long-document
    # cell's shapes, given pages (no scoring, no top-k): the launch
    # ``paged_index_keys`` against the twin's slice of row 1, bitwise, over
    # tables that are runs and over the same pages at shuffled places, 8
    # tables (decode rows) and 1 (a lone chunk), and tables of 1 590 pages
    # (24 whole chunks and a tail of 54); ms a launch and GB/s of the 768 B a
    # key it reads and writes, beside the twin's (``back_to_back``: the twin
    # re-tiles the whole second array in each)
    twin_keys = jax.jit(att.paged_index_keys, static_argnums=2)

    for kind, _, aux_pool, pool_tables in layouts:
        for n_tables, width in ((8, DMB), (1, DMB), (2, 1590)):
            tb = pool_tables[:n_tables, :width]
            whole, as_runs = psp.index_chunk_reads(tb)
            if int(as_runs) != (int(whole) if kind == "runs" else 0):
                raise SystemExit(f"index_keys, {kind}: {int(as_runs)} of "
                                 f"{int(whole)} whole chunks are runs")
            got = psp.paged_index_keys(aux_pool, tb, 128)
            want = twin_keys(aux_pool, tb, 128)
            if got.shape != want.shape or not bool(jnp.all(
                    jax.lax.bitcast_convert_type(got, jnp.uint16)
                    == jax.lax.bitcast_convert_type(want, jnp.uint16))):
                raise SystemExit(f"index_keys, {kind}, {n_tables} tables of "
                                 f"{width} pages: not the twin's bits")
            t_launch = back_to_back(psp.paged_index_keys, aux_pool, tb, 128)
            t_twin = back_to_back(twin_keys, aux_pool, tb, 128)
            gb = n_tables * width * BS * 768 / 1e9
            print(f"KERNEL index_keys {n_tables} tables x {width} pages, {kind} "
                  f"({int(as_runs)} of {int(whole)} whole chunks one "
                  f"descriptor): bitwise the twin; {t_launch * 1e3:.3f} ms a "
                  f"launch, {gb / t_launch:.0f} GB/s; the twin "
                  f"{t_twin * 1e3:.3f} ms", flush=True)

    # the selection behind those keys, beside the sort it replaced (PR 57)
    selection_case()

    # the decode launch reads a whole chunk of consecutive pages as ONE
    # descriptor an array (PR 50, ops/pallas_paged.PageReader) at the
    # contract cell's widths (128 q / 8 kv heads, 32 KiB pages, 32 a chunk:
    # 4 rows over 33.2k keys in tables of 2 112, one a tail of a page, one
    # empty, one of 64 whole chunks exactly) and the sparse-expert cell's (32
    # / 4, 16 KiB pages, 64 a chunk: 16 rows around 6.5k keys in tables of
    # 448): over tables that are runs, then over the same pages at shuffled
    # places of a second pool (every whole chunk page by page, one wait an
    # array): each against the twin, both bitwise the same; then 40 launches
    # back to back, runs and shuffled in turn (a miscounted semaphore shows
    # on the chip only, as a hang or a stale row); and ms a launch, chained
    # in one program (a launch alone is mostly its dispatch)
    from dynamo_tpu.ops import pallas_attention as pa
    from dynamo_tpu.ops import pallas_paged as ppaged

    ref_decode = highest(att.paged_decode_attention)

    @jax.jit
    def chained_decode(q, kp, vp, tb, lens):
        def body(_, q):
            out = pa.paged_decode_attention(q, kp, vp, tb, lens)
            return q.at[:, :, :1].add((out[:, :, :1] * 0).astype(q.dtype))
        return jax.lax.fori_loop(0, 20, body, q)

    def decode_runs_case(name, h, kvh, mb, lens):
        rows = len(lens)
        nb = rows * mb + 1
        kp, vp = rnd(nb, BS, kvh, D), rnd(nb, BS, kvh, D)
        run_tb = 1 + np.arange(rows * mb).reshape(rows, mb)
        place = np.concatenate([[0], 1 + rng.permutation(nb - 1)])
        back = jnp.asarray(np.argsort(place))
        qd = rnd(rows, h, D)
        cp = ppaged.chunk_pages(BS, kvh, D, bf, mb)
        whole = sum(-(-n // BS) // cp for n in lens)
        lens = jnp.asarray(lens, jnp.int32)
        outs, launches = [], []
        for kind, kpool, vpool, tb in (
            ("runs", kp, vp, run_tb),
            ("shuffled", kp[back], vp[back], place[run_tb]),
        ):
            tb = jnp.asarray(tb, jnp.int32)
            as_runs = int(jnp.sum(ppaged.chunk_runs(tb, cp)))
            if as_runs != (rows * (mb // cp) if kind == "runs" else 0):
                raise SystemExit(f"{name}, {kind}: {as_runs} chunks are runs")
            args = (qd, kpool, vpool, tb, lens)
            got = pa.paged_decode_attention(*args)
            live = np.asarray(lens) > 0
            if np.asarray(got, np.float32)[~live].any():
                raise SystemExit(f"{name}, {kind}: an empty row is not zeros")
            compare(f"{name}, {kind}", np.asarray(got, np.float32)[live],
                    np.asarray(ref_decode(*args), np.float32)[live])
            jax.block_until_ready(chained_decode(*args))
            t0 = time.perf_counter()
            jax.block_until_ready(chained_decode(*args))
            took = (time.perf_counter() - t0) / 20
            print(f"KERNEL {name}, {kind} ({whole if kind == 'runs' else 0} "
                  f"of {whole} whole chunks one descriptor an array): "
                  f"{took * 1e3:.3f} ms a launch of 20 in one program",
                  flush=True)
            outs.append(got)
            launches.append(args)
        if not bool(jnp.all(outs[0] == outs[1])):
            raise SystemExit(f"{name}: runs and shuffled pages give "
                             "different bits")
        stale = sum(
            jnp.any(pa.paged_decode_attention(*launches[i % 2]) != outs[0])
            for i in range(40)
        )
        if int(stale):
            raise SystemExit(f"{name}: {int(stale)} of 40 launches back to "
                             "back differ from the first")
        print(f"KERNEL {name}: runs and shuffled pages bitwise equal, 40 "
              "launches back to back bitwise the first", flush=True)

    decode_runs_case(
        "paged_decode_attention 128 q / 8 kv heads, 4 rows over 33.2k keys",
        128, 8, 2112, [33200, 33281, 0, 32768])
    decode_runs_case(
        "paged_decode_attention 32 q / 4 kv heads, 16 rows over 6.5k keys",
        32, 4, 448, [6500 - 37 * i for i in range(14)] + [1, 7168])
    # the looped decoder's cell (PR 60): ONE query head a kv head, which no
    # other cell runs, 64 KiB pages, 8 rows at about 600 keys
    decode_runs_case(
        "paged_decode_attention 16 q / 16 kv heads (1 a group), 8 rows at 600 keys",
        16, 16, 42, [600, 599, 0, 656, 257, 512, 1, 672])

    # the state-space mixer's decode recurrence (PR 39) at Falcon-H1-34B's
    # widths, 128 rows of which some are dead: the state in place, live rows
    # only; and the decode question at the family's 5 query heads a kv head
    from dynamo_tpu.ops import pallas_ssm

    SR, SH, SG, SN, SP = 128, 32, 2, 256, 128
    live = jnp.asarray(np.arange(SR) % 5 != 3)
    S0 = rnd(SR, SH, SN, SP).astype(jnp.float32)
    sx, sB, sC = rnd(SR, SH, SP), rnd(SR, SG, SN), rnd(SR, SG, SN)
    sdt = jax.nn.softplus(rnd(SR, SH).astype(jnp.float32) - 2.0)
    sA = -jnp.exp(rnd(SH).astype(jnp.float32))
    sD = jnp.ones((SH,), jnp.float32)
    want_S, want_y = jax.jit(pallas_ssm.ssm_state_update_reference)(
        S0, sx, sB, sC, sdt, sA, sD, live)
    got_S, got_y = pallas_ssm.ssm_state_update(S0 + 0, sx, sB, sC, sdt, sA, sD, live)
    compare("ssm_state_update 128 rows, 26 dead: the state", got_S, want_S)
    compare("ssm_state_update 128 rows, 26 dead: y", got_y, want_y)
    dead = ~np.asarray(live)
    if not np.array_equal(np.asarray(got_S)[dead], np.asarray(S0)[dead]):
        raise SystemExit("ssm_state_update: a dead row's state moved")
    g5_lens = np.asarray([1, 16, 17, 333, 1024, 1311, 0, 700], np.int32)
    q5 = rnd(8, 20, D)
    k5, v5 = rnd(NB, BS, 4, D), rnd(NB, BS, 4, D)
    g5_args = (q5, k5, v5, tables[:8], jnp.asarray(g5_lens))
    got = np.asarray(kernels.decode(*g5_args), np.float32)
    # an empty row is zeros from the kernel and a mean over masked keys
    # from the twin: held apart, as in the 8k case above
    if got[g5_lens == 0].any():
        raise SystemExit("decode question, 5 a group: an empty row is not zeros")
    compare(
        "decode question, 20 q / 4 kv heads (5 a group), one empty row",
        got[g5_lens > 0],
        np.asarray(highest(twins.decode)(*g5_args), np.float32)[g5_lens > 0],
    )

    # a KDA layer's decode recurrence (PR 41: the gated delta rule with a
    # decay a channel) at Solar-Open2-250B's widths, 128 rows of which some
    # are dead: the matrix state in place, live rows only
    from dynamo_tpu.ops import pallas_kda

    KH, KD = 64, 128
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    K0 = rnd(SR, KH, KD, KD).astype(jnp.float32)
    kq = unit(rnd(SR, KH, KD).astype(jnp.float32)) * KD ** -0.5
    kk = unit(rnd(SR, KH, KD).astype(jnp.float32))
    kv = rnd(SR, KH, KD)
    # decays from a channel that forgets inside a token to one that keeps a thousand
    kalpha = jnp.exp(-jnp.exp(2.5 * rnd(SR, KH, KD).astype(jnp.float32) - 3.0))
    kbeta = 2.0 * jax.nn.sigmoid(rnd(SR, KH).astype(jnp.float32))
    want_S, want_y = jax.jit(pallas_kda.kda_state_update_reference)(
        K0, kq, kk, kv, kalpha, kbeta, live)
    got_S, got_y = pallas_kda.kda_state_update(K0 + 0, kq, kk, kv, kalpha, kbeta, live)
    compare("kda_state_update 128 rows, 26 dead: the state", got_S, want_S)
    compare("kda_state_update 128 rows, 26 dead: y", got_y, want_y)
    if not np.array_equal(np.asarray(got_S)[dead], np.asarray(K0)[dead]):
        raise SystemExit("kda_state_update: a dead row's state moved")
    # the state is float32 IN FLIGHT too, which the benchmark's comparison
    # cannot tell (it reads what is kept: PERF.md section 7 (17)): a step
    # rounded to bf16 anywhere would be 2^-9 of the state away, and the loose
    # tolerance above would pass it. The kernel and a run's chunked scan are
    # held to the float32 recurrence at 2^-14
    def float32_close(name, got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        err = float(np.max(np.abs(got - want) / np.maximum(1.0, np.abs(want))))
        print(f"KERNEL {name}: max relative |got - float32 recurrence| = {err:.3e} "
              f"(2^-14 = 6.1e-05 allowed; a bf16 step reads 2e-03)", flush=True)
        if not err <= 2.0 ** -14:
            raise SystemExit(f"{name}: not a float32 computation ({err:.3e})")

    float32_close("kda_state_update, the state in flight", got_S, want_S)
    del K0, got_S, want_S
    KT, KHs = 96, 8  # three chunks of the scan, 8 heads of one row's run
    # the log of the decay drawn itself, as the model makes it: finite (the
    # log of an alpha that underflowed is not), to 30 a token
    kg = -jnp.minimum(jnp.exp(2.5 * rnd(KT, KHs, KD).astype(jnp.float32) - 3.0), 30.0)
    srun = (kq[:KT, :KHs], kk[:KT, :KHs], kv[:KT, :KHs], kg, kbeta[:KT, :KHs])
    S_run = rnd(KHs, KD, KD).astype(jnp.float32)

    @jax.jit
    def token_by_token(S, q, k, v, g, beta):
        def token(s, inp):
            q_t, k_t, v_t, g_t, b_t = (x[None] for x in inp)
            s, y = pallas_kda.kda_state_update_reference(
                s, q_t, k_t, v_t, jnp.exp(g_t), b_t, jnp.ones((1,), bool))
            return s, y[0]
        s, ys = jax.lax.scan(token, S[None], (q, k, v, g, beta))
        return ys, s[0]

    got_y, got_S = jax.jit(pallas_kda.kda_scan)(S_run, *srun)
    want_y, want_S = token_by_token(S_run, *srun)
    float32_close("kda_scan 96 tokens x 8 heads, the state after the run", got_S, want_S)
    float32_close("kda_scan 96 tokens x 8 heads, y", got_y, want_y)

    # EVA's decode attention (PR 46) at EvaByte's widths: 24 rows of 32
    # heads x 128 (multi-head) over a ring of 128 pages and up to 4 summary
    # blocks of 8 pages each in one pool; rows at a window's first and last
    # position, in window 0 (no summary read), one empty
    del k_cache, v_cache
    from dynamo_tpu.ops import pallas_eva

    EB, EH, RP, NW = 24, 32, 128, 5
    e_base = 1 + EB * RP
    e_pool = e_base + (1 + EB * NW) * 8
    ek, ev = rnd(e_pool, BS, EH, D), rnd(e_pool, BS, EH, D)
    e_tables = np.zeros((EB, RP + NW), np.int32)
    e_tables[:, :RP] = (rng.permutation(EB * RP) + 1).reshape(EB, RP)
    e_tables[:, RP:] = (rng.permutation(EB * NW) + 1).reshape(EB, NW)
    e_lens = rng.integers(1, 5 * 2048, EB).astype(np.int32)
    e_lens[:6] = [0, 1, 2048, 2049, 4 * 2048, 10239]
    e_args = (rnd(EB, EH, D), ek, ev, jnp.asarray(e_tables), jnp.asarray(e_lens))
    e_query = att.EvaQuery(None, None, 2048, 16)  # the geometry: no vector is read
    got = np.asarray(
        pallas_eva.eva_decode_attention(*e_args, e_query, e_base), np.float32)
    want = np.asarray(highest(lambda *a: att.eva_paged_decode_attention(
        *a, e_query, e_base))(*e_args), np.float32)
    if got[e_lens == 0].any():
        raise SystemExit("eva_decode_attention: an empty row is not zeros")
    compare("eva_decode_attention 24 rows x 32 heads, ring 2048, 4 summary blocks",
            got[e_lens > 0], want[e_lens > 0])
    del ek, ev

    # pages by layer kind (PR 49) at Command A+'s widths: 128 query heads over
    # 8 kv heads x 128 (SIXTEEN a kv head), 24 rows. The full layer's decode
    # launch over tables of 2 112 pages (33k keys), the sliding layers' over
    # their group's SHIFTED run of a row's table (ops/paged_attention
    # GroupView: 290 pages from the oldest page a row still holds, lengths
    # counted from there) under the window of 4 096; a row at its first
    # token, one whose window has not filled, one at a page's edge, an empty
    # one. Pages are drawn from one pool of 8 192 (rows may share pages:
    # nothing is written). The twins gather a row's whole context in
    # float32: the first 8 rows of each launch are held to them
    from dynamo_tpu.ops.paged_attention import GroupView

    CB, CH, CMB, CWP, CW = 24, 128, 2112, 290, 4096
    cpool = 8192
    ck, cv = rnd(cpool, BS, KVH, D), rnd(cpool, BS, KVH, D)
    c_lens = rng.integers(CW + 600, CMB * BS, CB).astype(np.int32)
    c_lens[:8] = [0, 1, 33792, 4096, 4097, 3000, 33000, 8192]
    c_tables = np.zeros((CB, CMB + CWP + 1), np.int32)
    c_tables[:, :CMB] = rng.integers(1, cpool, (CB, CMB))
    view = GroupView(CMB, CWP, BS)
    first = np.maximum(c_lens - 1 - CW + 1, 0) // BS           # WindowGroup.first_needed
    c_tables[:, CMB:CMB + CWP] = rng.integers(1, cpool, (CB, CWP))
    c_tables[:, CMB + CWP] = first
    cq = rnd(CB, CH, D)
    full_args = (cq, ck, cv, jnp.asarray(c_tables[:, :CMB]), jnp.asarray(c_lens))
    got = np.asarray(kernels.decode(*full_args), np.float32)
    if got[0].any():
        raise SystemExit("decode question, 16 heads a kv head: an empty row is not zeros")
    first8 = lambda q, kc, vc, *rows: (q[:8], kc, vc, *(r[:8] for r in rows))  # noqa: E731
    compare("decode question 24 rows x 128 heads (16 a kv head), 33k keys",
            got[1:8], highest(twins.decode)(*first8(*full_args))[1:8])
    run_tables, run_lens, _ = view.rows(
        jnp.asarray(c_tables), jnp.asarray(c_lens), jnp.asarray(c_lens > 0))
    if not np.array_equal(np.asarray(run_lens), np.where(c_lens > 0, c_lens - first * BS, 0)):
        raise SystemExit("GroupView.rows: lengths are not counted from the run's first page")
    win_args = (cq, ck, cv, run_tables, run_lens)
    got = np.asarray(kernels.decode(*win_args, window=CW), np.float32)
    if got[0].any():
        raise SystemExit("windowed decode over a shifted table: an empty row is not zeros")
    compare("windowed decode 24 rows x 128 heads over a shifted table, window 4096",
            got[1:8],
            highest(lambda *a: twins.decode(*a, window=CW))(
                *first8(*win_args))[1:8])
    del ck, cv

    # ...and its expert multiplication: 16 held experts of [4 096, 4 096]
    # (100.7 MB each over three matrices, the widest yet); 192 sorted rows is
    # a decode step's 24 rows x top 8 were they all held, one expert empty
    c_sizes = jnp.asarray([30, 0, 7, 20, 1, 40, 2, 12, 16, 9, 11, 5, 13, 8, 15, 3], jnp.int32)
    c_rows = rnd(192, 4096)
    cg, cu = (rnd(16, 4096, 4096) * 0.0156 for _ in range(2))
    cd = rnd(16, 4096, 4096) * 0.0156
    # the twin at the default precision: at these sizes ``ragged_dot`` under
    # "highest" is itself a Mosaic kernel, which refuses bf16 rows ("Bad lhs
    # type", my chip run, PR 49)
    wide_twin = jax.jit(pmoe.grouped_matmul_reference)
    act = pmoe.grouped_matmul(c_rows, (cg, cu), c_sizes)
    compare("moe_grouped_matmul gate, up and SwiGLU, 16 experts of [4096, 4096]",
            act, wide_twin(c_rows, (cg, cu), c_sizes))
    compare("moe_grouped_matmul down, 16 experts of [4096, 4096]",
            pmoe.grouped_matmul(act, (cd,), c_sizes),
            wide_twin(act, (cd,), c_sizes))
    del cg, cu, cd
    k_cache, v_cache = rnd(NB, BS, KVH, D), rnd(NB, BS, KVH, D)

    # block moves are copies: exact
    ids = jnp.asarray(rng.permutation(NB)[:32], jnp.int32)
    got = bc.gather_blocks(k_cache, ids)
    compare("gather_blocks", got, bc.gather_blocks_ref(k_cache, ids))
    pages = rnd(32, BS, KVH, D)
    want = bc.scatter_blocks_ref(v_cache, ids, pages)
    compare("scatter_blocks", bc.scatter_blocks(v_cache + 0, ids, pages), want)
    src, dst = ids[:16], ids[16:]
    want = bc.copy_blocks_ref(k_cache, src, dst)
    compare("copy_blocks", bc.copy_blocks(k_cache + 0, src, dst), want)

    # does block_until_ready wait for the device? Time a chain of matmuls
    # to its block_until_ready, then fetch: a wait that waited leaves
    # nothing for the fetch to wait for
    x = rnd(4096, 4096)

    @jax.jit
    def chain(a):
        y = jax.lax.fori_loop(0, 64, lambda _, y: (y @ a) * 0.01, a)
        return y, y[0, :8]  # a few bytes to fetch, from the same program

    jax.block_until_ready(chain(x))  # compile
    t0 = time.perf_counter()
    y, probe = chain(x)
    t_dispatch = time.perf_counter() - t0
    y.block_until_ready()
    t_block = time.perf_counter() - t0
    np.asarray(probe)
    t_fetch = time.perf_counter() - t0 - t_block
    waits = t_block > 4 * max(t_dispatch, 1e-4) and t_fetch < 0.25 * t_block
    print(f"KERNEL block_until_ready waits for the device: "
          f"{json.dumps(bool(waits))} (dispatch returned after "
          f"{t_dispatch * 1e3:.2f} ms, block_until_ready after "
          f"{t_block * 1e3:.2f} ms, the fetch took {t_fetch * 1e3:.2f} ms "
          f"more)", flush=True)
    print("KERNELS_OK " + json.dumps({"device": dev, "worst": worst}),
          flush=True)


# ---------------------------------------------------------------- four chips
TP_PROMPTS = [token_prompt(40, 31), token_prompt(24, 32), token_prompt(56, 33)]


def tp_session(tag: str, worker_args: List[str], ready_timeout_s: float,
               want_devices: int) -> Dict[str, Any]:
    """One engine worker through the normal entry points; the same three
    greedy prompts; what it answered and what its devices hold."""
    with Stack(tag, worker_args, ready_timeout_s) as stack:
        say(f"[{tag}] cold start: {stack.cold_start_s:.1f} s")
        meta = report_worker(stack)
        check(meta["engine"]["device"]["count"] == want_devices,
              f"{tag}: worker sees {meta['engine']['device']['count']} "
              f"devices, want {want_devices}")
        answers = []
        for i, prompt in enumerate(TP_PROMPTS):
            ans = ask(stack.base, "/v1/completions", {
                "prompt": prompt, "temperature": 0.0, "ignore_eos": True,
                "max_tokens": 32, "logprobs": 5,
            })
            check_answer(ans, f"{tag} prompt {i}", max_tokens=32,
                         prompt_tokens=len(prompt))
            check(len(ans.tokens) == 32,
                  f"{tag} prompt {i}: {len(ans.tokens)} logprob entries")
            say(f"[{tag}] prompt {i}: {ans.seconds:.1f} s, first tokens "
                f"{ans.tokens[:8]}")
            answers.append(ans)
        meta = stack.metadata()
        mem = meta["engine"]["device_bytes_in_use"]
        say(f"[{tag}] bytes in use per mesh device: {mem}")
        stack.check_processes()
        stack.stop()
        return {"answers": answers, "mem": mem,
                "device": meta["engine"]["device"]}


def four_chip_phase() -> Dict[str, Any]:
    qwen = ["--preset", "qwen3-0.6b", "--max-context", "1024"]
    one = tp_session("qwen-tp1", qwen + ["--tp", "1"], 600, 4)
    four = tp_session("qwen-tp4", qwen + ["--tp", "4"], 600, 4)
    llama = tp_session(
        "llama3-8b-tp4",
        ["--preset", "llama3-8b", "--tp", "4", "--max-context", "1024"],
        900, 4,
    )
    # every session has printed what it saw; now hold them to the rules
    faults = []
    for i, (a, b) in enumerate(zip(one["answers"], four["answers"])):
        agree = next(
            (j for j, (x, y) in enumerate(zip(a.tokens, b.tokens)) if x != y),
            len(a.tokens),
        )
        drift = max(
            [abs(x - y)
             for x, y in zip(a.logprobs[:agree], b.logprobs[:agree])],
            default=0.0,
        )
        line = (f"[tp] prompt {i}: tp=4 and tp=1 agree on the first {agree} "
                f"of {len(a.tokens)} greedy tokens, max |logprob difference|"
                f" over them {drift:.4f} (bound {TP_LOGPROB_TOL})")
        if drift > TP_LOGPROB_TOL:
            faults.append(f"prompt {i}: logprobs drift {drift:.3f}")
        if agree < len(a.tokens):
            # where they part: how much worse does each side think the
            # other's token is than its own? (inf: not in its top 5)
            gaps = [
                mine.logprobs[agree]
                - mine.top[agree].get(other.tokens[agree], float("-inf"))
                for mine, other in ((a, b), (b, a))
            ]
            line += (f"; they part at a tie: tp=1 ranks tp=4's token "
                     f"{gaps[0]:.4f} below its own, tp=4 ranks tp=1's "
                     f"{gaps[1]:.4f} below its own")
            if max(gaps) > TP_LOGPROB_TOL:
                faults.append(f"prompt {i}: parted at token {agree} without "
                              f"a near-tie (gaps {gaps})")
        say(line)
    mem = llama["mem"]
    check(len(mem) == 4 and all(isinstance(m, int) and m > 0 for m in mem),
          f"no per-device memory from the worker: {mem}")
    ratio = max(mem) / min(mem)
    say(f"[tp] llama3-8b tp=4 bytes in use per device: {mem} "
        f"(max/min {ratio:.3f}, bound {TP_BALANCE_RATIO})")
    check(not faults, "tp=4 against tp=1: " + "; ".join(faults))
    check(ratio <= TP_BALANCE_RATIO,
          f"llama3-8b shards unbalanced across devices: {mem}")
    # a whole bf16 copy is ~16 GB: a device holding its quarter (and its
    # quarter of the KV pages) stays far under half of that
    check(max(mem) < 8 * 2**30,
          f"a device holds more than a shard of llama3-8b: {mem}")
    return llama["device"]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the tp=4 phase and what it is "
                         "compared with (needs four chips)")
    args = ap.parse_args()
    say(f"[smoke] compile cache: {compile_cache_dir()}")
    if args.chips == 4:
        device = four_chip_phase()
    else:
        device = serving_phase()
        kernels = kernel_phase()
        check(kernels["device"] == device,
              f"kernel child saw {kernels['device']}, worker {device}")
    check(device["platform"] == PLATFORM, f"platform {device['platform']!r}")
    if args.chips == 4:
        check(device["count"] == 4, f"{device['count']} devices, not 4")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
