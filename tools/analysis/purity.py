"""JIT purity + engine-hot-path host-sync passes.

A ``.item()`` / ``np.asarray`` / ``device_get`` / ``block_until_ready`` on a
traced value forces a device round-trip: inside a jit-decorated function it
is at best a silent tracer materialization, and on the engine step path it
stalls the dispatch pipeline for a full device round trip —
the exact failure mode the ROADMAP item-1 kernel work must not reintroduce.

Two scopes, two rule ids:

- JIT-PURITY: inside functions decorated with ``jax.jit`` (any spelling:
  ``@jax.jit``, ``@jit``, ``@partial(jax.jit, ...)``), flag host-sync calls
  AND Python-side mutation (stores to ``self.*``/globals, mutating method
  calls on them) — side effects inside a traced function run once at trace
  time and never again, a classic silent-wrong-result bug.
- HOST-SYNC: host-sync calls in the engine step-loop scope —
  ``engine/engine.py`` module-level functions and the ``_loop`` method.
  Deliberate fetches (the RTT probe) carry ``# dtpu: ignore[HOST-SYNC]``
  with their rationale. Passing ``np.asarray`` as a callable (e.g. to the
  fetch executor) is NOT flagged — only direct calls sync the loop.
"""

from __future__ import annotations

import ast
from typing import Iterator, List, Optional, Tuple

from .core import MUTATING_METHODS, Context, Finding, register

_HOST_SYNC_METHODS = {
    "item": ".item() forces a device->host sync",
    "tolist": ".tolist() forces a device->host sync",
    "block_until_ready": ".block_until_ready() stalls until the device drains",
}

_HOST_SYNC_MODULE_CALLS = {
    ("np", "asarray"): "np.asarray() on a device array is a blocking fetch",
    ("np", "array"): "np.array() on a device array is a blocking fetch",
    ("numpy", "asarray"): "np.asarray() on a device array is a blocking fetch",
    ("numpy", "array"): "np.array() on a device array is a blocking fetch",
    ("jax", "device_get"): "jax.device_get() is a blocking fetch",
}

def _host_sync_in(node: ast.AST) -> Iterator[Tuple[int, str]]:
    """Direct host-sync CALLS under ``node`` (callable references pass)."""
    for n in ast.walk(node):
        if not isinstance(n, ast.Call):
            continue
        f = n.func
        if isinstance(f, ast.Attribute):
            if f.attr in _HOST_SYNC_METHODS:
                yield n.lineno, _HOST_SYNC_METHODS[f.attr]
            elif isinstance(f.value, ast.Name):
                key = (f.value.id, f.attr)
                if key in _HOST_SYNC_MODULE_CALLS:
                    yield n.lineno, _HOST_SYNC_MODULE_CALLS[key]
        elif isinstance(f, ast.Name) and f.id == "device_get":
            yield n.lineno, "device_get() is a blocking fetch"


def _is_jit_decorator(dec: ast.AST) -> bool:
    """@jax.jit / @jit / @partial(jax.jit, ...) / @functools.partial(jax.jit)."""
    if isinstance(dec, ast.Name) and dec.id == "jit":
        return True
    if isinstance(dec, ast.Attribute) and dec.attr == "jit":
        return True
    if isinstance(dec, ast.Call):
        f = dec.func
        is_partial = (isinstance(f, ast.Name) and f.id == "partial") or (
            isinstance(f, ast.Attribute) and f.attr == "partial"
        )
        if is_partial and dec.args:
            return _is_jit_decorator(dec.args[0])
        return _is_jit_decorator(f)
    return False


def jit_impurities(path: str, tree: ast.AST) -> List[Tuple[int, str]]:
    out: List[Tuple[int, str]] = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not any(_is_jit_decorator(d) for d in fn.decorator_list):
            continue
        for line, msg in _host_sync_in(fn):
            out.append((line, f"{msg} inside a jit-decorated function "
                              f"({fn.name}) — hoist it out of the traced scope"))
        # Python-side mutation: runs once at trace time, then never again
        for n in ast.walk(fn):
            tgt: Optional[str] = None
            if isinstance(n, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                targets = n.targets if isinstance(n, ast.Assign) else [n.target]
                for t in targets:
                    base = t
                    while isinstance(base, ast.Subscript):
                        base = base.value
                    if (
                        isinstance(base, ast.Attribute)
                        and isinstance(base.value, ast.Name)
                        and base.value.id == "self"
                    ):
                        tgt = f"self.{base.attr}"
            elif (
                isinstance(n, ast.Call)
                and isinstance(n.func, ast.Attribute)
                and n.func.attr in MUTATING_METHODS
                and isinstance(n.func.value, ast.Attribute)
                and isinstance(n.func.value.value, ast.Name)
                and n.func.value.value.id == "self"
            ):
                tgt = f"self.{n.func.value.attr}.{n.func.attr}()"
            if tgt is not None:
                out.append((
                    n.lineno,
                    f"Python-side mutation of {tgt} inside jit-decorated "
                    f"{fn.name}() — traced functions run their Python body "
                    f"once at trace time; this side effect silently stops "
                    f"firing after the first call",
                ))
    return out


@register("jit-purity", "host syncs / Python side effects inside jit functions")
def _jit_purity_pass(ctx: Context) -> Iterator[Finding]:
    for m in ctx.modules:
        # substring (not startswith): out-of-repo paths stay absolute after
        # normalization, and fixtures live under tmp/dynamo_tpu/...
        if "dynamo_tpu/" not in m.path:
            continue
        for line, msg in jit_impurities(m.path, m.tree):
            yield Finding("JIT-PURITY", m.path, line, msg)


_jit_purity_pass.RULES = ("JIT-PURITY",)


# -- HOST-SYNC (engine step-loop scope) --------------------------------------

def engine_host_syncs(path: str, tree: ast.AST) -> List[Tuple[int, str]]:
    """Host-sync calls in engine/engine.py's module-level functions and the
    ``_loop`` step method. The offload/onboard/transfer machinery (class
    methods running on executors) is out of scope by design — host copies
    are its job."""
    out: List[Tuple[int, str]] = []
    scopes: List[ast.AST] = [
        n for n in tree.body if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    ]
    for cls in tree.body:
        if isinstance(cls, ast.ClassDef):
            scopes.extend(
                n for n in cls.body
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                and n.name == "_loop"
            )
    for fn in scopes:
        for line, msg in _host_sync_in(fn):
            out.append((
                line,
                f"{msg} on the engine step path ({fn.name}) — it stalls "
                f"dispatch for a full device RTT; move it behind the fetch "
                f"executor or mark the deliberate fetch with an inline ignore",
            ))
    return out


@register("host-sync", "blocking device fetches on the engine step path")
def _host_sync_pass(ctx: Context) -> Iterator[Finding]:
    for m in ctx.modules:
        if not m.path.endswith("engine/engine.py"):
            continue
        for line, msg in engine_host_syncs(m.path, m.tree):
            yield Finding("HOST-SYNC", m.path, line, msg)


_host_sync_pass.RULES = ("HOST-SYNC",)
