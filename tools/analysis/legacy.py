"""Passes ported from tools/lint.py — same detectors, framework findings.

The per-pass helper functions keep their original ``(path, tree) ->
tuples`` signatures (tests and the lint.py shim import them directly); each
``register``ed wrapper adapts them onto the shared single-parse Context and
applies the pass's path scoping.
"""

from __future__ import annotations

import ast
import builtins
import os
import symtable
from typing import Iterator, List, Tuple

from .core import Context, Finding, register, spawn_call_name

BUILTINS = set(dir(builtins)) | {
    "__file__", "__name__", "__doc__", "__package__", "__spec__",
    "__loader__", "__builtins__", "__debug__", "__path__",
}


# -- UNDEFINED ---------------------------------------------------------------

def _collect_scopes(table, out):
    out.append(table)
    for child in table.get_children():
        _collect_scopes(child, out)


def undefined_globals(path: str, src: str) -> List[Tuple[str, str]]:
    """Names that resolve to module globals but are never bound there."""
    table = symtable.symtable(src, path, "exec")
    scopes: list = []
    _collect_scopes(table, scopes)
    module_scope = scopes[0]
    defined = {
        s.get_name()
        for s in module_scope.get_symbols()
        if s.is_assigned() or s.is_imported()
    }
    findings = []
    seen = set()
    for scope in scopes:
        for sym in scope.get_symbols():
            name = sym.get_name()
            if not sym.is_referenced() or name in BUILTINS or name in seen:
                continue
            if scope is module_scope:
                is_free_global = sym.is_global() or (
                    not sym.is_assigned() and not sym.is_imported()
                    and not sym.is_parameter()
                )
            else:
                is_free_global = sym.is_global()
            if is_free_global and name not in defined:
                seen.add(name)
                findings.append((path, name))
    return findings


@register("undefined", "names that resolve to module globals never bound there")
def _undefined_pass(ctx: Context) -> Iterator[Finding]:
    for m in ctx.modules:
        for _p, name in undefined_globals(m.path, m.src):
            yield Finding(
                "UNDEFINED", m.path, 0,
                f"{name} is read as a module global but never assigned, "
                f"imported, or a builtin",
            )


_undefined_pass.RULES = ("UNDEFINED",)


# -- UNUSED-IMPORT -----------------------------------------------------------

def _ident_tokens(text: str):
    tok = ""
    for ch in text:
        if ch.isidentifier() or (tok and ch.isalnum()):
            tok += ch
        else:
            if tok:
                yield tok
            tok = ""
    if tok:
        yield tok


def unused_imports(path: str, tree: ast.AST, src: str):
    """Module-level imports never referenced anywhere in the file."""
    imported = {}  # name -> lineno
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                name = (a.asname or a.name).split(".")[0]
                imported[name] = node.lineno
        elif isinstance(node, ast.ImportFrom):
            if node.module == "__future__":
                continue
            for a in node.names:
                if a.name == "*":
                    continue
                imported[a.asname or a.name] = node.lineno
    used = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            used.add(node.id)
    # names referenced only inside string annotations (from __future__)
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            for tok in _ident_tokens(node.value):
                used.add(tok)
    return [
        (path, name, lineno)
        for name, lineno in imported.items()
        if name not in used and not name.startswith("_")
    ]


@register("unused-import", "module-level imports referenced nowhere")
def _unused_import_pass(ctx: Context) -> Iterator[Finding]:
    for m in ctx.modules:
        if os.path.basename(m.path) == "__init__.py":
            continue  # re-export shims
        for _p, name, lineno in unused_imports(m.path, m.tree, m.src):
            yield Finding("UNUSED-IMPORT", m.path, lineno, f"{name} imported but unused")


_unused_import_pass.RULES = ("UNUSED-IMPORT",)


# -- ARITY -------------------------------------------------------------------

def call_arity(path: str, tree: ast.AST):
    """Wrong-arity calls to same-module top-level functions — the cheap,
    high-precision slice of what mypy would catch. Conservative by
    construction: only checks calls to undecorated module-level ``def``s
    whose name is never rebound, and skips unpacked calls."""
    funcs = {}
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node.decorator_list:
                continue
            funcs[node.name] = (node.args, node.lineno)

    # a name bound anywhere beyond its single top-level def may not be that
    # function at the call site — drop it
    bound_counts: dict = {}

    def bind(name):
        bound_counts[name] = bound_counts.get(name, 0) + 1

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            bind(node.name)
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                a = node.args
                for arg in (
                    a.posonlyargs + a.args + a.kwonlyargs
                    + ([a.vararg] if a.vararg else [])
                    + ([a.kwarg] if a.kwarg else [])
                ):
                    bind(arg.arg)
        elif isinstance(node, ast.Lambda):
            a = node.args
            for arg in a.posonlyargs + a.args + a.kwonlyargs:
                bind(arg.arg)
        elif isinstance(node, ast.Name) and isinstance(node.ctx, (ast.Store, ast.Del)):
            bind(node.id)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            for alias in getattr(node, "names", []):
                if alias.name != "*":
                    bind((alias.asname or alias.name).split(".")[0])
    checkable = {
        name: spec for name, spec in funcs.items() if bound_counts.get(name) == 1
    }

    findings = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)):
            continue
        entry = checkable.get(node.func.id)
        if entry is None:
            continue
        a, _def_line = entry
        if any(isinstance(x, ast.Starred) for x in node.args):
            continue
        if any(kw.arg is None for kw in node.keywords):
            continue
        pos_params = [p.arg for p in a.posonlyargs + a.args]
        n_defaults = len(a.defaults)
        required_pos = pos_params[: len(pos_params) - n_defaults]
        kwonly = {p.arg for p in a.kwonlyargs}
        kwonly_required = {
            p.arg for p, d in zip(a.kwonlyargs, a.kw_defaults) if d is None
        }
        kw_names = {kw.arg for kw in node.keywords}
        msg = None
        if a.vararg is None and len(node.args) > len(pos_params):
            msg = (
                f"too many positional args for {node.func.id}() "
                f"({len(node.args)} > {len(pos_params)})"
            )
        elif a.kwarg is None:
            byname = set(p.arg for p in a.args) | kwonly
            unknown = kw_names - byname
            if unknown:
                msg = f"unknown kwarg(s) for {node.func.id}(): {sorted(unknown)}"
        if msg is None:
            covered = set(pos_params[: len(node.args)]) | kw_names
            missing = [p for p in required_pos if p not in covered]
            missing += sorted(kwonly_required - kw_names)
            if missing:
                msg = f"missing required arg(s) for {node.func.id}(): {missing}"
        if msg:
            findings.append((path, node.lineno, msg))
    return findings


@register("arity", "wrong-arity calls to same-module top-level functions")
def _arity_pass(ctx: Context) -> Iterator[Finding]:
    for m in ctx.modules:
        for _p, lineno, msg in call_arity(m.path, m.tree):
            yield Finding("ARITY", m.path, lineno, msg)


_arity_pass.RULES = ("ARITY",)


# -- DROPPED-TASK ------------------------------------------------------------

def dropped_tasks(path: str, tree: ast.AST):
    """Fire-and-forget ``asyncio.create_task`` / ``loop.create_task`` /
    ``ensure_future`` calls whose result is DISCARDED (an expression
    statement). The event loop holds tasks only by weak reference, so a
    dropped task can be garbage-collected mid-flight and silently die.
    Store the task or use runtime/tasks.py spawn_bg/TaskTracker. A bare
    ``create_task(...)`` inside a larger expression (gather, list, call
    argument) keeps a reference and is fine."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Expr) or not isinstance(node.value, ast.Call):
            continue
        name = spawn_call_name(node.value)
        if name is not None:
            out.append((path, node.lineno,
                        f"{name}(...) result discarded — the loop only "
                        "weak-refs tasks; keep a reference"))
    return out


@register("dropped-task", "create_task/ensure_future result discarded")
def _dropped_task_pass(ctx: Context) -> Iterator[Finding]:
    for m in ctx.modules:
        for _p, lineno, msg in dropped_tasks(m.path, m.tree):
            yield Finding("DROPPED-TASK", m.path, lineno, msg)


_dropped_task_pass.RULES = ("DROPPED-TASK",)


# -- BROAD-RETRY / SLEEP-RETRY -----------------------------------------------

def _is_broad_handler(h: ast.ExceptHandler) -> bool:
    """bare ``except:`` or ``except (Base)Exception``."""
    if h.type is None:
        return True
    types = h.type.elts if isinstance(h.type, ast.Tuple) else [h.type]
    return any(
        isinstance(t, ast.Name) and t.id in ("Exception", "BaseException")
        for t in types
    )


def _sleep_calls(node: ast.AST):
    """time.sleep / asyncio.sleep calls (awaited or not) under ``node``."""
    for n in ast.walk(node):
        call = n.value if isinstance(n, ast.Await) else n
        if not isinstance(call, ast.Call):
            continue
        fn = call.func
        if (
            isinstance(fn, ast.Attribute)
            and fn.attr == "sleep"
            and isinstance(fn.value, ast.Name)
            and fn.value.id in ("time", "asyncio")
        ):
            yield call


def adhoc_retry(path: str, tree: ast.AST):
    """Hand-rolled retry loops that belong on runtime/resilience.py's shared
    policy (fixed pacing, no jitter, no give-up bound, invisible to the
    retry metrics). Two shapes:

      - BROAD-RETRY: a broad handler whose body is nothing but ``continue``
        (or pass+continue) — swallow the error, go around again, forever.
      - SLEEP-RETRY: a loop that both swallows broad exceptions (handler
        with no ``raise``) and paces itself with a CONSTANT-argument sleep.
    """
    out = []
    for loop_node in ast.walk(tree):
        if not isinstance(loop_node, (ast.While, ast.For, ast.AsyncFor)):
            continue
        swallows = None
        for n in ast.walk(loop_node):
            if not isinstance(n, ast.Try):
                continue
            for h in n.handlers:
                if not _is_broad_handler(h):
                    continue
                body = [s for s in h.body if not isinstance(s, ast.Pass)]
                if len(body) == 1 and isinstance(body[0], ast.Continue):
                    out.append((
                        path, h.lineno, "BROAD-RETRY",
                        "broad except swallowed into `continue` "
                        "— route retries through runtime/resilience.py",
                    ))
                elif not any(isinstance(x, ast.Raise) for x in ast.walk(h)):
                    swallows = h
        if swallows is None:
            continue
        for call in _sleep_calls(loop_node):
            if call.args and isinstance(call.args[0], ast.Constant):
                out.append((
                    path, call.lineno, "SLEEP-RETRY",
                    "fixed-interval sleep in a loop that "
                    "swallows broad exceptions — use a RetryPolicy "
                    "(runtime/resilience.py) for backoff",
                ))
                break  # one finding per loop is enough
    return out


@register("adhoc-retry", "hand-rolled retry loops bypassing runtime/resilience.py")
def _adhoc_retry_pass(ctx: Context) -> Iterator[Finding]:
    for m in ctx.modules:
        # resilience/faults are the funnel and may hand-roll by design
        if m.path.endswith(("runtime/resilience.py", "runtime/faults.py")):
            continue
        for _p, lineno, rule, msg in adhoc_retry(m.path, m.tree):
            yield Finding(rule, m.path, lineno, msg)


_adhoc_retry_pass.RULES = ("BROAD-RETRY", "SLEEP-RETRY")


# -- KV-DTYPE ----------------------------------------------------------------

# KV-plane files where a raw float32 KV buffer is a latent 2-4x byte bug:
# bf16 models must store/ship model-dtype bytes and int8 caches the
# payload+scales codec buffer — both via the central helper
# (kvbm/layout.block_shape_for / QuantizedBlockCodec), which is the ONE
# exempt file. engine/engine.py is out of scope (float32 there is sampling
# state, not KV bytes).
def _is_kv_plane_file(norm_path: str) -> bool:
    if norm_path.endswith("kvbm/layout.py"):
        return False  # the central layout helper owns the dtype decision
    return (
        "/kvbm/" in norm_path
        or norm_path.endswith("engine/transfer.py")
        or "dynamo_tpu/transfer/" in norm_path
        or norm_path.endswith("ops/block_copy.py")
    )


def kv_float32_allocations(path: str, tree: ast.AST):
    """np.float32 / jnp.float32 anywhere in a KV-plane file: KV buffers take
    their dtype from kvbm/layout.block_shape_for (model dtype or the int8
    codec), never a float32 literal."""
    out = []
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Attribute)
            and node.attr == "float32"
            and isinstance(node.value, ast.Name)
            and node.value.id in ("np", "jnp", "numpy")
        ):
            out.append((
                path, node.lineno,
                "raw float32 in a KV-plane file — derive the "
                "dtype from kvbm/layout.block_shape_for (model dtype / "
                "int8 codec) instead",
            ))
    return out


@register("kv-dtype", "raw float32 buffers in KV-plane files")
def _kv_dtype_pass(ctx: Context) -> Iterator[Finding]:
    for m in ctx.modules:
        if not _is_kv_plane_file(m.path):
            continue
        for _p, lineno, msg in kv_float32_allocations(m.path, m.tree):
            yield Finding("KV-DTYPE", m.path, lineno, msg)


_kv_dtype_pass.RULES = ("KV-DTYPE",)


# -- SIM-WALLCLOCK -----------------------------------------------------------

# Modules on the fleet simulator's path must pace and stamp time through an
# injected Clock (runtime/clock.py — the wall-clock funnel; sim/clock.py is
# the exempt virtual driver): a direct time.time()/time.monotonic()/
# asyncio.sleep() call silently mixes wall seconds into virtual timelines.
# time.perf_counter[_ns] stays allowed — measuring real control-plane CPU
# cost is the sim's job.
def _is_sim_path_file(norm_path: str) -> bool:
    if norm_path.endswith("sim/clock.py"):
        return False  # the Clock funnel owns the wall-clock calls
    return (
        "dynamo_tpu/sim/" in norm_path
        or "/mocker/" in norm_path
        # the whole KV-routing plane runs inside the virtual-clock sim:
        # metric staleness, approx TTLs and sync jitter must ride the
        # injected clock or the sim silently mixes wall seconds in
        or "dynamo_tpu/kv_router/" in norm_path
        or norm_path.endswith((
            "profiler/loadgen.py", "profiler/fleet_bench.py",
            "planner/metrics_source.py",
        ))
    )


def sim_wallclock(path: str, tree: ast.AST):
    out = []
    for call in ast.walk(tree):
        if not (isinstance(call, ast.Call) and isinstance(call.func, ast.Attribute)):
            continue
        fn = call.func
        if not isinstance(fn.value, ast.Name):
            continue
        if fn.value.id == "time" and fn.attr in ("time", "monotonic"):
            out.append((
                path, call.lineno,
                f"time.{fn.attr}() in a sim-path module — "
                "read the injected Clock (runtime/clock.py) so virtual time "
                "stays deterministic",
            ))
        elif fn.value.id == "time" and fn.attr == "sleep":
            out.append((
                path, call.lineno,
                "time.sleep() in a sim-path module — it "
                "blocks the virtualized loop in real wall seconds; await "
                "the injected Clock.sleep (runtime/clock.py)",
            ))
        elif fn.value.id == "asyncio" and fn.attr == "sleep":
            out.append((
                path, call.lineno,
                "asyncio.sleep() in a sim-path module — "
                "pace through the injected Clock.sleep (runtime/clock.py)",
            ))
    return out


@register("sim-wallclock", "wall-clock reads/sleeps in virtual-time sim modules")
def _sim_wallclock_pass(ctx: Context) -> Iterator[Finding]:
    for m in ctx.modules:
        if not _is_sim_path_file(m.path):
            continue
        for _p, lineno, msg in sim_wallclock(m.path, m.tree):
            yield Finding("SIM-WALLCLOCK", m.path, lineno, msg)


_sim_wallclock_pass.RULES = ("SIM-WALLCLOCK",)


# -- KERNEL-SPLIT ------------------------------------------------------------

# The attention seam (ops/paged_attention.PagedAttention): the step programs
# say what rows they have and ``ops/`` decides which kernel or pure-JAX twin
# serves them. A module outside ``ops/`` that imports a Pallas attention
# module makes that choice itself, once more. Tests, tools and chip_smoke.py
# hold the kernels to their twins and may import them.
PALLAS_ATTENTION_MODULES = frozenset({
    "pallas_attention", "pallas_unified", "pallas_paged",
})


def _is_kernel_split_exempt(norm_path: str) -> bool:
    p = "/" + norm_path
    return p.endswith("/chip_smoke.py") or any(
        d in p for d in ("/dynamo_tpu/ops/", "/tests/", "/tools/")
    )


def kernel_split_refs(path: str, tree: ast.AST):
    out = []

    def flag(lineno, names):
        for name in sorted(PALLAS_ATTENTION_MODULES.intersection(names)):
            out.append((path, lineno, (
                f"import of the Pallas attention module ops.{name} outside "
                "ops/ — ask the seam (ops/paged_attention.PagedAttention) "
                "for attention over paged rows; which kernel or twin serves "
                "them is decided there"
            )))

    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            parts = (node.module or "").split(".")
            flag(node.lineno, parts)
            if parts[-1] == "ops":  # from ..ops import pallas_unified
                flag(node.lineno, [a.name for a in node.names])
        elif isinstance(node, ast.Import):
            for a in node.names:
                flag(node.lineno, a.name.split("."))
    return out


@register("kernel-split", "Pallas attention modules imported outside ops/")
def _kernel_split_pass(ctx: Context) -> Iterator[Finding]:
    for m in ctx.modules:
        if _is_kernel_split_exempt(m.path):
            continue
        for _p, lineno, msg in kernel_split_refs(m.path, m.tree):
            yield Finding("KERNEL-SPLIT", m.path, lineno, msg)


_kernel_split_pass.RULES = ("KERNEL-SPLIT",)


# -- WIRE-BLOCKING -----------------------------------------------------------

# The disagg transfer plane streams KV in block windows
# (KvTransferServer._handle_stream / _window_item): the serving side ships
# each prefill chunk's blocks as they commit, hiding the wire under compute.
# A request-path call that gathers the FULL multi-block payload in one shot
# re-serializes the transfer behind the whole prefill — the exact TTFT
# regression PR 10 removed. The blocking branch of handle() keeps two such
# calls deliberately (legacy clients, device/native one-shot wires); those
# sites are baselined.
WHOLE_PAYLOAD_GATHERS = frozenset({
    "_gather", "_gather_np", "_gather_quant_np", "_gather_into_arena",
})
# functions ALLOWED to call the gather helpers: the streaming window
# implementation (window-bounded by construction) and the helpers' own
# bodies (they compose each other)
_WIRE_STREAMING_FUNCS = frozenset(
    {"_window_item", "_handle_stream"}
) | WHOLE_PAYLOAD_GATHERS
_WIRE_REQUEST_PATH = ("dynamo_tpu/engine/", "dynamo_tpu/llm/")


def _is_wire_request_path(norm_path: str) -> bool:
    # containment (not startswith): fixture trees live outside the repo root
    return any(seg in norm_path for seg in _WIRE_REQUEST_PATH)


def wire_blocking_refs(path: str, tree: ast.AST):
    out = []

    def msg(name):
        return (
            f"request-path code gathers a full multi-block KV payload in one "
            f"{name} call outside the streaming protocol — serve block "
            "windows instead (KvTransferServer._handle_stream) so transfer "
            "overlaps prefill; deliberate blocking-wire sites are baselined"
        )

    stack: list = []

    def walk(node):
        is_fn = isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
        if is_fn:
            stack.append(node.name)
        if isinstance(node, ast.Call):
            fn = node.func
            name = (
                fn.attr if isinstance(fn, ast.Attribute)
                else fn.id if isinstance(fn, ast.Name) else None
            )
            if (
                name in WHOLE_PAYLOAD_GATHERS
                # any enclosing scope counts: the helpers run their device
                # work in nested executor closures (def gather(): ...)
                and not any(f in _WIRE_STREAMING_FUNCS for f in stack)
            ):
                out.append((path, node.lineno, msg(name)))
        for child in ast.iter_child_nodes(node):
            walk(child)
        if is_fn:
            stack.pop()

    walk(tree)
    return out


@register("wire-blocking", "whole-payload KV gathers outside the streaming protocol")
def _wire_blocking_pass(ctx: Context) -> Iterator[Finding]:
    for m in ctx.modules:
        if not _is_wire_request_path(m.path):
            continue
        for _p, lineno, msg in wire_blocking_refs(m.path, m.tree):
            yield Finding("WIRE-BLOCKING", m.path, lineno, msg)


_wire_blocking_pass.RULES = ("WIRE-BLOCKING",)


# -- PROMETHEUS-IMPORT -------------------------------------------------------

def prometheus_imports(path: str, tree: ast.AST):
    """Direct prometheus_client imports outside runtime/metrics.py: every
    metric must ride a MetricsScope so it lands in the shared registry."""
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            names = [node.module or ""]
        else:
            continue
        if any(n.split(".")[0] == "prometheus_client" for n in names):
            out.append((
                path, node.lineno,
                "import prometheus_client outside "
                "runtime/metrics.py — go through MetricsScope",
            ))
    return out


@register("prometheus-import", "prometheus_client imported outside runtime/metrics.py")
def _prometheus_import_pass(ctx: Context) -> Iterator[Finding]:
    for m in ctx.modules:
        if m.path.endswith("runtime/metrics.py"):
            continue
        for _p, lineno, msg in prometheus_imports(m.path, m.tree):
            yield Finding("PROMETHEUS-IMPORT", m.path, lineno, msg)


_prometheus_import_pass.RULES = ("PROMETHEUS-IMPORT",)


# -- WALLCLOCK-LATENCY -------------------------------------------------------

# Request-path modules where latency must flow through MetricsScope on a
# monotonic clock, not hand-rolled wall-clock subtraction. kv_router/scheduler
# is deliberately out: its staleness check compares a CROSS-PROCESS wall-clock
# stamp, where monotonic would be wrong.
def _is_request_path_file(norm_path: str) -> bool:
    return (
        "/llm/http/" in norm_path
        or "/runtime/request_plane/" in norm_path
        or norm_path.endswith((
            "llm/backend.py", "llm/discovery.py", "llm/migration.py",
            "llm/prefill_router.py",
        ))
    )


def _is_wallclock_call(node: ast.AST) -> bool:
    return (
        isinstance(node, ast.Call)
        and isinstance(node.func, ast.Attribute)
        and node.func.attr == "time"
        and isinstance(node.func.value, ast.Name)
        and node.func.value.id == "time"
    )


def wallclock_latency(path: str, tree: ast.AST):
    """``time.time() - x`` / ``x - time.time()`` in a request-path module:
    an ad-hoc latency measurement on the WALL clock that bypasses
    MetricsScope. ``int(time.time())`` creation timestamps pass."""
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub):
            if _is_wallclock_call(node.left) or _is_wallclock_call(node.right):
                out.append((
                    path, node.lineno,
                    "time.time() subtraction in a "
                    "request-path module — use time.monotonic() and a "
                    "MetricsScope histogram (runtime/metrics.py)",
                ))
    return out


@register("wallclock-latency", "wall-clock latency subtraction on the request path")
def _wallclock_latency_pass(ctx: Context) -> Iterator[Finding]:
    for m in ctx.modules:
        if not _is_request_path_file(m.path):
            continue
        for _p, lineno, msg in wallclock_latency(m.path, m.tree):
            yield Finding("WALLCLOCK-LATENCY", m.path, lineno, msg)


_wallclock_latency_pass.RULES = ("WALLCLOCK-LATENCY",)


# -- UNUSED-METRIC (cross-file) ----------------------------------------------

def unused_metric_names(parsed):
    """Canonical ``dtpu_*`` names declared in runtime/metrics.py with zero
    call sites anywhere else: a name in the catalog that nothing observes is
    a dashboard lying in wait. ``parsed`` is the [(path, tree)] list for the
    whole run; the pass is skipped unless runtime/metrics.py is in it."""
    metrics_entry = next(
        (
            (p, t) for p, t in parsed
            if p.replace(os.sep, "/").endswith("runtime/metrics.py")
        ),
        None,
    )
    if metrics_entry is None:
        return []
    mpath, mtree = metrics_entry
    declared = {}  # constant name -> lineno
    for node in mtree.body:
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        tgt = node.targets[0]
        if not (isinstance(tgt, ast.Name) and tgt.id.isupper()):
            continue
        # metric names are f"{PREFIX}_..." JoinedStrs (or plain strings);
        # PREFIX itself and the LABEL_* constants are not metric names
        if tgt.id == "PREFIX" or tgt.id.startswith("LABEL_"):
            continue
        if isinstance(node.value, ast.JoinedStr) or (
            isinstance(node.value, ast.Constant)
            and isinstance(node.value.value, str)
        ):
            declared[tgt.id] = node.lineno
    if not declared:
        return []
    used = set()
    for p, tree in parsed:
        if p == mpath:
            continue
        for node in ast.walk(tree):
            if isinstance(node, ast.Attribute) and node.attr in declared:
                used.add(node.attr)
            elif isinstance(node, ast.Name) and node.id in declared:
                used.add(node.id)
    return [
        (mpath, lineno,
         f"{name} is in the canonical catalog but nothing "
         "observes it — wire it or drop it")
        for name, lineno in sorted(declared.items(), key=lambda kv: kv[1])
        if name not in used
    ]


@register("unused-metric", "catalog metric names with zero observation sites")
def _unused_metric_pass(ctx: Context) -> Iterator[Finding]:
    if getattr(ctx, "partial", False):
        return  # zero-site checks need the whole tree (--changed-only)
    parsed = [(m.path, m.tree) for m in ctx.modules]
    for p, lineno, msg in unused_metric_names(parsed):
        yield Finding("UNUSED-METRIC", p, lineno, msg)


_unused_metric_pass.RULES = ("UNUSED-METRIC",)


# -- METRIC-CARDINALITY ------------------------------------------------------

# Prometheus label values must come from bounded sets: a label fed from
# request ids, raw prompts, traceparents or per-worker transfer addresses
# grows one time series per distinct value and /metrics without bound.
# Label *names* that are unbounded by definition:
_CARDINALITY_SUSPECT_LABELS = {
    "request_id", "rid", "prompt", "traceparent", "trace_id", "address",
}
# identifier fragments that mark a label *value* as drawn from an unbounded
# set (worker/instance ids churn under autoscaling; addresses are per-host
# outside the known-instance path; prompts/request ids are per-request)
_CARDINALITY_UNBOUNDED_NAMES = {
    "request_id", "rid", "prompt", "traceparent", "trace_id",
    "address", "transfer_address", "instance_id", "worker_id", "iid", "wid",
}
_METRIC_OBSERVE_METHODS = {"inc", "dec", "observe"}


def _is_metric_scope_file(norm_path: str) -> bool:
    return (
        "dynamo_tpu/runtime/" in norm_path
        or "dynamo_tpu/llm/" in norm_path
        or "dynamo_tpu/engine/" in norm_path
    )


def _is_metric_call(node: ast.Call) -> bool:
    """inc/dec/observe on anything, plus .set on a gauge-named receiver
    (``.set`` alone is too common: spans, health state, jax ``.at[].set``)."""
    func = node.func
    if not isinstance(func, ast.Attribute):
        return False
    if func.attr in _METRIC_OBSERVE_METHODS:
        return True
    if func.attr == "set":
        recv = func.value
        name = (
            recv.attr if isinstance(recv, ast.Attribute)
            else recv.id if isinstance(recv, ast.Name) else ""
        )
        return "gauge" in name.lower() or name.endswith("_g")
    return False


def _unbounded_value_name(expr: ast.AST):
    for n in ast.walk(expr):
        if isinstance(n, ast.Name) and n.id in _CARDINALITY_UNBOUNDED_NAMES:
            return n.id
        if isinstance(n, ast.Attribute) and n.attr in _CARDINALITY_UNBOUNDED_NAMES:
            return n.attr
    return None


def metric_cardinality(path: str, tree: ast.AST):
    """Metric label values fed from unbounded sets in runtime//llm//engine/:
    each distinct value is a new time series kept forever by the registry."""
    out = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and _is_metric_call(node)):
            continue
        for kw in node.keywords:
            if kw.arg is None:
                continue
            src = (
                kw.arg if kw.arg in _CARDINALITY_SUSPECT_LABELS
                else _unbounded_value_name(kw.value)
            )
            if src is not None:
                out.append((
                    path, node.lineno,
                    f"metric label {kw.arg!r} is fed from the unbounded "
                    f"set {src!r} (one series per distinct value) — label "
                    "with a bounded class instead, or keep the metric on a "
                    "detached scope",
                ))
    return out


@register("metric-cardinality", "metric labels fed from unbounded value sets")
def _metric_cardinality_pass(ctx: Context) -> Iterator[Finding]:
    for m in ctx.modules:
        if not _is_metric_scope_file(m.path):
            continue
        for _p, lineno, msg in metric_cardinality(m.path, m.tree):
            yield Finding("METRIC-CARDINALITY", m.path, lineno, msg)


_metric_cardinality_pass.RULES = ("METRIC-CARDINALITY",)


# -- MIXED-GATE --------------------------------------------------------------

# Mixed continuous batching's family gate lives in ONE documented site —
# the `self.mixed_enabled = bool(... and ...)` assignment in
# TpuEngine.__init__ (dynamo_tpu/engine/engine.py). PR 14 shrank the gate
# to pp/sp/vision/multihost; every surviving `and`-term is baselined, so
# ADDING an exclusion term (or a second gate site anywhere else) surfaces
# as a new finding. The gate can only shrink silently — growing it takes a
# deliberate baseline entry.
_MIXED_GATE_SITE = "dynamo_tpu/engine/engine.py"


def _target_names(node: ast.Assign):
    for t in node.targets:
        if isinstance(t, ast.Attribute):
            yield t.attr
        elif isinstance(t, ast.Name):
            yield t.id


def mixed_gate_terms(path: str, tree: ast.AST):
    """(path, lineno, msg) per `and`-term of every mixed_enabled
    assignment, plus a site finding for assignments outside the documented
    gate location."""
    out = []
    at_site = path.endswith(_MIXED_GATE_SITE) or path == _MIXED_GATE_SITE
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        if "mixed_enabled" not in set(_target_names(node)):
            continue
        if not at_site:
            out.append((
                path, node.lineno,
                "mixed_enabled assigned outside the documented gate site "
                f"({_MIXED_GATE_SITE} TpuEngine.__init__) — family "
                "eligibility must stay in the one audited gate",
            ))
            continue
        val = node.value
        if (
            isinstance(val, ast.Call)
            and isinstance(val.func, ast.Name)
            and val.func.id == "bool"
            and val.args
        ):
            val = val.args[0]
        terms = (
            val.values
            if isinstance(val, ast.BoolOp) and isinstance(val.op, ast.And)
            else [val]
        )
        for term in terms:
            out.append((
                path, term.lineno,
                f"mixed gate term `{ast.unparse(term)}` — adding a family "
                "exclusion needs a deliberate baseline entry (the gate "
                "should only shrink)",
            ))
    return out


@register("mixed-gate", "mixed-batching family exclusions outside the audited gate")
def _mixed_gate_pass(ctx: Context) -> Iterator[Finding]:
    for m in ctx.modules:
        if m.path.startswith(("tests/", "tools/")):
            continue
        for _p, lineno, msg in mixed_gate_terms(m.path, m.tree):
            yield Finding("MIXED-GATE", m.path, lineno, msg)


_mixed_gate_pass.RULES = ("MIXED-GATE",)
