"""tools/analysis: the single-parse multi-pass AST analyzer.

Covers the framework (baseline round-trip, inline ignores, pycache guard,
CLI exit codes), fixture positive/negative cases for the semantic passes
(ASYNC-RMW, ASYNC-BLOCKING, JIT-PURITY, HOST-SYNC, TASK-LIFECYCLE), and a
parity check that the passes ported from the pre-framework tools/lint.py
report the same findings on the current tree.
"""

import json
import os
import subprocess
import sys

import pytest

from tools.analysis import core

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def analyze(tmp_path, rel, src, rule=None):
    """Write ``src`` at tmp_path/rel, analyze it, return findings (for one
    rule if given). No baseline — raw findings."""
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(src)
    modules, parse = core.load_modules([str(tmp_path)])
    found = core.collect_findings(modules, parse)
    if rule is not None:
        found = [f for f in found if f.rule == rule]
    return found


def run_cli(args, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "tools.analysis", *args],
        capture_output=True, text=True, timeout=120, cwd=cwd,
    )


# -- ASYNC-RMW ---------------------------------------------------------------

def test_rmw_check_then_act_flagged(tmp_path):
    found = analyze(
        tmp_path, "dynamo_tpu/router/cache.py",
        "import asyncio\n"
        "class Router:\n"
        "    async def get(self, k, fetch):\n"
        "        if k not in self.cache:\n"
        "            v = await fetch(k)\n"
        "            self.cache[k] = v\n"
        "        return self.cache[k]\n",
        rule="ASYNC-RMW",
    )
    assert len(found) == 1 and found[0].line == 6
    assert "check-then-act" in found[0].message


def test_rmw_read_await_write_flagged(tmp_path):
    found = analyze(
        tmp_path, "dynamo_tpu/planner/pool.py",
        "import asyncio\n"
        "class Pool:\n"
        "    async def bump(self):\n"
        "        n = self.count\n"
        "        await asyncio.sleep(0)\n"
        "        self.count = n + 1\n",
        rule="ASYNC-RMW",
    )
    assert len(found) == 1 and found[0].line == 6
    assert "read-modify-write of self.count" in found[0].message


def test_rmw_aug_assign_await_flagged(tmp_path):
    # CPython evaluates the augtarget's read BEFORE awaiting the rhs
    found = analyze(
        tmp_path, "dynamo_tpu/transfer/meter.py",
        "class Meter:\n"
        "    async def add(self, fetch):\n"
        "        self.total += await fetch()\n",
        rule="ASYNC-RMW",
    )
    assert len(found) == 1 and "self.total" in found[0].message


def test_rmw_lock_guarded_not_flagged(tmp_path):
    found = analyze(
        tmp_path, "dynamo_tpu/router/locked.py",
        "import asyncio\n"
        "class Router:\n"
        "    async def get(self, k, fetch):\n"
        "        async with self._lock:\n"
        "            if k not in self.cache:\n"
        "                v = await fetch(k)\n"
        "                self.cache[k] = v\n"
        "        return self.cache[k]\n",
        rule="ASYNC-RMW",
    )
    assert found == []


def test_rmw_double_checked_lock_not_flagged(tmp_path):
    # the TcpClient._get_conn idiom: lock-free fast path, re-check + write
    # under the lock
    found = analyze(
        tmp_path, "dynamo_tpu/router/pool2.py",
        "import asyncio\n"
        "class Pool:\n"
        "    async def conn(self, addr, connect):\n"
        "        c = self._conns.get(addr)\n"
        "        if c is not None:\n"
        "            return c\n"
        "        async with self._lock:\n"
        "            c = self._conns.get(addr)\n"
        "            if c is not None:\n"
        "                return c\n"
        "            c = await connect(addr)\n"
        "            self._conns[addr] = c\n"
        "            return c\n",
        rule="ASYNC-RMW",
    )
    assert found == []


def test_rmw_lock_reacquired_in_own_body_flagged(tmp_path):
    found = analyze(
        tmp_path, "dynamo_tpu/router/deadlock.py",
        "import asyncio\n"
        "class R:\n"
        "    async def lock_twice(self):\n"
        "        async with self._lock:\n"
        "            async with self._lock:\n"
        "                pass\n",
        rule="ASYNC-RMW",
    )
    assert len(found) == 1 and found[0].line == 5
    assert "not reentrant" in found[0].message


def test_rmw_out_of_scope_module_not_flagged(tmp_path):
    # same racy shape, but not a control-plane module: no finding
    found = analyze(
        tmp_path, "dynamo_tpu/models/foo.py",
        "import asyncio\n"
        "class M:\n"
        "    async def bump(self):\n"
        "        n = self.count\n"
        "        await asyncio.sleep(0)\n"
        "        self.count = n + 1\n",
        rule="ASYNC-RMW",
    )
    assert found == []


# -- ASYNC-BLOCKING ----------------------------------------------------------

def test_blocking_calls_in_async_def_flagged(tmp_path):
    found = analyze(
        tmp_path, "svc.py",
        "import time\n"
        "import requests\n"
        "import subprocess\n"
        "async def handler():\n"
        "    time.sleep(1)\n"
        "    requests.get('http://x')\n"
        "    subprocess.run(['ls'])\n",
        rule="ASYNC-BLOCKING",
    )
    assert [f.line for f in found] == [5, 6, 7]
    assert "blocks the event loop" in found[0].message


def test_blocking_in_nested_sync_def_not_flagged(tmp_path):
    # nested sync defs typically run on an executor; asyncio.sleep is fine
    found = analyze(
        tmp_path, "svc2.py",
        "import asyncio\n"
        "import time\n"
        "async def handler(loop):\n"
        "    def work():\n"
        "        time.sleep(1)\n"
        "    await loop.run_in_executor(None, work)\n"
        "    await asyncio.sleep(0.1)\n",
        rule="ASYNC-BLOCKING",
    )
    assert found == []


# -- JIT-PURITY / HOST-SYNC --------------------------------------------------

def test_jit_purity_host_sync_and_mutation_flagged(tmp_path):
    found = analyze(
        tmp_path, "dynamo_tpu/ops/fused.py",
        "import jax\n"
        "import numpy as np\n"
        "from functools import partial\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return x.item()\n"
        "@partial(jax.jit, static_argnums=0)\n"
        "class _:\n"
        "    pass\n"
        "class K:\n"
        "    @jax.jit\n"
        "    def fwd(self, x):\n"
        "        self.calls += 1\n"
        "        return np.asarray(x)\n",
        rule="JIT-PURITY",
    )
    lines = sorted(f.line for f in found)
    assert 6 in lines           # .item() in @jax.jit
    assert 13 in lines          # self.calls += 1 mutation
    assert 14 in lines          # np.asarray
    mutation = next(f for f in found if f.line == 13)
    assert "trace time" in mutation.message


def test_jit_purity_undecorated_not_flagged(tmp_path):
    found = analyze(
        tmp_path, "dynamo_tpu/ops/plain.py",
        "import numpy as np\n"
        "def fetch(x):\n"
        "    return np.asarray(x)\n",
        rule="JIT-PURITY",
    )
    assert found == []


def test_host_sync_engine_scope_and_inline_ignore(tmp_path):
    src = (
        "import numpy as np\n"
        "def measure(x):\n"
        "    return np.asarray(x)\n"
        "def probe(x):\n"
        "    return np.asarray(x)  # dtpu: ignore[HOST-SYNC] deliberate\n"
        "class Engine:\n"
        "    def _loop(self, x):\n"
        "        return x.item()\n"
        "    def offload(self, x):\n"
        "        return np.asarray(x)\n"
    )
    found = analyze(tmp_path, "dynamo_tpu/engine/engine.py", src, rule="HOST-SYNC")
    lines = sorted(f.line for f in found)
    # module-level fn + _loop flagged; inline ignore honored; other class
    # methods (offload/onboard executors) out of scope by design
    assert lines == [3, 8]


# -- TASK-LIFECYCLE ----------------------------------------------------------

def test_task_handle_never_used_flagged(tmp_path):
    found = analyze(
        tmp_path, "tasks1.py",
        "import asyncio\n"
        "async def spawn(work):\n"
        "    t = asyncio.create_task(work())\n"
        "async def spawn2(work):\n"
        "    _ = asyncio.create_task(work())\n",
        rule="TASK-LIFECYCLE",
    )
    assert sorted(f.line for f in found) == [3, 5]


def test_task_handle_retained_not_flagged(tmp_path):
    found = analyze(
        tmp_path, "tasks2.py",
        "import asyncio\n"
        "async def awaited(work):\n"
        "    t = asyncio.create_task(work())\n"
        "    await t\n"
        "class S:\n"
        "    def start(self, work):\n"
        "        self._t = asyncio.create_task(work())\n"
        "    def tracked(self, work):\n"
        "        t = asyncio.create_task(work())\n"
        "        self._tasks.append(t)\n",
        rule="TASK-LIFECYCLE",
    )
    assert found == []


# -- framework: inline ignores, baseline, guard, CLI -------------------------

def test_inline_ignore_wrong_rule_still_fires(tmp_path):
    found = analyze(
        tmp_path, "wrong_ignore.py",
        "import time\n"
        "async def h():\n"
        "    time.sleep(1)  # dtpu: ignore[ASYNC-RMW]\n",
        rule="ASYNC-BLOCKING",
    )
    assert len(found) == 1  # names a different rule: not suppressed


def test_inline_ignore_star_suppresses_all(tmp_path):
    found = analyze(
        tmp_path, "star_ignore.py",
        "import time\n"
        "async def h():\n"
        "    time.sleep(1)  # dtpu: ignore[*]\n",
        rule="ASYNC-BLOCKING",
    )
    assert found == []


def test_syntax_error_is_a_finding_not_a_crash(tmp_path):
    (tmp_path / "ok.py").write_text("X = 1\n")
    (tmp_path / "broken.py").write_text("def f(:\n")
    modules, parse = core.load_modules([str(tmp_path)])
    assert [f.rule for f in parse] == ["SYNTAX"]
    assert len(modules) == 1  # the broken file didn't hide the good one


def test_baseline_round_trip_and_line_independence(tmp_path):
    fixture = tmp_path / "pkg"
    fixture.mkdir()
    bad = fixture / "bad.py"
    bad.write_text("import time\nasync def h():\n    time.sleep(1)\n")
    baseline = tmp_path / "baseline.txt"

    r = run_cli([str(fixture), "--no-baseline"])
    assert r.returncode == 1 and "ASYNC-BLOCKING" in r.stdout

    r = run_cli([str(fixture), "--baseline", str(baseline), "--write-baseline"])
    assert r.returncode == 0 and baseline.exists()

    r = run_cli([str(fixture), "--baseline", str(baseline)])
    assert r.returncode == 0, r.stdout  # baselined: gate is clean

    # baseline keys carry no line numbers: editing ABOVE the finding must
    # not churn the gate
    bad.write_text("# a new comment line\n" + bad.read_text())
    r = run_cli([str(fixture), "--baseline", str(baseline)])
    assert r.returncode == 0, r.stdout

    # a NEW finding of the same rule elsewhere is NOT covered
    (fixture / "worse.py").write_text(
        "import time\nasync def g():\n    time.sleep(2)\n"
    )
    r = run_cli([str(fixture), "--baseline", str(baseline)])
    assert r.returncode == 1 and "worse.py" in r.stdout

    # fixing the baselined finding for real surfaces a stale-entry note
    (fixture / "worse.py").unlink()
    bad.write_text("import asyncio\nasync def h():\n    await asyncio.sleep(1)\n")
    r = run_cli([str(fixture), "--baseline", str(baseline)])
    assert r.returncode == 0 and "stale baseline entry" in r.stdout


def test_stale_notes_scoped_to_scanned_paths_and_selected_rules(tmp_path):
    # a baseline entry is only provably stale if this run could have
    # re-produced it: scanning a different tree, or filtering the entry's
    # rule out with --select, must not flag it
    fixture = tmp_path / "pkg"
    fixture.mkdir()
    (fixture / "bad.py").write_text(
        "import time\nasync def h():\n    time.sleep(1)\n"
    )
    baseline = tmp_path / "baseline.txt"
    r = run_cli([str(fixture), "--baseline", str(baseline), "--write-baseline"])
    assert r.returncode == 0

    other = tmp_path / "other"
    other.mkdir()
    (other / "ok.py").write_text("X = 1\n")
    r = run_cli([str(other), "--baseline", str(baseline)])
    assert r.returncode == 0 and "stale" not in r.stdout

    r = run_cli(
        [str(fixture), "--select", "TASK-LIFECYCLE", "--baseline", str(baseline)]
    )
    assert r.returncode == 0 and "stale" not in r.stdout

    # within scope, a genuinely-fixed finding still gets the prune note
    (fixture / "bad.py").write_text(
        "import asyncio\nasync def h():\n    await asyncio.sleep(1)\n"
    )
    r = run_cli(
        [str(fixture), "--select", "ASYNC-BLOCKING", "--baseline", str(baseline)]
    )
    assert r.returncode == 0 and "stale baseline entry" in r.stdout


def test_baseline_is_a_multiset(tmp_path):
    # two identical findings, one baselined copy: exactly one suppressed
    fixture = tmp_path / "pkg"
    fixture.mkdir()
    (fixture / "dup.py").write_text(
        "import time\n"
        "async def a():\n"
        "    time.sleep(1)\n"
        "async def b():\n"
        "    time.sleep(1)\n"
    )
    modules, parse = core.load_modules([str(fixture)])
    found = [
        f for f in core.collect_findings(modules, parse)
        if f.rule == "ASYNC-BLOCKING"
    ]
    assert len(found) == 2
    assert found[0].baseline_key() == found[1].baseline_key()
    from collections import Counter

    new, suppressed, stale = core.apply_baseline(
        found, Counter({found[0].baseline_key(): 1})
    )
    assert len(new) == 1 and len(suppressed) == 1 and not stale


def test_pycache_only_dir_refused(tmp_path):
    orphan = tmp_path / "ghostpkg" / "__pycache__"
    orphan.mkdir(parents=True)
    (orphan / "core.cpython-310.pyc").write_bytes(b"\x00\x01")
    r = run_cli([str(tmp_path / "ghostpkg")])
    assert r.returncode == 2
    assert "refusing to analyze" in r.stderr and "__pycache__" in r.stderr


def test_empty_dir_is_usage_error(tmp_path):
    (tmp_path / "empty").mkdir()
    r = run_cli([str(tmp_path / "empty")])
    assert r.returncode == 2 and "no Python sources" in r.stderr


def test_cli_list_rules_and_select(tmp_path):
    r = run_cli(["--list-rules"])
    rules = set(r.stdout.split())
    assert r.returncode == 0
    # >= 9 rules: the 4 new semantic passes + the ported legacy passes
    expected = {
        "ASYNC-RMW", "ASYNC-BLOCKING", "JIT-PURITY", "HOST-SYNC",
        "TASK-LIFECYCLE", "UNDEFINED", "UNUSED-IMPORT", "ARITY",
        "DROPPED-TASK", "BROAD-RETRY", "SLEEP-RETRY", "KV-DTYPE",
        "SIM-WALLCLOCK", "PROMETHEUS-IMPORT", "WALLCLOCK-LATENCY",
        "UNUSED-METRIC",
        # the interprocedural lifecycle + catalog-drift rules (flows.py)
        "RESOURCE-LEAK", "LOCK-ACROSS-AWAIT", "TASK-JOIN",
        "ENV-DRIFT", "FAULTS-DRIFT",
    }
    assert expected <= rules

    fixture = tmp_path / "sel.py"
    fixture.write_text("import json\nimport time\nasync def h():\n    time.sleep(1)\n")
    r = run_cli([str(fixture), "--no-baseline", "--select", "UNUSED-IMPORT"])
    assert r.returncode == 1
    assert "UNUSED-IMPORT" in r.stdout and "ASYNC-BLOCKING" not in r.stdout

    r = run_cli([str(fixture), "--select", "NOT-A-RULE"])
    assert r.returncode == 2 and "unknown rule" in r.stderr

    # --write-baseline REPLACES the file; under --select it would silently
    # drop every other rule's entries — refuse instead of corrupting
    r = run_cli(
        [str(fixture), "--select", "UNUSED-IMPORT", "--write-baseline",
         "--baseline", str(tmp_path / "b.txt")]
    )
    assert r.returncode == 2 and "--select" in r.stderr
    assert not (tmp_path / "b.txt").exists()


def test_cli_json_output(tmp_path):
    fixture = tmp_path / "j.py"
    fixture.write_text("import time\nasync def h():\n    time.sleep(1)\n")
    r = run_cli([str(fixture), "--no-baseline", "--json"])
    assert r.returncode == 1
    obj = json.loads(r.stdout)
    assert obj["suppressed"] == 0 and obj["stale_baseline"] == []
    [f] = [x for x in obj["findings"] if x["rule"] == "ASYNC-BLOCKING"]
    assert f["line"] == 3 and f["severity"] == "error"


# -- WIRE-BLOCKING -----------------------------------------------------------

_WIRE_POS = (
    "class Mover:\n"
    "    async def pull_all(self, ids):\n"
    "        return await self._gather_np(ids)\n"
)


def test_wire_blocking_flags_request_path_whole_gather(tmp_path):
    found = analyze(
        tmp_path, "dynamo_tpu/engine/mover.py", _WIRE_POS,
        rule="WIRE-BLOCKING",
    )
    assert len(found) == 1 and found[0].line == 3
    assert "_gather_np" in found[0].message
    assert "streaming protocol" in found[0].message


def test_wire_blocking_exempts_streaming_protocol_and_helpers(tmp_path):
    found = analyze(
        tmp_path, "dynamo_tpu/engine/mover.py",
        "class Srv:\n"
        "    async def _handle_stream(self, req):\n"
        "        return await self._gather(ids)\n"       # window-bounded
        "    async def _window_item(self, ids):\n"
        "        def gather():\n"
        "            return self._gather_np(ids)\n"      # nested closure
        "        return gather\n"
        "    async def _gather(self, ids):\n"
        "        return self._gather_quant_np(ids)\n"    # helper composing
        "    def other_gathers(self, ids):\n"
        "        return kv_gather(ids)\n",               # different name
        rule="WIRE-BLOCKING",
    )
    assert found == []


def test_wire_blocking_scoped_to_request_path_modules(tmp_path):
    # the same call outside engine//llm/ (tools, kvbm background tiers) is
    # not request-path and stays unflagged
    found = analyze(
        tmp_path, "dynamo_tpu/kvbm/pool.py", _WIRE_POS, rule="WIRE-BLOCKING",
    )
    assert found == []


def test_wire_blocking_current_tree_only_baselined_sites(repo_analysis):
    """The live tree carries exactly the deliberate blocking-wire sites in
    handle()'s legacy branch — both baselined; anything new fails the gate."""
    _modules, _parse, findings = repo_analysis
    found = [f for f in findings if f.rule == "WIRE-BLOCKING"]
    assert len(found) == 2
    assert all(f.path == "dynamo_tpu/engine/transfer.py" for f in found)
    baseline = core.load_baseline(core.DEFAULT_BASELINE)
    for f in found:
        assert f.baseline_key() in baseline


# -- parity with the pre-framework lint.py -----------------------------------

def test_ported_passes_match_preport_lint_on_current_tree(repo_analysis):
    """The legacy helpers kept their pre-port behavior: driving them with
    the OLD tools/lint.py main()'s per-file orchestration (scoping rules
    and all) over dynamo_tpu/ must produce exactly the findings the
    framework reports for those rules."""
    from tools.analysis import legacy

    modules, parse, findings = repo_analysis
    assert not parse

    old = []  # (rule, path, line) per finding, old-driver scoping
    parsed = []
    for m in modules:
        parsed.append((m.path, m.tree))
        for _p, name in legacy.undefined_globals(m.path, m.src):
            old.append(("UNDEFINED", m.path, 0, name))
        if os.path.basename(m.path) != "__init__.py":
            for _p, name, lineno in legacy.unused_imports(m.path, m.tree, m.src):
                old.append(("UNUSED-IMPORT", m.path, lineno, name))
        for _p, lineno, _msg in legacy.call_arity(m.path, m.tree):
            old.append(("ARITY", m.path, lineno, None))
        for _p, lineno, _msg in legacy.dropped_tasks(m.path, m.tree):
            old.append(("DROPPED-TASK", m.path, lineno, None))
        if not m.path.endswith(("runtime/resilience.py", "runtime/faults.py")):
            for _p, lineno, rule, _msg in legacy.adhoc_retry(m.path, m.tree):
                old.append((rule, m.path, lineno, None))
        if legacy._is_kv_plane_file(m.path):
            for _p, lineno, _msg in legacy.kv_float32_allocations(m.path, m.tree):
                old.append(("KV-DTYPE", m.path, lineno, None))
        if legacy._is_sim_path_file(m.path):
            for _p, lineno, _msg in legacy.sim_wallclock(m.path, m.tree):
                old.append(("SIM-WALLCLOCK", m.path, lineno, None))
        if not m.path.endswith("runtime/metrics.py"):
            for _p, lineno, _msg in legacy.prometheus_imports(m.path, m.tree):
                old.append(("PROMETHEUS-IMPORT", m.path, lineno, None))
        if legacy._is_request_path_file(m.path):
            for _p, lineno, _msg in legacy.wallclock_latency(m.path, m.tree):
                old.append(("WALLCLOCK-LATENCY", m.path, lineno, None))
    for p, lineno, _msg in legacy.unused_metric_names(parsed):
        old.append(("UNUSED-METRIC", p, lineno, None))

    legacy_rules = {r for r, *_ in old} | {
        "UNDEFINED", "UNUSED-IMPORT", "ARITY", "DROPPED-TASK", "BROAD-RETRY",
        "SLEEP-RETRY", "KV-DTYPE", "SIM-WALLCLOCK", "PROMETHEUS-IMPORT",
        "WALLCLOCK-LATENCY", "UNUSED-METRIC",
    }
    new = []
    for f in findings:
        if f.rule not in legacy_rules:
            continue
        name = f.message.split()[0] if f.rule in ("UNDEFINED", "UNUSED-IMPORT") else None
        new.append((f.rule, f.path, f.line, name))
    assert sorted(old) == sorted(new)


# -- METRIC-CARDINALITY ------------------------------------------------------

_CARD_POS = (
    "class Svc:\n"
    "    def on_finish(self, rid, model, request_id, address):\n"
    "        self._lat.observe(0.5, model=model, request_id=rid)\n"
    "        self._reqs.inc(model=model, worker=f'{address}')\n"
)


def test_metric_cardinality_flags_unbounded_labels(tmp_path):
    found = analyze(
        tmp_path, "dynamo_tpu/llm/http/svc.py", _CARD_POS,
        rule="METRIC-CARDINALITY",
    )
    assert len(found) == 2
    assert found[0].line == 3 and "request_id" in found[0].message
    # 'worker' label is fine as a name, but its VALUE is an address
    assert found[1].line == 4 and "'address'" in found[1].message


def test_metric_cardinality_allows_bounded_labels_and_non_metrics(tmp_path):
    found = analyze(
        tmp_path, "dynamo_tpu/runtime/thing.py",
        "class Svc:\n"
        "    def ok(self, model, status, wire, request_id, span, state):\n"
        "        self._reqs.inc(model=model, status=status)\n"     # bounded
        "        self._bw_gauge.set(1.0, wire=wire)\n"             # bounded
        "        span.set(request_id=request_id)\n"                # a span, not a metric
        "        state.set('x', True, request_id=request_id)\n"    # health state
        "        self.flight.record(request_id, 'queued')\n",      # positional, not a label
        rule="METRIC-CARDINALITY",
    )
    assert found == []


def test_metric_cardinality_scoped_to_serving_packages(tmp_path):
    # the same call in tools/ or sim/ is not a serving-path registry
    found = analyze(
        tmp_path, "tools/report.py", _CARD_POS, rule="METRIC-CARDINALITY",
    )
    assert found == []


def test_metric_cardinality_current_tree_clean(repo_analysis):
    """The live serving tree keeps every metric label bounded (worker ids
    ride detached scopes; anything new fails the gate)."""
    _modules, _parse, findings = repo_analysis
    found = [f for f in findings if f.rule == "METRIC-CARDINALITY"]
    assert found == []


# -- KERNEL-SPLIT ------------------------------------------------------------

@pytest.mark.parametrize("path,source,hits", [
    ("dynamo_tpu/engine/engine.py",
     "def build():\n    from ..ops import pallas_unified as pun\n    return pun\n", 1),
    ("dynamo_tpu/parallel/pp_serving.py",
     "from dynamo_tpu.ops.pallas_attention import paged_decode_attention\n"
     "import dynamo_tpu.ops.pallas_paged\n", 2),
    # the seam and the pure-JAX twins are what a program asks
    ("dynamo_tpu/engine/engine.py",
     "from ..ops import attention as att\n"
     "from ..ops.paged_attention import PagedAttention\n"
     "x = att.paged_decode_attention\n", 0),
    # ops/ itself, tests and chip_smoke.py hold the kernels to their twins
    ("dynamo_tpu/ops/paged_attention.py",
     "from .pallas_unified import ragged_paged_attention\n", 0),
    ("chip_smoke.py", "from dynamo_tpu.ops import pallas_unified\n", 0),
], ids=["engine-import", "pp-import-forms", "seam-and-twins", "inside-ops",
        "chip-smoke"])
def test_kernel_split_is_the_seam(tmp_path, path, source, hits):
    found = analyze(tmp_path, path, source, rule="KERNEL-SPLIT")
    assert len(found) == hits
    assert all("ops/paged_attention.PagedAttention" in f.message for f in found)


# -- MIXED-GATE --------------------------------------------------------------

def test_mixed_gate_flags_terms_at_site(tmp_path):
    found = analyze(
        tmp_path, "dynamo_tpu/engine/engine.py",
        "class E:\n"
        "    def __init__(self, config):\n"
        "        self.mixed_enabled = bool(\n"
        "            mixed\n"
        "            and config.pp == 1\n"
        "            and config.new_family is None\n"
        "        )\n",
        rule="MIXED-GATE",
    )
    # one finding per and-term: a NEW exclusion term surfaces as a new,
    # non-baselined finding
    assert len(found) == 3
    assert any("config.new_family is None" in f.message for f in found)
    assert all("baseline entry" in f.message for f in found)


def test_mixed_gate_flags_assignment_outside_site(tmp_path):
    found = analyze(
        tmp_path, "dynamo_tpu/engine/dp.py",
        "class D:\n"
        "    def setup(self):\n"
        "        self.mixed_enabled = False\n",
        rule="MIXED-GATE",
    )
    assert len(found) == 1
    assert "outside the documented gate site" in found[0].message


def test_mixed_gate_ignores_reads_and_tests(tmp_path):
    found = analyze(
        tmp_path, "dynamo_tpu/engine/loop.py",
        "def f(self):\n"
        "    if self.mixed_enabled:\n"
        "        return 1\n",
        rule="MIXED-GATE",
    )
    assert found == []


def test_mixed_gate_current_tree_exactly_baselined(repo_analysis):
    """The live gate carries exactly the documented pp/sp/vision/multihost
    exclusions (plus the two intent terms), all baselined — the gate can
    only shrink without touching the baseline."""
    _modules, _parse, findings = repo_analysis
    found = [f for f in findings if f.rule == "MIXED-GATE"]
    assert len(found) == 6
    assert all(f.path == "dynamo_tpu/engine/engine.py" for f in found)
    msgs = "\n".join(f.message for f in found)
    for term in ("config.pp == 1", "config.sp == 1",
                 "config.vision is None", "multihost is None"):
        assert term in msgs
    # the retired family exclusions stay retired
    for gone in ("spec_draft", "lora_max_adapters", "is_gptoss", "is_gemma"):
        assert gone not in msgs
    baseline = core.load_baseline(core.DEFAULT_BASELINE)
    for f in found:
        assert f.baseline_key() in baseline
