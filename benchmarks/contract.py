"""The shape of a run's last line, checked against ``BENCHMARK.json``.

``run.py`` calls ``check_line`` on its own line before printing it: a line
that would be refused is never printed. Nothing here names a cell, a model
or a metric; all of that is read from the manifest.
"""

from __future__ import annotations

import json
import math
from typing import Any, Dict, List

REQUIRED_KEYS = ("correct", "attempted", "failed", "metrics", "device")
ALLOWED_KEYS = REQUIRED_KEYS + ("breakdown",)
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
TRACED_DEVICE_KEYS = ("window_s", "busy_s")


def load_manifest(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def cell_of(manifest: Dict[str, Any], workload: str) -> Dict[str, Any]:
    for cell in manifest["workloads"]:
        if cell["name"] == workload:
            return cell
    names = [c["name"] for c in manifest["workloads"]]
    raise KeyError(f"no workload {workload!r} in the manifest (has {names})")


def metrics_of(manifest: Dict[str, Any], workload: str, trace: bool) -> List[Dict[str, Any]]:
    """The metrics this cell reports in this mode: its ``per_layer`` metrics
    traced, its ``end_to_end`` metrics otherwise. A metric without a
    ``workloads`` key belongs to every cell."""
    group = manifest["per_layer" if trace else "end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def _finite_number(x: Any) -> bool:
    return (
        isinstance(x, (int, float)) and not isinstance(x, bool)
        and math.isfinite(x)
    )


def check_line(manifest: Dict[str, Any], workload: str, trace: bool,
               line: Dict[str, Any]) -> List[str]:
    """Every way in which ``line`` breaks the contract; empty = fine."""
    bad: List[str] = []
    if not isinstance(line, dict):
        return ["the line is not a JSON object"]
    for k in REQUIRED_KEYS:
        if k not in line:
            bad.append(f"key {k!r} is missing")
    for k in line:
        if k not in ALLOWED_KEYS:
            bad.append(f"key {k!r} is not one the contract names")
    if "breakdown" in line and not trace:
        bad.append("'breakdown' belongs to a traced run only")
    if bad:
        return bad
    if not isinstance(line["correct"], bool):
        bad.append("'correct' is not true or false")
    for k in ("attempted", "failed"):
        v = line[k]
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            bad.append(f"{k!r} is not a whole number >= 0")
    if not bad and line["failed"] > line["attempted"]:
        bad.append("'failed' is above 'attempted'")

    metrics = line["metrics"]
    if not isinstance(metrics, dict):
        bad.append("'metrics' is not an object")
        metrics = {}
    wanted = {m["name"]: m for m in metrics_of(manifest, workload, trace)}
    for name, spec in wanted.items():
        got = metrics.get(name)
        if got is None:
            bad.append(f"metric {name!r} is listed for this cell and missing")
            continue
        if not isinstance(got, dict) or set(got) != {"value", "unit"}:
            bad.append(f"metric {name!r} is not {{value, unit}}")
            continue
        if not _finite_number(got["value"]):
            bad.append(f"metric {name!r} has the value {got['value']!r}, not a finite number")
        if got["unit"] != spec["unit"]:
            bad.append(f"metric {name!r} has the unit {got['unit']!r}, the manifest says {spec['unit']!r}")
    for name in metrics:
        if name not in wanted:
            bad.append(f"metric {name!r} is not listed for this cell in this mode")

    dev = line["device"]
    if not isinstance(dev, dict):
        return bad + ["'device' is not an object"]
    for k in DEVICE_KEYS + (TRACED_DEVICE_KEYS if trace else ()):
        if k not in dev:
            bad.append(f"device.{k} is missing")
    for k in ("platform", "kind"):
        if k in dev and not (isinstance(dev[k], str) and dev[k]):
            bad.append(f"device.{k} is not a string")
    for k in ("count", "memory_peak_bytes"):
        if k in dev and not (isinstance(dev[k], int) and not isinstance(dev[k], bool) and dev[k] > 0):
            bad.append(f"device.{k} is not a whole number above 0")
    if trace and all(k in dev for k in TRACED_DEVICE_KEYS):
        w, b = dev["window_s"], dev["busy_s"]
        if not (_finite_number(w) and _finite_number(b)):
            bad.append("device.window_s / busy_s are not finite numbers")
        elif not (0 < b <= w):
            bad.append(f"device.busy_s {b!r} is not above 0 and at most window_s {w!r}")

    if "breakdown" in line:
        br = line["breakdown"]
        if not isinstance(br, dict) or set(br) - {"device_ops", "idle_gaps"}:
            bad.append("'breakdown' holds other keys than device_ops and idle_gaps")
        else:
            for k, rows in br.items():
                if not isinstance(rows, list) or len(rows) > 10:
                    bad.append(f"breakdown.{k} is not a list of at most 10 entries")
                    continue
                for row in rows:
                    if not (isinstance(row, list) and len(row) == 2
                            and isinstance(row[0], str) and _finite_number(row[1])):
                        bad.append(f"breakdown.{k} entry {row!r} is not [name, seconds]")
    return bad


def dumps(line: Dict[str, Any]) -> str:
    """The line as printed: one line, and never ``NaN`` or ``Infinity``."""
    return json.dumps(line, allow_nan=False, separators=(", ", ": "))
