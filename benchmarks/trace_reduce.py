"""From a profiler trace to numbers: the one reduction every PR shares.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
``load_xplane`` reads it with ``jax.profiler.ProfileData`` into plain lists
(the form the recorded trace under ``tests/data`` is kept in), and the
functions below reduce that form. Which plane and which line of it hold what
is data (``trace_layout.json``), written after looking at a real trace by
hand; nothing here names a cell, a model or a metric.

Clock: every plane's events carry nanoseconds on the trace's one timebase.
The traced sub-window is the extent of the benchmark's own marker event
(``TraceAnnotation`` in ``run.py``, on a host plane); device events are
clipped to it, so ``busy <= window`` by construction.

``python benchmarks/trace_reduce.py --dump <dir-or-xplane.pb>`` prints the
planes, the lines and each line's heaviest event names: how to look at a
trace by hand.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
from typing import Any, Dict, Iterable, List, Optional, Tuple

_HERE = os.path.dirname(os.path.abspath(__file__))

Interval = Tuple[int, int]          # [start_ns, end_ns)
Event = Tuple[str, int, int]        # name, start_ns, duration_ns


class NoDeviceEvents(RuntimeError):
    """The trace holds no device operation inside the window."""


def load_layout(path: Optional[str] = None) -> Dict[str, Any]:
    with open(path or os.path.join(_HERE, "trace_layout.json")) as f:
        return json.load(f)


def find_xplane(trace_dir: str) -> str:
    if os.path.isfile(trace_dir):
        return trace_dir
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str, layout: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """The trace as plain data: ``{"planes": [{"name", "lines": [{"name",
    "events": [[name, start_ns, duration_ns], ...]}]}]}``. Of the host
    planes only the benchmark's marker events are kept (they are large), of
    a device plane only the lines the layout names."""
    import jax

    layout = layout or load_layout()
    dev_re = re.compile(layout["device_plane"])
    wanted = re.compile(f"(?:{layout['ops_line']})|(?:{layout['modules_line']})")
    marker = layout["marker"]
    data = jax.profiler.ProfileData.from_file(find_xplane(path))
    planes = []
    for plane in data.planes:
        is_dev = bool(dev_re.match(plane.name))
        lines = []
        for line in plane.lines:
            if is_dev and not wanted.search(line.name):
                continue  # e.g. the copies in flight: large, and not busy time
            events = []
            for ev in line.events:
                if is_dev or ev.name.startswith(marker):
                    events.append([short_name(ev.name), int(ev.start_ns), int(ev.duration_ns)])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def short_name(name: str) -> str:
    """The op line of a TPU trace names an event by its whole HLO text,
    ``%fusion.12 = bf16[...] fusion(...)``; the instruction's name is enough."""
    return name.split(" = ", 1)[0].lstrip("%") if " = " in name else name


def stem(name: str) -> str:
    """``paged_decode_attention.263`` -> ``paged_decode_attention``: the same
    op of every layer under one name."""
    return re.sub(r"[.\d]+$", "", name) or name


def leaves(events: List[Event]) -> List[Event]:
    """Events that hold no other event. The op line nests: a ``while`` or a
    ``conditional`` spans the ops of its body, and counting the wrapper would
    call the gaps between those ops busy."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    has_child = [False] * len(order)
    stack: List[int] = []
    for i, (_, s, d) in enumerate(order):
        while stack and order[stack[-1]][1] + order[stack[-1]][2] <= s:
            stack.pop()
        if stack and s + d <= order[stack[-1]][1] + order[stack[-1]][2]:
            has_child[stack[-1]] = True
        stack.append(i)
    return [e for e, c in zip(order, has_child) if not c]


# -- interval arithmetic ---------------------------------------------------

def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    out = []
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out.append((a, b))
    return out


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Overlapping or touching intervals merged; sorted."""
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def union_ns(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in merge(intervals))


def gaps(merged: List[Interval], lo: int, hi: int) -> List[Interval]:
    """The parts of ``[lo, hi)`` that ``merged`` (sorted, disjoint) leaves."""
    out, cur = [], lo
    for a, b in merged:
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        out.append((cur, hi))
    return out


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The common part of two sorted lists of disjoint intervals (one pass)."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


def total_ns(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in intervals)


# -- the trace's parts -----------------------------------------------------

def device_planes(trace: Dict[str, Any], layout: Dict[str, Any]) -> List[Dict[str, Any]]:
    dev_re = re.compile(layout["device_plane"])
    found = [(int(dev_re.match(p["name"]).group(1) or 0), p)
             for p in trace["planes"] if dev_re.match(p["name"])]
    return [p for _, p in sorted(found, key=lambda t: t[0])]


def line_events(plane: Dict[str, Any], line_pattern: str,
                rename: Optional[List[str]] = None) -> List[Event]:
    """The events of the plane's lines whose name matches ``line_pattern``
    (a regular expression; on a TPU plane it names exactly one line)."""
    rx = re.compile(line_pattern)
    out: List[Event] = []
    for line in plane["lines"]:
        if rx.search(line["name"]):
            out.extend((e[0], int(e[1]), int(e[2])) for e in line["events"])
    if rename:
        rn = re.compile(rename[0])
        out = [(rn.sub(rename[1], n), s, d) for n, s, d in out if rn.search(n)]
    return out


def marker_window(trace: Dict[str, Any], layout: Dict[str, Any]) -> Interval:
    """The extent of the benchmark's marker event: the traced sub-window."""
    marker = layout["marker"]
    for plane in trace["planes"]:
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(marker) and dur > 0:
                    return int(start), int(start) + int(dur)
    raise NoDeviceEvents(f"the trace holds no {marker!r} marker event")


class Reduced:
    """One trace, reduced. Times in seconds unless a name says ``_ns``."""

    def __init__(self, trace: Dict[str, Any], layout: Optional[Dict[str, Any]] = None,
                 window: Optional[Interval] = None):
        self.layout = layout = layout or load_layout()
        self.lo, self.hi = window or marker_window(trace, layout)
        planes = device_planes(trace, layout)
        if not planes:
            raise NoDeviceEvents(
                f"no plane matches {layout['device_plane']!r}: the trace has "
                f"{[p['name'] for p in trace['planes']]}"
            )
        self.n_devices = len(planes)
        busy = []
        for p in planes:
            ops = leaves(line_events(p, layout["ops_line"]))
            busy.append(union_ns(clip(((s, s + d) for _, s, d in ops), self.lo, self.hi)))
        if not any(busy):
            raise NoDeviceEvents(
                f"no event on line {layout['ops_line']!r} of any device plane "
                f"falls inside the traced window"
            )
        self.window_s = (self.hi - self.lo) / 1e9
        self.busy_s = sum(busy) / len(busy) / 1e9
        self.busy_s_per_device = [b / 1e9 for b in busy]
        # detail from device 0: SPMD devices run the same program
        p0 = planes[0]
        self.ops: List[Event] = self._inside(leaves(line_events(p0, layout["ops_line"])))
        self.modules: List[Event] = self._inside(
            line_events(p0, layout["modules_line"], layout.get("modules_rename")))
        self.busy0_s = busy[0] / 1e9

    def _inside(self, events: List[Event]) -> List[Event]:
        """Events clipped to the window (duration shortened at its edges)."""
        out = []
        for name, s, d in events:
            a, b = max(s, self.lo), min(s + d, self.hi)
            if b > a:
                out.append((name, a, b - a))
        return out

    # kernels and programs by name ------------------------------------------
    def op_seconds(self, pattern: str) -> float:
        """Summed device time of the ops whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(d for n, _, d in self.ops if rx.search(n)) / 1e9

    def op_count(self, pattern: str) -> int:
        rx = re.compile(pattern)
        return sum(1 for n, _, _ in self.ops if rx.search(n))

    def module_durations_s(self, pattern: str, whole_only: bool = True) -> List[float]:
        """Device time of each execution of the programs whose name matches.
        ``whole_only`` leaves out executions cut by the window's edges."""
        rx = re.compile(pattern)
        out = []
        for n, s, d in self.modules:
            if not rx.search(n):
                continue
            if whole_only and (s <= self.lo or s + d >= self.hi):
                continue
            out.append(d / 1e9)
        return out

    def top_ops(self, k: int = 10) -> List[List[Any]]:
        total: Dict[str, int] = {}
        for n, _, d in self.ops:
            total[stem(n)] = total.get(stem(n), 0) + d
        rows = sorted(total.items(), key=lambda t: -t[1])[:k]
        return [[n, d / 1e9] for n, d in rows]

    def idle_by_class(self, in_flight: Optional[List[Interval]] = None) -> List[List[Any]]:
        """Device 0's idle time in the window, classed as far as can be seen
        from outside the program: ``in_step`` (inside an execution of a
        program, between its ops), ``between_steps`` (between executions,
        while a request was in flight) and ``no_request`` (none in flight;
        ``in_flight`` is on the trace's clock). Heaviest class first."""
        busy = merge((s, s + d) for _, s, d in self.ops)
        idle = gaps(busy, self.lo, self.hi)
        mods = merge((s, s + d) for _, s, d in self.modules)
        flight = merge(clip(in_flight, self.lo, self.hi)) if in_flight is not None else [(self.lo, self.hi)]
        inside = intersect(idle, mods)
        outside = intersect(idle, gaps(mods, self.lo, self.hi))
        with_req = total_ns(intersect(outside, flight))
        total = {"in_step": total_ns(inside), "between_steps": with_req,
                 "no_request": total_ns(outside) - with_req}
        rows = sorted(total.items(), key=lambda t: -t[1])
        return [[n, d / 1e9] for n, d in rows]


# -- looking at a trace by hand ----------------------------------------------

def dump(path: str, out=sys.stdout, top: int = 25, save_small: Optional[str] = None) -> None:
    import jax

    data = jax.profiler.ProfileData.from_file(find_xplane(path))
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines", file=out)
        for line in lines:
            total: Dict[str, List[int]] = {}
            n = 0
            first = last = None
            sample = None
            for ev in line.events:
                n += 1
                t = total.setdefault(short_name(ev.name), [0, 0])
                t[0] += int(ev.duration_ns)
                t[1] += 1
                first = ev.start_ns if first is None else min(first, ev.start_ns)
                last = ev.start_ns + ev.duration_ns if last is None else max(last, ev.start_ns + ev.duration_ns)
                if sample is None:
                    try:
                        sample = {k: (v if isinstance(v, (int, float)) else str(v)[:120])
                                  for k, v in ev.stats}
                    except Exception as e:  # stats are optional reading
                        sample = {"stats_error": repr(e)}
            if not n:
                continue
            print(f"  LINE {line.name!r}: {n} events, {len(total)} names, "
                  f"extent {(last - first) / 1e9:.4f} s, first stats {sample}", file=out)
            for name, (dur, cnt) in sorted(total.items(), key=lambda t: -t[1][0])[:top]:
                print(f"    {dur / 1e9:10.6f} s  x{cnt:<7d} {name[:140]}", file=out)
    if save_small:
        # a recorded slice for the tests: the first 80 ms of the marked window
        layout = load_layout()
        small = load_xplane(path, layout)
        lo, _ = marker_window(small, layout)
        hi = lo + 80_000_000
        for p in small["planes"]:
            for ln in p["lines"]:
                ln["events"] = [
                    [n, lo, hi - lo] if n.startswith(layout["marker"]) else [n, s, d]
                    for n, s, d in ln["events"]
                    if n.startswith(layout["marker"]) or (s < hi and s + d > lo)
                ]
        with open(save_small, "w") as f:
            json.dump(small, f, separators=(",", ":"))


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--dump":
        dump(sys.argv[2], save_small=sys.argv[3] if len(sys.argv) > 3 else None)
    else:
        sys.exit("usage: python benchmarks/trace_reduce.py --dump <dir-or-xplane.pb> [small.json]")
