"""Medians and spreads of ``many.py``'s runs: ``python3 benchmarks/spread.py
chiprun_out/<set>.jsonl [...]``. A spread is the distance between the first
and the third quartile as ``statistics.quantiles(values, n=4)`` gives them,
as a share of the median — the builder's contract's rule for a bound (about
five times the widest spread over the cells, never under 1%). Also reads the
``latency statistics`` line of each run from the set's ``.log``."""
import json
import statistics
import sys


def spread(values):
    q = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q[2] - q[0]) / med if med else float("nan")


def flatten(d, prefix=""):
    for k, v in d.items():
        if isinstance(v, dict):
            yield from flatten(v, f"{prefix}{k}.")
        elif isinstance(v, (int, float)) and v is not None:
            yield prefix + k, v


def main():
    for path in sys.argv[1:]:
        runs = [json.loads(x) for x in open(path)]
        good = [r for r in runs if r["rc"] == 0 and r["line"]]
        print(f"== {path}: {len(good)} of {len(runs)} runs ok; correct {[r['line']['correct'] for r in good]}; "
              f"failed {[r['line']['failed'] for r in good]}; attempted {[r['line']['attempted'] for r in good]}")
        cols = {}
        for r in good:
            for name, m in r["line"]["metrics"].items():
                cols.setdefault(name, []).append(m["value"])
            dev = r["line"]["device"]
            for k in ("memory_peak_bytes", "busy_s", "window_s"):
                if k in dev:
                    cols.setdefault("device." + k, []).append(dev[k])
        try:
            for x in open(path.replace(".jsonl", ".log")):
                if "latency statistics" in x:
                    for k, v in flatten(json.loads(x.split("(not the result): ", 1)[1])):
                        cols.setdefault("# " + k, []).append(v)
        except OSError:
            pass
        for name, v in cols.items():
            if len(v) >= 3:
                print(f"  {name:34s} n={len(v)} median {statistics.median(v):.6g}  spread {100 * spread(v):.2f}%  "
                      f"min {min(v):.6g} max {max(v):.6g}")
            else:
                print(f"  {name:34s} {v}")


if __name__ == "__main__":
    main()
