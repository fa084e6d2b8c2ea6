"""Several runs in one call, one process each (a chip belongs to one process
at a time; this parent never touches JAX). A builder's tool, not the
driver's: ``python3 benchmarks/many.py OUT.jsonl WORKLOAD SECONDS TRACE
SEED [SEED ...] [-- extra run.py arguments]``. Each run's last line goes to
``chiprun_out/OUT.jsonl`` with its seed, exit code and wall time, and the
``#`` lines of each run to ``chiprun_out/OUT.log``."""
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main() -> None:
    args = sys.argv[1:]
    extra = []
    if "--" in args:
        i = args.index("--")
        args, extra = args[:i], args[i + 1:]
    out, workload, seconds, trace, *seeds = args
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    jl = open(os.path.join(ROOT, "chiprun_out", out + ".jsonl"), "a")
    log = open(os.path.join(ROOT, "chiprun_out", out + ".log"), "a")
    for seed in seeds:
        t = time.monotonic()
        p = subprocess.run(
            [sys.executable, os.path.join(HERE, "run.py"), "--workload", workload, "--seed", seed,
             "--seconds", seconds, "--trace", trace, *extra],
            cwd=ROOT, capture_output=True, text=True,
        )
        wall = time.monotonic() - t
        lines = p.stdout.strip().splitlines()
        last = lines[-1] if lines else ""
        try:
            parsed = json.loads(last) if last.startswith("{") else None
        except ValueError:
            parsed = None
        rec = {"workload": workload, "seed": int(seed), "trace": int(trace), "rc": p.returncode,
               "wall_s": wall, "line": parsed}
        jl.write(json.dumps(rec) + "\n")
        jl.flush()
        log.write(f"==== {workload} seed {seed} trace {trace} rc {p.returncode} wall {wall:.1f}s\n")
        log.write("\n".join(x[:3000] for x in lines[:-1] if x.startswith("#")) + "\n")
        if p.returncode != 0 or parsed is None:
            log.write("STDERR TAIL:\n" + p.stderr[-6000:] + "\n")
        log.flush()
        brief = {k: v["value"] for k, v in (parsed or {}).get("metrics", {}).items()}
        print(f"{workload} seed {seed} trace {trace} rc {p.returncode} wall {wall:.1f}s "
              f"correct {(parsed or {}).get('correct')} failed {(parsed or {}).get('failed')}/"
              f"{(parsed or {}).get('attempted')} {json.dumps(brief)}", flush=True)


if __name__ == "__main__":
    main()
