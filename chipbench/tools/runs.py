"""Run one cell several times, one process each, and summarise.

    chiprun --chips N -- python3 -m chipbench.tools.runs --workload W \
        --seeds 11,12,13 --seconds 30 [--trace 1] [--sets 2]

This parent never touches JAX (a chip belongs to one process).  Every
run's whole output goes to ``chiprun_out/runs/<tag>/``; the last lines
and, per set, the median and quartile spread of each metric
(``statistics.quantiles(n=4)``, as the bounds are set) are printed.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

from chipbench import stats


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--tag", default=None)
    ap.add_argument("--control", default=None,
                    help="pass --control to the first --control-first runs")
    ap.add_argument("--control-first", type=int, default=0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    tag = args.tag or f"{args.workload}.t{args.trace}"
    out_dir = os.path.join("chiprun_out", "runs", tag)
    os.makedirs(out_dir, exist_ok=True)
    sets, rc = [], 0
    for k in range(args.sets):
        rows = []
        for i, seed in enumerate(seeds):
            cmd = [sys.executable, "-m", "chipbench.run", "--workload",
                   args.workload, "--seed", str(seed), "--seconds",
                   str(args.seconds), "--trace", str(args.trace)]
            if args.control and k == 0 and i < args.control_first:
                cmd += ["--control", args.control]
            t0 = time.time()
            proc = subprocess.run(cmd, capture_output=True, text=True)
            wall = time.time() - t0
            with open(os.path.join(out_dir, f"set{k}_seed{seed}.out"),
                      "w") as f:
                f.write(proc.stdout + "\n--- stderr ---\n" + proc.stderr)
            last = (proc.stdout.strip().splitlines() or [""])[-1]
            print(f"[run set={k} seed={seed} rc={proc.returncode} "
                  f"wall={wall:.1f}s] {last}", flush=True)
            for line in proc.stdout.splitlines():
                if any(t in line for t in ("[check]", "[setup]", "[roofline]",
                                            "[control")):
                    print("    " + line, flush=True)
            if proc.returncode != 0:
                rc = 1
                print(proc.stderr[-3000:], flush=True)
                continue
            rows.append(json.loads(last))
        sets.append(rows)
    for k, rows in enumerate(sets):
        names = sorted({n for r in rows for n in r["metrics"]})
        for n in names:
            vals = [r["metrics"][n]["value"] for r in rows
                    if n in r["metrics"]]
            if len(vals) >= 2:
                print(f"[summary set={k}] {n}: median "
                      f"{stats.median(vals):.6g} spread "
                      f"{100 * stats.quartile_spread(vals):.3f}% of the "
                      f"median, min {min(vals):.6g} max {max(vals):.6g} "
                      f"n={len(vals)}", flush=True)
        print(f"[summary set={k}] correct: "
              f"{[r['correct'] for r in rows]}", flush=True)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
