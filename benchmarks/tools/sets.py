"""The builder's sets of runs: one cell, several seeds, each run a process of its own
(this parent never touches JAX, so the chip is the child's). Writes every result
line to ``chiprun_out/<tag>.jsonl`` and prints each metric's values, median and
spread (``statistics.quantiles`` quartiles over the median, and the same with the
farthest run left out).

    python3 benchmarks/tools/sets.py --workload W --seconds 30 --seeds 1,2,3 --tag study30
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmarks import stats  # noqa: E402  (no JAX behind it)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seconds", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--tag", required=True)
    parser.add_argument("--trace", default="0")
    parser.add_argument("--rehearsal", default="0", help="1: try a call's script on the CPU")
    args = parser.parse_args()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out_path = os.path.join(ROOT, "chiprun_out", f"{args.tag}.jsonl")
    values: dict = {}
    for seed in args.seeds.split(","):
        cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload",
               args.workload, "--seed", seed, "--seconds", args.seconds, "--trace", args.trace,
               "--rehearsal", args.rehearsal]
        t = time.perf_counter()
        proc = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT)
        wall = time.perf_counter() - t
        lines = proc.stdout.strip().splitlines()
        if proc.returncode != 0 or not lines:
            print(f"seed {seed}: rc {proc.returncode}\n{proc.stderr[-3000:]}", flush=True)
            continue
        result = json.loads(lines[-1])
        result.update(seed=int(seed), wall_s=wall, tag=args.tag, seconds=float(args.seconds))
        result["earlier_lines"] = lines[:-1][-80:]
        result["stderr_tail"] = [l for l in proc.stderr.splitlines()
                                 if "hugepages" not in l and "warnings.warn" not in l][-12:]
        with open(out_path, "a") as f:
            f.write(json.dumps(result) + "\n")
        shown = {k: round(v["value"], 4) for k, v in result["metrics"].items()}
        compared = {k: v["value"] for k, v in result["compared"].items()}
        print(f"seed {seed}: correct {result['correct']} failed {result['failed']}/"
              f"{result['attempted']} wall {wall:.1f}s {shown} {compared} "
              f"peak {result['device']['memory_peak_bytes'] / 1e9:.3f} GB", flush=True)
        if args.trace == "1":
            print("  device", {k: result["device"].get(k) for k in ("busy_s", "window_s")},
                  "\n  breakdown", json.dumps(result.get("breakdown")), flush=True)
        for k, v in result["metrics"].items():
            values.setdefault(k, []).append(v["value"])
    for k, vs in values.items():
        if len(vs) >= 3 and statistics.median(vs):  # a share that reads 0 has no spread
            print(f"{args.tag} {k}: n {len(vs)} median {statistics.median(vs):.4f} "
                  f"spread {100 * stats.spread(vs):.3f}% without farthest "
                  f"{100 * stats.spread_without_farthest(vs):.3f}% "
                  f"min {min(vs):.4f} max {max(vs):.4f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
