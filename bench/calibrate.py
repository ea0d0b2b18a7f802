"""Readings that the ``logit_gap`` limit is set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10 \\
        [--controls int8,fp8] [--out chiprun_out/cal.jsonl]

For each seed, in this one process: one run of the cell through the timed
path (a short window at the cell's own load), then the widest gap of the
program's served tokens against the plain reference and, at the same
positions, of the token each lower-precision control puts first. The lower
reading of the limit is the largest program gap over a dozen seeds or more;
the upper is the smallest control gap.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--controls", default="int8,fp8")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    controls = tuple(c for c in args.controls.split(",") if c)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(args.workload, seed, args.seconds, False,
                           controls=controls)
        row = {"seed": seed, "readings": out.get("readings"),
               "checks": out["checks"], "metrics": out["metrics"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    prog = [r["readings"]["program"] for r in rows if r["readings"]]
    summary = {"lower": max(prog, default=None)}
    for c in controls:
        vals = [r["readings"][c] for r in rows if r["readings"]]
        summary[f"upper_{c}"] = min(vals, default=None)
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
