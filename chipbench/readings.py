"""Readings that set a cell's correctness limit, many seeds in one process.

  python3 chipbench/readings.py --workload <name> --seeds 1,2,3 \
      --seconds <s> [--control-bits 4]

For each seed: one run of the cell as ``run.py`` makes it (the same
set-up, window and reference check), printing the widest gap of the
served tokens and, with ``--control-bits``, the widest gap of the tokens
that the control (the reference at that precision, every projection's
weights and activations quantized) ranks first at the same positions,
with the ``correct`` that the cell's own comparison and limits give it.
The lower reading of a limit is the largest program gap over a dozen
seeds or more; the upper, the smallest control gap. The benchmark's own
runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench.cell import resolve  # noqa: E402
from chipbench.run import run_cell  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-bits", type=int, default=None)
    args = ap.parse_args()
    cell = resolve(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run_cell(cell, seed, args.seconds, False,
                       t_start=time.perf_counter(),
                       control_bits=args.control_bits)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "widest_gap": out["checks"]["widest_gap"]["value"],
                          "tokens": out["checks"]["tokens_checked"]["value"],
                          "control_correct": out.get("control_correct"),
                          "control_widest_gap": out.get(
                              "control_checks", {}).get(
                              "widest_gap", {}).get("value"),
                          "metrics": out["metrics"], "run": out["run"]}),
              flush=True)


if __name__ == "__main__":
    main()
