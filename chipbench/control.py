"""Readings of the control of ``correct``: the plain reference at
bfloat16, the next precision below the configuration's float32, answers
in the program's place, for the same sampled answers a run compares.

  python3 chipbench/control.py --workload tr-day.sssp-stream \
      --seconds 15 --seeds 11 12 13

Each seed is one whole run of the cell (deploy, warm-up, window) whose
sampled answers are then replaced by the control's; one JSON line per
seed gives the numbers compared and their limits.  The limits in the
traffic files lie between these readings and the program's own.  The
benchmark's runs never call it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import ml_dtypes  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import run as R  # noqa: E402


def bfloat16(a: np.ndarray) -> np.ndarray:
    """Round to bfloat16 and back: one rounding per stored value."""
    a = np.asarray(a)
    return a.astype(ml_dtypes.bfloat16).astype(a.dtype)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        res = R.run_cell(args.workload, seed, args.seconds, False,
                         control=bfloat16)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": "bfloat16",
                          "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
