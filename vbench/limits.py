#!/usr/bin/env python3
"""Read the two ends that a cell's correctness limit is set between.

    python3 vbench/limits.py --workload <name> --seeds 1,2,3 --seconds 3

In one process, for each seed: a run of the cell at its own load and
sizes (a short window), whose answers give the program's reading, and
the control on the same weights and images, which gives the control's
reading.  The control is the plain reference put in the program's place
and computed in the configuration's ``control`` precision, the next
below the one it states.  The lower end of a limit is the largest
reading of the program over a dozen seeds or more; the upper end is the
smallest reading of the control.  Prints one JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def control_reading(cell, params, bank) -> dict:
    """Each compared number of the control against the reference, over
    the bank."""
    import numpy as np
    from vbench import harness
    corr = cell.config["correct"]
    n_cal = int(cell.config.get("calib_images", 8))
    reference = harness.reference_logits(cell, params, bank)
    control = harness.reference_logits(cell, params, bank,
                                       precision=corr["control"],
                                       calib=bank[:n_cal])
    return harness.gaps(cell, control, np.arange(len(bank)), reference)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from vbench import env
    env.setup(ROOT)
    from vbench import harness, spec

    cell = spec.load_cell(ROOT, args.workload)
    limits = cell.config["correct"]["limits"]
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(ROOT, args.workload, seed, args.seconds,
                               False)
        row = {"seed": seed,
               "program": {k: out.checks[k]["value"] for k in limits},
               "answers": int(len(out.answers)),
               "unanswered": out.failed,
               "control": control_reading(cell, out.params, out.bank)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({
        "workload": args.workload,
        "lower": {k: max(r["program"][k] for r in rows) for k in limits},
        "upper": {k: min(r["control"][k] for r in rows) for k in limits},
        "limit": limits}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
