#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 vbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout.  The cell, its configuration, its
traffic mix and its metrics are found by name from ``BENCHMARK.json``.
The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and with
``--trace 1``, ``breakdown``), and last ``checks``: each number compared
with the reference, beside its limit.  The same numbers are the last
lines of standard error.  A traced run measures a window of at most
``harness.TRACE_SECONDS``.  Without a TPU, or with fewer chips than the
cell asks for, it prints no result and exits with code 3.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from vbench import env
    env.setup(ROOT)
    from vbench import harness
    harness.program_path()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    try:
        out = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                               bool(args.trace), t_process=T_PROCESS)
    except harness.NoChip as e:
        print(f"vbench: {e}", file=sys.stderr)
        return 3
    for note in out.notes:
        print(f"vbench: {note}", flush=True)
    correct = harness.passed(out.checks)
    for name, c in out.checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    line = {"correct": correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": out.metrics,
            "device": out.device}
    if out.breakdown is not None:
        line["breakdown"] = out.breakdown
    line["checks"] = out.checks
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
