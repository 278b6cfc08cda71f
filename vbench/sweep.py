#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains, by a sweep on the chip.

    python3 vbench/sweep.py --workload deit_t_int8.poisson --seed 7 \
        --seconds 5 --rates 1000,2000,4000

One process sets the cell up once and offers each rate for a window of
its own.  With ``--fresh`` each rate and seed gets a process and a set-up
of its own instead, as a benchmark run does, so that a rate is judged
from a cold start; the parent never touches JAX.  A rate is sustained
when the backlog does not grow (every
request due in the window is answered by one SLA after it closes, and
the answers keep pace with the arrivals) and the 95th percentile of the
latency is within the SLA.  The rate written into a cell's traffic file
is a fixed number, four fifths of the highest sustained rate.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, requests/s")
    ap.add_argument("--fresh", type=int, default=0, metavar="SEEDS",
                    help="run each rate from a cold start in its own "
                         "process, on this many seeds from --seed on")
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]
    if args.fresh:
        return fresh(args.workload, args.seed, args.fresh, args.seconds,
                     rates)
    sys.path.insert(0, str(ROOT))
    from vbench import env
    env.setup(ROOT)
    sweep(ROOT, args.workload, args.seed, args.seconds, rates)
    return 0


def fresh(workload: str, seed: int, seeds: int, seconds: float,
          rates) -> int:
    import subprocess
    ok = {}
    for rate in rates:
        for k in range(seeds):
            proc = subprocess.run(
                [sys.executable, __file__, "--workload", workload,
                 "--seed", str(seed + k), "--seconds", str(seconds),
                 "--rates", str(rate)], capture_output=True, text=True)
            rows = [json.loads(line) for line in proc.stdout.splitlines()
                    if line.startswith('{"rate"')]
            row = rows[0] if rows else {"rate": rate, "sustained": False,
                                        "error": proc.stderr[-500:]}
            row["seed"] = seed + k
            print(json.dumps(row), flush=True)
            ok[rate] = ok.get(rate, True) and row["sustained"]
    best = max((r for r, good in ok.items() if good), default=None)
    print(json.dumps({"highest_sustained": best,
                      "cell_rate": None if best is None else 0.8 * best}))
    return 0


def sweep(root, workload: str, seed: int, seconds: float, rates):
    """Offer each of ``rates`` in turn to one set-up of ``workload``;
    prints a row per rate and returns the highest sustained rate."""
    from vbench import harness, spec, stats, traffic

    cell = spec.load_cell(root, workload)
    sla = cell.traffic.get("sla_ms")
    s = harness.prepare(cell, seed, int(cell.traffic.get("bank", 64)))
    tracer = harness._Tracer(None)
    rows = []
    for rate in rates:
        mix = dict(cell.traffic, rate_per_s=rate)
        plan = traffic.plan(mix, seed, seconds,
                            cell.config["buckets"])
        n0 = len(s.rec.dispatches)
        window, sent, late = harness.drive(s, plan, tracer)
        due = [r for r in sent if stats.in_window(r.t_submit, window)]
        deadline = window[1] + (sla or 0.0) / 1e3
        lat = stats.late_latencies_ms([r.t_submit for r in due],
                                      [r.t_done for r in due], deadline)
        done_in = sum(1 for r in sent
                      if r.t_done is not None
                      and stats.in_window(r.t_done, window))
        fills = [d for d in s.rec.dispatches[n0:]
                 if stats.in_window(d[0], window)]
        row = {"rate": rate, "due": len(due),
               "answered_per_s": done_in / seconds,
               "p50_ms": stats.percentile(lat, 50),
               "p95_ms": stats.percentile(lat, 95),
               "late_after_deadline": sum(1 for x in lat
                                          if x == float("inf")),
               "generator_late_p95_ms": stats.percentile(late, 95) * 1e3,
               "mean_batch": (sum(d[3] for d in fills) / len(fills)
                              if fills else 0.0)}
        row["sustained"] = (row["late_after_deadline"] == 0
                            and row["p95_ms"] <= (sla or float("inf"))
                            and row["answered_per_s"] >= 0.97 * rate)
        rows.append(row)
        print(json.dumps(row), flush=True)
        if not row["sustained"] and row["p95_ms"] > 10 * (sla or 1e9):
            break
    best = max((r["rate"] for r in rows if r["sustained"]), default=None)
    print(json.dumps({"highest_sustained": best,
                      "cell_rate": None if best is None else 0.8 * best}))
    return best


if __name__ == "__main__":
    sys.exit(main())
