"""Find an open-loop cell's knee: one set-up, then one window per offered
rate, at steps of about 1.5x.

    python3 benchmark/sweep.py --workload <cell> --seed <n> \\
        --seconds 10 --start 2000 --steps 14

The knee is the highest offered rate at which the achieved rate stays at
or above 98% of the offered rate, nothing is shed or fails, the backlog
does not grow (the mean latency of the window's last quarter is under
twice that of its first quarter plus 1 ms), and nothing stalls (no push
leaves the generator more than ``STALL_MS`` late; pauses of about
115 ms, in which the whole machine stops, come at every rate and are
no stall of the system under test).  Each row
also says how late the generator ran and why (``pace``).  The cell's
traffic file takes four fifths of it as its ``rate``.  Prints one JSON
line per rate and, last, the knee.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.cell import Cell  # noqa: E402

STALL_MS = 250.0


def sustained(row: dict) -> bool:
    return (row["achieved_per_s"] >= 0.98 * row["offered_per_s"]
            and row["failed"] == 0
            and row["last_quarter_ms"] < 2 * row["first_quarter_ms"] + 1.0
            and row["gen_late_max_ms"] < STALL_MS)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--start", type=float, required=True)
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--factor", type=float, default=1.5)
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    run.place_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        run.log(f"no TPU: platform={dev.platform}")
        return 2
    rates = [round(args.start * args.factor ** i)
             for i in range(args.steps)]
    res = run.serve(cell, args.seed, args.seconds, False,
                    rates=",".join(str(r) for r in rates))
    knee = prev = None
    for row in res["got"]["sweep"]:
        row["sustained"] = sustained(row)
        print(json.dumps(row), flush=True)
        if row["sustained"] and (knee is None or knee == prev):
            knee = row["rate"]
        prev = row["rate"]
    print(json.dumps({"workload": args.workload, "knee_per_s": knee,
                      "rate_4_5": None if knee is None else 0.8 * knee,
                      "device": dev.device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
