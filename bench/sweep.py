#!/usr/bin/env python3
"""Find an open-loop cell's knee: one run per offered rate, in one
process, on the chip.

    python3 bench/sweep.py --workload <name> --rates 0.2,0.4 --seconds 45

The rate is the mix's ``arrivals.rate`` (sessions/s).  For each rate it
prints what the window held (``run.window_summary``): requests sent,
finished and cut at the deadline, the output rate and the tails.  Below
the knee every request finishes and the output rate follows the offered
load; above it the rate stays flat, requests are cut, and the tails
grow with the queue.  The cell's rate is then fixed in its mix file.
"""

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import cell, run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from repro.launch import compile_cache
    compile_cache.enable()
    import jax
    if jax.devices()[0].platform != "tpu":
        sys.exit("sweep: runs on the chip only")
    w = cell.workload(args.workload)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = cell.traffic(w["traffic"])
        mix["arrivals"] = dict(mix["arrivals"], rate=rate)
        r = run.run_cell(args.workload, args.seed, args.seconds, False,
                         mix=mix)
        print(json.dumps({"rate": rate, "correct": r["correct"],
                          "failed": r["failed"], **r["window"]}),
              flush=True)


if __name__ == "__main__":
    main()
