#!/usr/bin/env python3
"""Readings that set a cell's ``correct`` limit, on the chip.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 20

For each seed, one run of the cell at its own load (one process, so the
programs compile once), then the widest logit gap of the program's
served tokens and of the fp8 control's first choices on the same
sample (``reference.py``), each judged by the run's own verdict.  The
limit in the configuration file must lie above every program reading
and below every control reading, so every line reads ``correct`` true
and ``control_correct`` false; the script exits 1 where one does not.
The benchmark's own runs do not run the control.
"""

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from repro.launch import compile_cache
    compile_cache.enable()
    import jax
    if jax.devices()[0].platform != "tpu":
        sys.exit("control: runs on the chip only")
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        r = run.run_cell(args.workload, seed, args.seconds, False,
                         control=True)
        c = r["control"]
        ok &= r["correct"] and not c["correct"]
        print(json.dumps({
            "seed": seed, "correct": r["correct"],
            "control_correct": c["correct"], "attempted": r["attempted"],
            "program": r["checks"]["max_logit_gap"]["value"],
            "control": c["checks"]["max_logit_gap"]["value"],
            "limit": r["checks"]["max_logit_gap"]["limit"],
            "metrics": r["metrics"]}), flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
