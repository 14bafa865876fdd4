import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"   # a CPU simulation: never the chip

"""Optimized-configuration sweep: every (arch × shape) cell with the
§Perf levers that transferred (EXPERIMENTS.md):

* train/prefill: causal block skipping (+ grouped MoE dispatch for MoE)
* decode: seq-sharded int8 KV cache (+ block skipping for prefill math)

Writes results_dryrun_optimized.json with the same schema as the
baseline sweeps, so the before/after table is a straight join.
"""

import json
import sys
import traceback

from ..configs import ARCHS, SHAPES
from .dryrun import cells, run_cell


def extras_for(cfg, shape):
    e = {}
    if cfg.n_heads:                       # any attention in the stack
        e["attn_block_skip"] = True
    if cfg.n_experts:
        e["moe_groups"] = 32
    if shape.kind == "decode":
        e["decode_seq_shard"] = True
        e["kv_cache_dtype"] = "int8"
    return e


def main():
    out = sys.argv[1] if len(sys.argv) > 1 else "results_dryrun_optimized.json"
    results, failures = [], []
    for cfg, shape in cells():
        extra = extras_for(cfg, shape)
        tag = f"{cfg.name} × {shape.name}"
        try:
            rec = run_cell(cfg, shape, False, extra, verbose=False)
            rec["extras"] = extra
            results.append(rec)
            print(f"PASS {tag} bottleneck={rec['bottleneck']} "
                  f"mfu={rec['mfu_bound']:.4f}", flush=True)
        except Exception as e:
            failures.append((tag, repr(e)))
            print(f"FAIL {tag}: {e}", flush=True)
            traceback.print_exc()
    with open(out, "w") as f:
        json.dump({"results": results, "failures": failures}, f, indent=1)
    print(f"{len(results)} passed, {len(failures)} failed")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
