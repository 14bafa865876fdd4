import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"   # a CPU simulation: never the chip

"""Multi-pod dry-run (assignment contract).

Lowers + compiles every (architecture × input shape) cell against the
production meshes — 16×16 single-pod and 2×16×16 multi-pod — using
ShapeDtypeStruct inputs (no allocation), prints memory/cost analysis,
and records roofline terms to JSON for EXPERIMENTS.md.

The lines above MUST precede any jax import (device count locks at
first init); this env var is deliberately NOT set anywhere global.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1p5-32b \
        --shape train_4k [--multi-pod] [--out results.json]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""

import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from ..configs import ARCHS, SHAPES, LONG_CONTEXT_ARCHS, get as get_arch
from ..models import registry
from ..models import params as PP
from ..roofline import analysis as RA
from ..train import train_loop as TL
from ..serve import serve_loop as SL
from .mesh import PRODUCTION_DEVICE_KIND, make_production_mesh


def cells(only_arch=None, only_shape=None):
    for name, cfg in ARCHS.items():
        if only_arch and name != only_arch:
            continue
        for sname, shape in SHAPES.items():
            if only_shape and sname != only_shape:
                continue
            if sname == "long_500k" and name not in LONG_CONTEXT_ARCHS:
                continue  # no sub-quadratic path (DESIGN §7)
            yield cfg, shape


def lower_cell(cfg, shape, mesh, extra_cfg=None):
    """Build + lower + compile one cell; returns (compiled, seconds)."""
    import dataclasses
    if extra_cfg:
        cfg = dataclasses.replace(cfg, **extra_cfg)
    t0 = time.time()
    with jax.sharding.set_mesh(mesh):
        if shape.kind == "train":
            # Production train-cell settings: 4 microbatches (activation
            # memory), ZeRO-1 moment sharding, bf16 gradient reduction.
            tcfg = TL.TrainCfg(grad_accum=4, zero1=True, compress_grads=True)
            fn, _, (ab_params, _) = TL.make_train_step(cfg, tcfg, mesh=mesh)
            ab_opt = TL.abstract_opt_state(ab_params)
            batch = registry.input_specs(cfg, shape)
            lowered = fn.lower(ab_params, ab_opt, batch)
        elif shape.kind == "prefill":
            pre, _, _, _ = SL.make_serve_steps(cfg, shape.global_batch,
                                               shape.seq_len, mesh)
            ab_params = PP.abstract_params(registry.decls(cfg))
            batch = registry.input_specs(cfg, shape)
            lowered = pre.lower(ab_params, batch)
        else:  # decode
            _, dec, ab_cache, _ = SL.make_serve_steps(
                cfg, shape.global_batch, shape.seq_len, mesh)
            ab_params = PP.abstract_params(registry.decls(cfg))
            batch = registry.input_specs(cfg, shape)
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            lowered = dec.lower(ab_params, ab_cache, batch, pos)
        compiled = lowered.compile()
    return compiled, time.time() - t0


def run_cell(cfg, shape, multi_pod: bool, extra_cfg=None, verbose=True):
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = 512 if multi_pod else 256
    mesh_name = "2x16x16" if multi_pod else "16x16"
    compiled, dt = lower_cell(cfg, shape, mesh, extra_cfg)
    roof = RA.analyze(compiled, cfg, shape, mesh_name, n_chips,
                      registry.num_active_params(cfg),
                      device_kind=PRODUCTION_DEVICE_KIND)
    rec = roof.to_dict(n_chips)
    rec["compile_seconds"] = dt
    if verbose:
        try:
            print(compiled.memory_analysis())
        except Exception as e:  # pragma: no cover
            print("memory_analysis unavailable:", e)
        print(json.dumps({k: v for k, v in rec.items()
                          if k != "collectives"}, indent=1))
        print("collectives:", rec["collectives"])
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=[None] + list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--quiet", action="store_true")
    ap.add_argument("--extra", default=None,
                    help="JSON dict of ModelConfig overrides (perf exps)")
    args = ap.parse_args()

    if not args.all and not args.arch:
        ap.error("pass --arch <id> or --all")
    arch = get_arch(args.arch).name if args.arch else None
    extra = json.loads(args.extra) if args.extra else None

    results, failures = [], []
    for cfg, shape in cells(arch, args.shape):
        tag = f"{cfg.name} × {shape.name} × " \
              f"{'2x16x16' if args.multi_pod else '16x16'}"
        try:
            rec = run_cell(cfg, shape, args.multi_pod, extra,
                           verbose=not args.quiet)
            results.append(rec)
            print(f"PASS {tag}  compile={rec['compile_seconds']:.1f}s "
                  f"bottleneck={rec['bottleneck']}", flush=True)
        except Exception as e:
            failures.append((tag, repr(e)))
            print(f"FAIL {tag}: {e}", flush=True)
            if not args.quiet:
                traceback.print_exc()
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"results": results, "failures": failures}, f, indent=1)
    print(f"\n{len(results)} passed, {len(failures)} failed")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
