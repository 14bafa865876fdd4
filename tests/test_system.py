"""End-to-end system behaviour: the full drivers (train + serve) run
through their public CLIs, and the dry-run machinery works on a small
simulated mesh (the 512-device production sweep runs via
``python -m repro.launch.dryrun --all``; see EXPERIMENTS.md)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from _subproc import check, SRC


def _run_module(args, env_extra=None, timeout=420):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    if env_extra:
        env.update(env_extra)
    return subprocess.run([sys.executable, "-m"] + args, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_train_driver_end_to_end(tmp_path):
    r = _run_module(["repro.launch.train", "--arch", "minitron-4b",
                     "--smoke", "--steps", "12", "--batch", "2",
                     "--seq", "32", "--ckpt-dir", str(tmp_path),
                     "--ckpt-every", "6", "--log-every", "5"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "loss" in r.stdout
    assert os.path.exists(os.path.join(str(tmp_path), "LATEST"))
    # resume continues from the checkpoint
    r2 = _run_module(["repro.launch.train", "--arch", "minitron-4b",
                      "--smoke", "--steps", "14", "--batch", "2",
                      "--seq", "32", "--ckpt-dir", str(tmp_path),
                      "--resume", "--log-every", "5"])
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "resumed from step 12" in r2.stdout


def test_serve_driver_end_to_end():
    r = _run_module(["repro.launch.serve", "--arch", "minitron-4b",
                     "--smoke", "--requests", "4", "--slots", "2",
                     "--prompt-len", "8", "--max-new", "6",
                     "--max-seq", "32"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "4 requests" in r.stdout


def test_dryrun_machinery_small_mesh():
    """lower+compile+roofline on an 8-device simulated mesh for a smoke
    config — the exact code path the 512-device production sweep uses."""
    out = check("""
import dataclasses, json
import jax
from repro.configs import get, SHAPES
from repro.configs.base import smoke_variant, ShapeCfg
from repro.launch import dryrun
from repro.models import registry
from repro.roofline import analysis as RA

cfg = smoke_variant(get("minitron-4b"))
shape = ShapeCfg("train_tiny", "train", 64, 8)
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
compiled, dt = dryrun.lower_cell(cfg, shape, mesh)
roof = RA.analyze(compiled, cfg, shape, "4x2", 8,
                  registry.num_active_params(cfg),
                  device_kind="TPU v5 lite")
rec = roof.to_dict(8)
assert rec["flops_per_device"] > 0
assert rec["bottleneck"] in ("compute", "memory", "collective")
print("OK", rec["bottleneck"])
""")
    assert "OK" in out


def test_production_dryrun_artifacts_exist():
    """The full 512-device sweeps are run offline (they take ~1h on this
    1-core container); their artifacts must exist and be green."""
    for f in ("results_dryrun_single.json", "results_dryrun_multipod.json"):
        path = os.path.join(os.path.dirname(__file__), "..", f)
        if not os.path.exists(path):
            pytest.skip(f"{f} not generated yet")
        d = json.load(open(path))
        assert len(d["failures"]) == 0, d["failures"]
        assert len(d["results"]) == 33
