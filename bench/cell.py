"""Finding a cell's parts by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix.  A
configuration is ``configs/<name>.json``: the published config's sizes
under the source's own keys, as they are run, plus a ``program`` group
(the model family and what the program needs that the source does not
say), a ``serving`` group, the ``correct`` limits and the sizes
``assumed``.  The family names ``families/<family>.py`` (dimensions,
weights, reference, work).  A traffic mix is ``traffic/<name>.json``,
read by ``traffic.py``, whose arrivals kind names ``arrivals/<kind>.py``.
A per-layer metric is ``metrics/<name>.py``, named by the metric's name
up to its first dot (``step_mfu.serve`` and ``step_mfu.batch`` are both
read by ``metrics/step_mfu.py``).
"""

from __future__ import annotations

import dataclasses
import importlib
import inspect
import json
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

# build_batcher's arguments that the harness supplies itself
HARNESS_OWNS = {"cfg", "params", "telemetry", "seed"}


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return load_json(ROOT / "BENCHMARK.json")


def workload(name: str, bench: Optional[Dict[str, Any]] = None
             ) -> Dict[str, Any]:
    bench = bench or benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json "
                     f"(have {[w['name'] for w in bench['workloads']]})")


def config(name: str) -> Dict[str, Any]:
    return load_json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> Dict[str, Any]:
    return load_json(BENCH / "traffic" / f"{name}.json")


def part(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``."""
    try:
        return importlib.import_module(f"bench.{kind}.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"bench.{kind}.{name}":
            raise
        raise ValueError(f"no {kind}/{name}.py for {name!r}") from None


def family(name: str):
    return part("families", name)


def model_dims(c: Dict[str, Any]) -> Dict[str, Any]:
    """The model's dimensions from a configuration file, with its
    ``family``."""
    fam = c["program"]["family"]
    return dict(family(fam).dims(c), family=fam)


def serving(c: Dict[str, Any]) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """The ``serving`` group split into ``ModelConfig`` fields and
    ``build_batcher`` arguments, under the program's own names.  A key
    that is neither is refused, so nothing is dropped unseen."""
    from repro.configs.base import ModelConfig
    from repro.launch.serve import build_batcher
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    args = set(inspect.signature(build_batcher).parameters) - HARNESS_OWNS
    cfg_kw, batcher_kw = {}, {}
    for k, v in c["serving"].items():
        if k in args:
            batcher_kw[k] = v
        elif k in fields:
            cfg_kw[k] = v
        else:
            raise ValueError(
                f"serving key {k!r} is neither a ModelConfig field nor an "
                f"argument of build_batcher")
    return cfg_kw, batcher_kw


def model_config(c: Dict[str, Any], name: str):
    """The program's ``ModelConfig`` for a configuration file: its
    family's fields, then the serving group's."""
    from repro.configs.base import ModelConfig
    m = model_dims(c)
    fields = family(m["family"]).model_fields(c, m)
    cfg_kw, _ = serving(c)
    clash = sorted(set(cfg_kw) & set(fields))
    if clash:
        raise ValueError(f"serving keys {clash} are the model's, not the "
                         f"deployment's")
    return ModelConfig(name=name, **fields, **cfg_kw)
