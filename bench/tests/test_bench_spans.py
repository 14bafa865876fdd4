"""The program's spans and scopes on the device trace, and the readers
that use them, on a small traced run of each cell on the CPU.  The CPU
has no published peaks; the run borrows the v5e's, so the roofline
share it reads is a check of the arithmetic, not a device number."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
import small  # noqa: E402

from bench import peaks as P  # noqa: E402
from bench import run  # noqa: E402
from bench import spans as S  # noqa: E402
from bench import trace as TR  # noqa: E402

CELLS = [("minitron-4b", "minitron-4b.chat-shared", "serve"),
         ("granite-34b", "granite-34b.batch-gen", "batch")]
NEW = ("attn_read_ms", "attn_read_roofline", "host_gap_ms")


@pytest.fixture(scope="module", params=CELLS, ids=[c[2] for c in CELLS])
def traced(request):
    name, workload, suffix = request.param
    seen = {}
    per_layer = run.per_layer

    def keep(names, ctx):
        seen["ctx"] = ctx
        # read while the batcher lives: the map holds it weakly
        seen["programs"] = {k: dict(v) for k, v in
                            ctx.stats1["programs"].items()}
        return per_layer(names, ctx)

    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(P.PEAKS, "cpu", P.PEAKS["TPU v5 lite"])
        mp.setattr(run, "per_layer", keep)
        result = run.run_cell(workload, 2 ** 31 + 91, 3.0, True,
                              config=small.config(name),
                              mix=small.mix(workload.split(".", 1)[1]))
        yield result, seen["ctx"], suffix, seen["programs"]


def test_clock_fit_maps_every_annotation(traced):
    _, ctx, _, _ = traced
    v = S.view(ctx)
    assert v is not None and len(v.residuals_ns) > 10
    assert v.unmatched == 0
    assert np.percentile(np.abs(v.residuals_ns), 99) < 0.5e6
    names = {sp[2] for sp in v.spans}
    for name in ("serve.admit", "serve.decode_step", "serve.readback",
                 "serve.emit", "serve.prefill_chunk"):
        assert name in names, name


def test_scope_map_assigns_decode_ops(traced):
    _, ctx, _, programs = traced
    decode = TR.program(ctx.reduced, ctx.programs[0])
    assert decode in programs
    mapped = {S.scope_name(p) for p in programs[decode].values()}
    assert set(S.SCOPES) <= mapped
    v = S.view(ctx)
    seen = {v.scope_of(o) for o in v.ops if o.program == decode}
    assert {"attn_read", "mlp", "logits"} <= seen
    # a leaf contains no other op: the loop's while is not one
    assert not any(o.name.startswith("while") for o in v.ops)


def test_new_readers_return_numbers(traced):
    result, _, suffix, _ = traced
    m = {k: v["value"] for k, v in result["metrics"].items()}
    for name in NEW:
        assert f"{name}.{suffix}" in m, (name, sorted(m))
    assert 0 < m[f"attn_read_roofline.{suffix}"] <= 100
    assert 0 < m[f"attn_read_ms.{suffix}"] <= m[f"decode_step_ms.{suffix}"]
    assert m[f"host_gap_ms.{suffix}"] >= 0


def test_existing_readers_read_the_same(traced):
    _, ctx, _, _ = traced
    names = [e["name"] for e in run.C.benchmark()["per_layer"]
             if not e["name"].startswith(NEW)]
    fresh = run.Context(**{k: v for k, v in vars(ctx).items()
                           if k != "spans_view"})
    assert "spans_view" in vars(ctx)
    assert run.per_layer(names, fresh) == run.per_layer(names, ctx)
