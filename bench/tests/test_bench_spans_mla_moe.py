"""The latent read's and the expert layers' scopes on the device trace,
and the readers that use them, on a small traced ``doc-qa`` run on the
CPU.  The CPU has no published peaks; the run borrows the v5e's, so the
roofline shares it reads check the arithmetic, not the device."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))
import small_mla_moe as small  # noqa: E402

from bench import peaks as P  # noqa: E402
from bench import run  # noqa: E402
from bench import spans as S  # noqa: E402
from bench import trace as TR  # noqa: E402
from bench.metrics import moe_ms  # noqa: E402

NEW = ("decode_step_ms", "attn_read_ms", "step_mfu", "latent_read_roofline",
       "moe_ms", "moe_roofline")


@pytest.fixture(scope="module")
def traced():
    seen = {}
    per_layer = run.per_layer

    def keep(names, ctx):
        seen["ctx"] = ctx
        seen["programs"] = {k: dict(v) for k, v in
                            ctx.stats1["programs"].items()}
        return per_layer(names, ctx)

    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(P.PEAKS, "cpu", P.PEAKS["TPU v5 lite"])
        mp.setattr(run, "per_layer", keep)
        result = run.run_cell("deepseek-v2-lite.doc-qa", 2 ** 31 + 91, 3.0,
                              True, config=small.config(), mix=small.mix())
        yield result, seen["ctx"], seen["programs"]


def test_new_readers_return_numbers(traced):
    result, _, _ = traced
    m = {k: v["value"] for k, v in result["metrics"].items()}
    for name in NEW:
        assert f"{name}.docqa" in m, (name, sorted(m))
    step = m["decode_step_ms.docqa"]
    assert 0 < m["attn_read_ms.docqa"] <= step
    assert 0 < m["moe_ms.docqa"] <= step
    assert 0 < m["latent_read_roofline.docqa"] <= 100
    assert 0 < m["moe_roofline.docqa"] <= 100
    assert 0 < m["step_mfu.docqa"] <= 100


def test_decode_ops_carry_the_new_scopes(traced):
    """The latent read's ops map to ``attn_read`` and ``kv_write``, and
    the expert layers' to each of their three scopes."""
    _, ctx, programs = traced
    decode = TR.program(ctx.reduced, ctx.programs[0])
    paths = programs[decode].values()
    assert {"attn_read", "kv_write", "attn_proj", "mlp", "logits"} <= {
        S.scope_name(p) for p in paths}
    assert set(moe_ms.MOE_SCOPES) <= {moe_ms.moe_scope(p) for p in paths}
    ms, split, calls = moe_ms.per_call(ctx)
    assert calls > 0 and set(split) <= set(moe_ms.MOE_SCOPES)
    assert ms <= sum(split.values()) + 1e-9


def test_readers_find_nothing_in_a_dense_cell(traced):
    """A model without experts or a latent cache reads nothing: the new
    readers return None rather than a number or an error."""
    from bench.metrics import latent_read_roofline, moe_roofline
    _, ctx, _ = traced
    dense = run.Context(**dict(vars(ctx), m={"n_layers": 2}))
    for reader in (latent_read_roofline, moe_ms, moe_roofline):
        assert reader.read(dense) is None
