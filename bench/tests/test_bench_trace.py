"""The trace reduction, on a small trace this test records on the CPU."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))
import small  # noqa: E402,F401

from bench import trace as TR  # noqa: E402


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    @jax.jit
    def step_fn(x):
        return jnp.tanh(x @ x) + 1.0

    x = jnp.ones((256, 256))
    step_fn(x).block_until_ready()
    d = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(d), profiler_options=opts)
    with jax.profiler.TraceAnnotation(TR.WINDOW):
        for i in range(5):
            with jax.profiler.StepTraceAnnotation("serve.decode_step",
                                                  step_num=i):
                step_fn(x).block_until_ready()
            with jax.profiler.TraceAnnotation("serve.host_work"):
                sum(range(200_000))
    jax.profiler.stop_trace()
    return TR.reduce(ProfileData.from_file(TR.find_xplane(str(d))))


def test_programs_and_calls(recorded):
    prog = TR.program(recorded, "step_fn")
    assert prog == "jit_step_fn"
    assert recorded.program_calls[prog] == 5
    assert 0 < recorded.program_ns[prog] <= recorded.busy_ns


def test_busy_inside_window(recorded):
    assert 0 < recorded.busy_s < recorded.window_s
    gaps = sum(s for _, s in recorded.gaps)
    assert gaps <= recorded.window_s - recorded.busy_s + 1e-9


def test_gaps_named_by_host_span(recorded):
    names = [n for n, _ in recorded.gaps]
    assert "serve.host_work" in names
    bd = TR.breakdown(recorded)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert all(k.startswith("jit_step_fn:") for k, _ in bd["device_ops"])


def test_union():
    assert TR.union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [(0, 3), (5, 9)]
