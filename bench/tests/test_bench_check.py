"""The check that decides ``correct``: it passes the program, fails the
fp8 control, and fails a run whose timed path is broken underneath.
Each case drives a whole run of a small copy of a cell, without the
harness's look for a chip."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))
import small  # noqa: E402

from bench import run  # noqa: E402

CELLS = [("minitron-4b", "minitron-4b.chat-shared"),
         ("granite-34b", "granite-34b.batch-gen")]


def run_small(name, workload, seed, **kw):
    return run.run_cell(workload, seed, 3.0, False,
                        config=small.config(name),
                        mix=small.mix(workload.split(".", 1)[1]), **kw)


@pytest.mark.parametrize("name,workload", CELLS)
def test_program_passes_and_control_fails(name, workload):
    """The run's own verdict passes the program and, with the fp8
    control's tokens in the program's place, fails the control."""
    r = run_small(name, workload, 2 ** 31 + 77, control=True)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    c = r["control"]
    assert not c["correct"], c["checks"]
    assert c["checks"]["max_logit_gap"]["value"] > \
        c["checks"]["max_logit_gap"]["limit"]


def _broken_decode(monkeypatch, fault):
    """Break the batched decode program where it produces its tokens."""
    import jax.numpy as jnp
    from repro.serve import batching
    make = batching.make_paged_decode_step

    def broken(cfg, max_seq, page_size):
        step = make(cfg, max_seq, page_size)

        def call(params, pools, block_tab, last_tok, pos, remaining,
                 active):
            fed = jnp.array(last_tok, copy=True)     # the input is donated
            out = step(params, pools, block_tab, last_tok, pos, remaining,
                       active)
            pools, nxt, pos, remaining, active, o = out
            if fault == "token":       # a token altered where it is made
                nxt = jnp.where(active | (o[1] > 0),
                                (nxt + 1) % cfg.vocab_size, nxt)
            elif fault == "stale":     # the step hands back its input token
                nxt = fed
            o = o.at[0].set(nxt)
            return pools, nxt, pos, remaining, active, o
        return call

    monkeypatch.setattr(batching, "make_paged_decode_step", broken)


@pytest.mark.parametrize("fault", ["token", "stale"])
@pytest.mark.parametrize("name,workload", CELLS)
def test_broken_decode_is_not_correct(monkeypatch, name, workload, fault):
    _broken_decode(monkeypatch, fault)
    r = run_small(name, workload, 5)
    assert r["attempted"] > 0
    assert not r["correct"], r["checks"]
    assert r["checks"]["max_logit_gap"]["value"] > \
        r["checks"]["max_logit_gap"]["limit"]


def test_missing_requests_are_not_correct(monkeypatch):
    """A request whose answer never completes fails the run."""
    from repro.serve import batching
    submit = batching.ContinuousBatcher.submit

    def drop_some(self, req):
        if req.rid < 1 << 30 and req.rid % 3 == 2:   # a window request
            req.out.close()            # never queued: a short answer
            return True
        return submit(self, req)

    monkeypatch.setattr(batching.ContinuousBatcher, "submit", drop_some)
    r = run_small(*CELLS[1], 6)
    assert r["failed"] > 0 and not r["correct"]
    assert r["checks"]["max_logit_gap"]["value"] is not None


def test_requests_cut_at_the_close_are_not_failures(monkeypatch):
    """Requests still decoding when their deadline (the close plus the
    grace) passes are cut off by the batcher, not counted as failed."""
    monkeypatch.setattr(run, "GRACE_S", 0.3)
    mix = small.mix("batch-gen")
    mix["arrivals"] = dict(mix["arrivals"], clients=2)
    mix["output_len"] = dict(mix["output_len"], median=150, lo=120, hi=200)
    r = run.run_cell("granite-34b.batch-gen", 8, 3.0, False,
                     config=small.config("granite-34b"), mix=mix)
    assert r["failed"] == 0 and r["correct"], r["checks"]
    assert r["attempted"] > 2
