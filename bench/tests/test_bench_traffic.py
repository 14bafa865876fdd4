"""The traffic generator, and the harness finding parts by name."""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
import small  # noqa: E402

from bench import cell, traffic  # noqa: E402


def _plan(name, seed, vocab=49152):
    return traffic.plan(cell.traffic(name), vocab, seed, 51.0, 4096)


@pytest.mark.parametrize("name", ["chat-shared", "batch-gen"])
def test_same_seed_same_plan(name):
    a, b = _plan(name, 2 ** 31 + 12345), _plan(name, 2 ** 31 + 12345)
    for sa, sb in zip(a.sessions, b.sessions):
        assert len(sa.turns) == len(sb.turns)
        for ta, tb in zip(sa.turns, sb.turns):
            assert np.array_equal(ta.prompt, tb.prompt)
            assert (ta.max_new, ta.due_s) == (tb.max_new, tb.due_s)


@pytest.mark.parametrize("name", ["chat-shared", "batch-gen"])
def test_another_seed_sends_the_same_work_in_another_order(name):
    """The requests' due times and, position by position, the multiset
    of their sizes are the seed's to order, not to choose."""
    a, c = _plan(name, 1), _plan(name, 2)
    first = lambda p: [(s.turns[0].due_s, s.turns[0].max_new)  # noqa: E731
                       for s in p.sessions if s.turns]
    assert [d for d, _ in first(a)] == [d for d, _ in first(c)]
    assert sorted(n for _, n in first(a)) == sorted(n for _, n in first(c))
    assert [n for _, n in first(a)] != [n for _, n in first(c)]


@pytest.mark.parametrize("name", ["chat-shared", "batch-gen"])
def test_lengths_in_range(name):
    mix = cell.traffic(name)
    p = traffic.plan(mix, 256000, 3, 51.0, 4096)
    for s in p.sessions:
        assert len(s.turns) <= mix["turns"]["hi"]
        for t in s.turns:
            assert 0 <= t.due_s < 51.0
            assert (mix["output_len"]["lo"] <= t.max_new
                    <= mix["output_len"]["hi"])
            assert len(t.prompt) + t.max_new < 4096 - 1
            assert t.prompt.min() >= 0 and t.prompt.max() < 256000
        if s.turns and not mix.get("chain"):
            for t in s.turns:
                lo, hi = mix["message_len"]["lo"], mix["message_len"]["hi"]
                assert lo <= len(t.prompt) <= hi
    for sp in p.system_prompts:
        assert len(sp) == mix["system_prompts"]["len"]


def test_chat_turn_extends_its_predecessor():
    mix = cell.traffic("chat-shared")
    p = _plan("chat-shared", 11, vocab=1000)
    s = next(s for s in p.sessions if len(s.turns) > 1)
    assert any(np.array_equal(s.turns[0].prompt[:1024], sp)
               for sp in p.system_prompts)
    for prev, turn in zip(s.turns, s.turns[1:]):
        n = len(prev.prompt)
        assert np.array_equal(turn.prompt[:n], prev.prompt)
        msg = len(turn.prompt) - n - prev.max_new
        assert mix["message_len"]["lo"] <= msg <= mix["message_len"]["hi"]
        assert turn.due_s - prev.due_s >= mix["think_s"]["lo"]


def test_poisson_starts_are_one_fixed_path():
    """Open-loop sessions start on one Poisson path of the mix's rate,
    not stretched to the window: the same for every seed."""
    mix = cell.traffic("chat-shared")
    rate = mix["arrivals"]["rate"]
    a = [s.turns[0].due_s for s in _plan("chat-shared", 1).sessions
         if s.turns]
    b = [s.turns[0].due_s for s in _plan("chat-shared", 2 ** 31 + 9).sessions
         if s.turns]
    assert a == b and a == sorted(a)
    starts = cell.part("arrivals", "poisson").starts(
        {"rate": rate}, 10_000.0, np.random.default_rng(0))
    assert abs(len(starts) / 10_000.0 - rate) < 0.05 * rate
    assert 0 <= starts.min() and starts.max() < 10_000.0


def test_shuffle_block_keeps_each_blocks_sizes():
    """With ``shuffle_block`` the seed orders sizes only within each run
    of that many sessions."""
    mix = cell.traffic("chat-shared")
    block = mix["shuffle_block"]
    plans = [_plan("chat-shared", s) for s in (3, 4)]
    sizes = [[s.turns[0].max_new for s in p.sessions] for p in plans]
    assert sizes[0] != sizes[1]
    for lo in range(0, len(sizes[0]), block):
        assert (sorted(sizes[0][lo:lo + block])
                == sorted(sizes[1][lo:lo + block]))


def test_unknown_serving_key_is_refused():
    conf = small.config("minitron-4b")
    conf["serving"] = dict(conf["serving"], no_such_setting=1)
    with pytest.raises(ValueError, match="no_such_setting"):
        cell.serving(conf)


def test_serving_group_reaches_the_program():
    conf = small.config("granite-34b")
    conf["serving"] = dict(conf["serving"], prefill_chunk=32,
                           schedule="fifo")
    cfg = cell.model_config(conf, "granite-34b")
    assert cfg.prefill_chunk == 32 and cfg.kv_page_size == 16
    assert cfg.prefix_cache and cfg.n_kv_heads == 1
    cfg_kw, batcher_kw = cell.serving(conf)
    assert batcher_kw["schedule"] == "fifo"
    assert batcher_kw["n_slots"] == 4 and "prefill_chunk" in cfg_kw


def test_new_parts_are_found_by_name(tmp_path, monkeypatch):
    """A configuration, a traffic mix, an arrivals kind, a model family
    and a metric reader added as new files are found without an edit to
    any file there."""
    import bench.arrivals
    import bench.families
    import bench.metrics
    from bench import run
    for sub in ("configs", "traffic", "metrics", "arrivals", "families"):
        (tmp_path / sub).mkdir()
    conf = small.config("minitron-4b")
    conf["program"] = dict(conf["program"], family="new_family")
    (tmp_path / "configs" / "new-model.json").write_text(json.dumps(conf))
    mix = small.mix("chat-shared")
    mix["arrivals"] = {"kind": "new_kind", "every": 0.5}
    (tmp_path / "traffic" / "new-mix.json").write_text(json.dumps(mix))
    (tmp_path / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return ctx.value\n")
    (tmp_path / "arrivals" / "new_kind.py").write_text(
        "import numpy as np\nCLOSED = False\n"
        "def starts(spec, seconds, rng):\n"
        "    return np.arange(0.0, seconds, spec['every'])\n")
    (tmp_path / "families" / "new_family.py").write_text(
        "from bench.families.dense import *  # noqa\n"
        "from bench.families.dense import dims as _dims\n"
        "def dims(c):\n    return dict(_dims(c), d_model=96)\n")
    monkeypatch.setattr(cell, "BENCH", tmp_path)
    for pkg, sub in ((bench.metrics, "metrics"), (bench.arrivals, "arrivals"),
                     (bench.families, "families")):
        monkeypatch.setattr(pkg, "__path__",
                            list(pkg.__path__) + [str(tmp_path / sub)])
    m = cell.model_dims(cell.config("new-model"))
    assert m["family"] == "new_family" and m["d_model"] == 96
    p = traffic.plan(cell.traffic("new-mix"), 2048, 1, 3.0, 256)
    assert [s.turns[0].due_s for s in p.sessions] == [0.0, 0.5, 1.0, 1.5,
                                                       2.0, 2.5]
    got = run.per_layer(["new_metric.a"], run.Context(value=1.5))
    assert got == {"new_metric.a": 1.5}
    with pytest.raises(ValueError, match="no_kind"):
        cell.part("arrivals", "no_kind")
