"""One generator for every traffic mix: a seed and a mix's parameters in,
a plan of requests out.

A mix (``traffic/<name>.json``) gives:

* ``arrivals``: ``{"kind": <kind>, ...}``; ``arrivals/<kind>.py`` gives
  the sessions' start times and whether the loop is closed.
  ``poisson`` (``rate``, sessions/s) is open loop: every turn is due at
  a fixed time.  ``clients`` (``clients``) is closed loop: every client
  starts at once and sends its next request when its last one ends.
* ``turns``: ``{"lo", "hi"}``, the requests in one session.
* ``system_prompts`` (optional): ``{"count", "len"}``; each session
  opens with one of them, and the harness caches them during set-up.
* ``chain``: turn k's prompt is turn k-1's prompt, plus a reply of turn
  k-1's output length, plus a new message (a chat history).  Otherwise
  each message is a whole prompt.  The reply is drawn, not the served
  tokens, so every prompt is fixed before the run.
* ``message_len``, ``output_len``, ``think_s``: truncated log-normal
  distributions ``{"median", "sigma", "lo", "hi"}``.  Turn k of a
  session is due ``think_s`` after turn k-1 (open loop only).
* ``shuffle_block`` (optional): the seed orders sizes only among this
  many sessions in a row (in start order); by default among all.

Everything but the tokens is one fixed set per mix and window, drawn
from a fixed stream: session starts, turn counts, think times, and the
sizes of the turns at each position of a session.  The seed deals the
sizes of each position out to the sessions of each block in its own
order and draws the token ids.  So every seed sends the same requests
at the same times, with the sizes in another order; a turn due after
the window, or whose prompt and output would pass ``max_seq``, is never
sent.
"""

from __future__ import annotations

import dataclasses
import zlib
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Turn:
    prompt: np.ndarray           # int32 tokens
    max_new: int
    due_s: float                 # open loop: seconds into the window


@dataclasses.dataclass
class Session:
    turns: List[Turn]


@dataclasses.dataclass
class Plan:
    system_prompts: List[np.ndarray]
    sessions: List[Session]
    closed: bool


def lognormal(rng: np.random.Generator, spec: Dict, n: int) -> np.ndarray:
    """``n`` draws of a log-normal with median ``spec["median"]`` and
    shape ``spec["sigma"]``, redrawn until inside [lo, hi]."""
    out = np.empty(n)
    todo = np.arange(n)
    while len(todo):
        x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(
            len(todo)))
        ok = (x >= spec["lo"]) & (x <= spec["hi"])
        out[todo[ok]] = x[ok]
        todo = todo[~ok]
    return out


def _fixed_rng(mix: Dict, seconds: float) -> np.random.Generator:
    tag = f"{sorted(mix.items())}|{seconds}".encode()
    return np.random.default_rng(zlib.crc32(tag))


def plan(mix: Dict, vocab: int, seed: int, seconds: float,
         max_seq: int) -> Plan:
    from bench import cell
    arrivals = cell.part("arrivals", mix["arrivals"]["kind"])
    closed = arrivals.CLOSED

    # the fixed part: one skeleton of sessions for every seed
    fixed = _fixed_rng(mix, seconds)
    starts = arrivals.starts(mix["arrivals"], seconds, fixed)
    n_sess = len(starts)
    n_turns = fixed.integers(mix["turns"]["lo"], mix["turns"]["hi"] + 1,
                             n_sess)
    depth = int(n_turns.max())
    msg_len = np.rint(lognormal(fixed, mix["message_len"], n_sess * depth)
                      ).astype(int).reshape(depth, n_sess)
    out_len = np.rint(lognormal(fixed, mix["output_len"], n_sess * depth)
                      ).astype(int).reshape(depth, n_sess)
    if closed:
        due = np.zeros((depth, n_sess))
    else:
        think = lognormal(fixed, mix["think_s"], (depth - 1) * n_sess
                          ).reshape(depth - 1, n_sess)
        due = np.vstack([starts, starts + np.cumsum(think, 0)])
    sys_spec = mix.get("system_prompts")

    # the seed's part: which session gets which size at each position,
    # and every token.
    rng = np.random.default_rng(int(seed))
    system = [rng.integers(0, vocab, int(sys_spec["len"])).astype(np.int32)
              for _ in range(int(sys_spec["count"]))] if sys_spec else []
    block = int(mix.get("shuffle_block") or n_sess)
    for k in range(depth):
        for lo in range(0, n_sess, block):
            has = lo + np.flatnonzero(n_turns[lo:lo + block] > k)
            order = rng.permutation(has)
            msg_len[k, has] = msg_len[k, order]
            out_len[k, has] = out_len[k, order]

    def tokens(n):
        return rng.integers(0, vocab, int(n)).astype(np.int32)

    sessions = []
    for i in range(n_sess):
        head = (system[int(rng.integers(len(system)))] if system
                else np.zeros(0, np.int32))
        turns: List[Turn] = []
        for k in range(int(n_turns[i])):
            msg = tokens(msg_len[k, i])
            if mix.get("chain") and turns:
                prev = turns[-1]
                head = np.concatenate([prev.prompt, tokens(prev.max_new)])
            prompt = np.concatenate([head, msg])
            if len(prompt) + out_len[k, i] >= max_seq - 1 or (
                    not closed and due[k, i] >= seconds):
                break
            turns.append(Turn(prompt=prompt, max_new=int(out_len[k, i]),
                              due_s=float(due[k, i])))
        sessions.append(Session(turns=turns))
    return Plan(system_prompts=system, sessions=sessions, closed=closed)
