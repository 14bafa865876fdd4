"""Small copies of the benchmark's cells for CPU tests: the same code
paths at widths a test run can hold."""

import os
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import cell  # noqa: E402

# the program pads its vocabulary to a multiple of 2048
SMALL = dict(hidden_size=64, n_embd=64, intermediate_size=128, n_inner=128,
             num_attention_heads=4, n_head=4, head_dim=16,
             num_hidden_layers=2, n_layer=2, vocab_size=2048)


def config(name: str, **serving):
    c = cell.config(name)
    for k, v in SMALL.items():
        if k in c:
            c[k] = v
    if "num_key_value_heads" in c:
        c["num_key_value_heads"] = 2
    c["serving"] = dict(c["serving"], n_slots=4, max_seq=256, n_pages=None,
                        **serving)
    # at these widths sound runs read gaps up to 0.011 and the fp8
    # control 0.105 and more (CPU, seeds 1-3 of both cells)
    c["correct"] = {"max_logit_gap": 0.04}
    return c


def mix(name: str):
    m = cell.traffic(name)
    m["message_len"] = dict(m["message_len"], median=12, lo=4, hi=40)
    m["output_len"] = dict(m["output_len"], median=8, lo=4, hi=24)
    if "think_s" in m:
        m["think_s"] = dict(m["think_s"], median=0.2, lo=0.05, hi=0.5)
    if "system_prompts" in m:
        m["system_prompts"] = dict(m["system_prompts"], len=48)
    if m["arrivals"]["kind"] == "poisson":
        m["arrivals"] = dict(m["arrivals"], rate=2.0)
    else:
        m["arrivals"] = dict(m["arrivals"], clients=4)
    return m
