"""A small copy of the ``deepseek-v2-lite`` configuration and the
``doc-qa`` mix for CPU tests: every published setting (routing, YaRN,
the dense first layer) at widths a test run can hold."""

import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
import small  # noqa: E402,F401  (puts the repo on the path)

from bench import cell  # noqa: E402

WIDTHS = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
              num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
              qk_rope_head_dim=16, v_head_dim=16, moe_intermediate_size=32,
              n_routed_experts=32, num_experts_per_tok=6, n_shared_experts=2,
              num_hidden_layers=3, vocab_size=2048)


def config(**serving):
    c = dict(cell.config("deepseek-v2-lite"), **WIDTHS)
    c["serving"] = dict(c["serving"], n_slots=4, max_seq=256, n_pages=None,
                        prefill_chunk=32, **serving)
    # at these widths sound runs read gaps up to 0.026 and the fp8
    # control 0.387 and more (CPU, seeds 1-10).  A bf16 router's near
    # ties route some tokens to another expert than float32's: with 8
    # experts of which 3 are chosen that reached 0.31 and the control's
    # least 0.35, so the copy keeps the published top-6 over 32.
    c["correct"] = {"max_logit_gap": 0.1}
    return c


def mix():
    m = cell.traffic("doc-qa")
    m["system_prompts"] = dict(m["system_prompts"], count=2, len=48)
    m["arrivals"] = dict(m["arrivals"], clients=4)
    m["message_len"] = dict(m["message_len"], median=12, lo=4, hi=40)
    m["output_len"] = dict(m["output_len"], median=8, lo=4, hi=24)
    return m
