"""Serving: generation drivers + continuous batching.

Serving fast path (device-resident slot state)
----------------------------------------------
The hlslib thesis — hardware-style plumbing (FIFOs, dataflow PEs, packed
vectors) as first-class library abstractions so the hot path never
leaves the pipeline — applied to inference:

* ``serve_loop.make_sampling_serve_steps`` fuses sampling into the
  jitted prefill/decode steps: each call returns int32 token ids, so the
  per-token device->host transfer is 4 bytes/slot instead of a vocab
  row, and the logits never materialize off-device.
* ``batching.ContinuousBatcher`` keeps ALL per-slot decode state
  (``last_tok``, ``pos``, ``remaining``, active mask) in device arrays;
  one donated jitted call advances every slot per step and streams back
  a single small int32 vector (token + finished flag per slot) — the
  batcher PE's only output FIFO to the host.
* Admission is bucketed (pad-to-power-of-two prompts, LRU-bounded
  compile cache) and batched, so arbitrary prompt lengths cost at most
  log2(max_seq) prefill compilations.
* ``kernels.flash_attention.flash_attention_decode`` is the sq=1
  decode-specialized attention kernel (kv-only grid, GQA group folded
  into the q block, static skipping of future/out-of-window kv blocks),
  routed via ``ModelConfig.decode_flash``; the paged read takes
  ``flash_attention_decode_paged`` on a TPU
  (``kernels.platform.paged_decode_kernel``).
"""

from . import (serve_loop, batching, kv_tiers, prefix_cache, resilience,
               telemetry)
