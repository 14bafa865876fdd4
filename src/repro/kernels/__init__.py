"""Pallas TPU kernels (interpreted on the CPU backend; see platform.py).

flash_attention.py  tiled online-softmax attention (causal/sliding-window/GQA)
ssd_scan.py         Mamba2 SSD chunked scan (MXU matmul form)
stencil.py          2D 4-point stencil via the shift-register pattern
treereduce_kernel.py lane-level balanced tree reduction
ops.py              jit'd wrappers with XLA fallbacks
platform.py         compiled-or-interpreted rule (CPU interprets)
ref.py              pure-jnp oracles
"""
from . import ops, ref
