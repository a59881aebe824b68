"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel: <name>.py (pl.pallas_call + explicit BlockSpec VMEM tiling)
and a pure-jnp oracle in ref.py; ops.py dispatches the ones on the
model's path by an explicit mode (pallas | interpret | ref).  Validated
in interpret=True mode on CPU (tests/test_kernels.py, tests/test_paged.py);
the paged-decode kernels are compile-checked for v5e
(tests/test_tpu_compile.py).

  flash_attention       prefill attention (causal, sliding-window, GQA)
  flash_attention_vjp   differentiable variant (custom_vjp Pallas backward)
  decode_attention      flash-decode: one token vs a long KV cache
  decode_attention_q8   flash-decode over an int8-quantized KV cache
  decode_attention_paged(_q8)  flash-decode through a block table (the
                        served decode path)
  ssd_scan              Mamba2 SSD chunk scan with VMEM state carry
"""
from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.decode_attention_q8 import decode_attention_q8
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention_bwd import flash_attention_vjp
from repro.kernels.ssd_scan import ssd_scan

__all__ = ["ops", "ref", "decode_attention", "decode_attention_q8",
           "flash_attention", "flash_attention_vjp", "ssd_scan"]
