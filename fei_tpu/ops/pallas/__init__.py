"""Pallas TPU kernels — the performance core (SURVEY.md §7 step 4).

The reference has no kernels at all (its FLOPs leave the process over HTTP,
fei/core/assistant.py:524-530); these are the greenfield TPU-native hot ops:

- flash_attention: blockwise causal attention for prefill — O(T) memory,
  online softmax, MXU-shaped [block_q, block_k] score tiles.
- paged_attention: paged-KV attention over a block table at a fixed query
  block: the decode step (one query a sequence) and the solo prefill chunk.
- ragged_paged_attention: mixed prefill+decode rows — per-row
  (limit, q_len) metadata — in ONE invocation over the paged pool.
- ssd_step: a Mamba-2 mixer's decode step over its state block in place,
  a live row read once and written once, a dead row not at all.

Every kernel runs in interpret mode on CPU (the hermetic test mesh) and
compiled on TPU; the XLA-native fei_tpu.ops.attention is the correctness
oracle for both.
"""

from fei_tpu.ops.pallas.flash_attention import flash_attention
from fei_tpu.ops.pallas.paged_attention import paged_attention
from fei_tpu.ops.pallas.ragged_paged_attention import ragged_paged_attention

__all__ = ["flash_attention", "paged_attention", "ragged_paged_attention"]
