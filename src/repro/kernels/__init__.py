"""Pallas TPU kernels for the paper's compute hot-spots.

- hamming_scan: streaming AND-popcount + Eq.3 scoring over word planes of
  the codes (linear-scan baseline, distributed reranker)
- verify_tuples: batched exact-tuple verification (AMIH candidate
  pruning); verify_tuples_grouped runs a whole z-group per launch over a
  padded (B, C, W) layout with in-kernel padding masks and fused
  tuple->bucket-key packing
- blockmax_scan: per-block score maxima for the exact bound-pruned scan
  (§Perf R2 — fused traffic: codes once + (B, n_blocks))
- flash_attention: fused flash attention forward (§Perf L2 — prefill/serve
  hot spot of the LM zoo feeding the retrieval encoder)
- ops: jit'd public wrappers (padding, streaming top-K, pruned top-K,
  backend selection)
- ref: pure-jnp oracles used for validation and as the CPU path
"""

from . import ops, ref
from .blockmax_scan import blockmax_scores
from .flash_attention import flash_attention
from .hamming_scan import hamming_scan_scores
from .verify_tuples import verify_tuples, verify_tuples_grouped

__all__ = [
    "blockmax_scores",
    "flash_attention",
    "hamming_scan_scores",
    "ops",
    "ref",
    "verify_tuples",
    "verify_tuples_grouped",
]
