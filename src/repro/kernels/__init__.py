"""Pallas TPU kernels for the perf-critical data planes.

* ``segment_reduce`` — fused gather + tiled segment-sum (MXU one-hot
  matmul) and tiled segment min/max (masked VPU reduce) over one tile
  plan.  The paper's entire query data plane (DBIndex pass 1/2, I-Index
  window differences) plus GNN message passing and recsys EmbeddingBag.
* ``bitset_expand``  — packed-uint32 BFS hop (segmented OR scan + 16-bit
  split boundary extraction).  The paper's window computation.
* ``fm_interaction`` — FM sum-square second-order term (memory-bound fuse).
* ``flash_attention``— causal GQA streaming-softmax attention (LM prefill).

Every kernel ships ``ops.py`` (jit'd wrapper, backend dispatch) and
``ref.py`` (oracle used by the allclose sweeps in tests/).
"""
