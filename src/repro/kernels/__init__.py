# Pallas TPU kernels for the paper's compute hot-spots (§5.1):
#   l2_tile     — tiled exact L2/IP distance (MXU): brute force, build, rank
#   pq_adc      — batched PQ asymmetric-distance via one-hot MXU matmul
#   block_topk  — fused block-tile ranking: distances + top-m select (VPU)
#   tier0_fetch — fused tier-0 probe + gather + rank: the device search's
#                 ISSUE-3 fetch stage (VMEM hot-tile hit or HBM block DMA)
#                 + fused_round, the divergence-aware batched round:
#                 whole-batch sorted-unique dedup + once-per-distinct-
#                 block gather (double-buffered DMA when compiled) +
#                 per-tile broadcast + rank + top-M expansion order
#   dedup       — the shared sorted-unique / join-mask helpers both the
#                 kernel's union pass and the search loop's accounting
#                 mirror group duplicates with (they must never drift)
# Each kernel: <name>.py (pl.pallas_call + BlockSpec) with a pure-jnp
# oracle in ref.py and the jit'd dispatch wrapper in ops.py.
from repro.kernels.dedup import join_mask, sorted_unique_ranks
from repro.kernels.ops import (pairwise_l2, pq_adc_batch, block_rank,
                               tier0_rank, fused_round, round_tile)
