"""Pallas TPU kernels for the serving hot spots (DESIGN §6).

The paper's §6 implementation layer has two kernel-level pieces: the
PagedAttention-style decode kernel extended to head-granular cache blocks,
and dense prefill attention.  On TPU these become:

  flash_attention — prefill causal attention, BlockSpec (block_q x block_k)
                    VMEM tiling, GQA + sliding window.
  paged_attention — decode attention over the head-granular paged KV pool;
                    block tables are scalar-prefetched (SMEM) and drive the
                    HBM->VMEM index_map — the TPU-native form of Hetis'
                    per-(request, head) cache fetch.

Each kernel ships ``ops.py`` (jit'd wrapper; interpret=True on the CPU
backend only) and ``ref.py`` (pure-jnp oracle for the allclose sweeps).
"""
