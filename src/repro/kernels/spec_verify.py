"""Pallas TPU multi-token verify: flash attention for speculative decoding.

Speculative decoding (DESIGN.md §6.1-spec) verifies ``K = spec_k + 1`` new
tokens — the pending token plus k draft tokens — in ONE target forward
against the paged KV pool.  By the time attention runs, the K tokens' KV has
already been scattered into pool pages at positions
``lengths[b] .. lengths[b]+K-1``; what distinguishes this kernel from the
single-token ``paged_decode`` is the *per-query* causal bound: draft query
``j`` (absolute position ``lengths[b] + j``) may attend positions
``<= lengths[b] + j``, so each query row of the block gets its own length
limit instead of the row-wide scalar.

Layout, tuning and the kernel body are shared with ``paged_decode``
(DESIGN.md §Perf-kernels): the pool is read as stored, page blocks of all
kv heads scored in one 2-D matmul, and the K query positions of all H
heads ride in one ``(K*H, d)`` q block; row ``r`` is draft ``r // H`` and
attends positions ``<= lengths[b] + r // H``.

The jnp oracles are ``ref.paged_verify_ref`` / ``ref.paged_verify_quant_ref``.
"""

from __future__ import annotations

from typing import Optional

import jax

from repro.kernels.paged_decode import _paged_attention


def flash_paged_verify_tpu(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_tables: jax.Array,
                           lengths: jax.Array, *,
                           k_scale=None, v_scale=None, layer=None,
                           pages_per_step=None,
                           interpret: Optional[bool] = None
                           ) -> jax.Array:
    """q: (B, K, H, D) — K new tokens per row, whose KV is already in the
    pool at positions ``lengths[b] .. lengths[b]+K-1``; pools:
    (L, P, page, Hkv, D) read at layer ``layer``, or one layer's
    (P, page, Hkv, D); block_tables: (B, maxp) int32; lengths: (B,) int32
    valid tokens per row BEFORE the K new tokens.  For int8 pools pass
    ``k_scale``/``v_scale``: the pools' shape with a trailing 1.
    ``pages_per_step`` overrides the recorded tuning.  Returns (B, K, H, D).
    """
    # query j sits at position lengths + j and attends through it
    return _paged_attention(q, k_pool, v_pool, block_tables, lengths + 1,
                            k_scale, v_scale, layer, pages_per_step,
                            interpret)
