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

Layout and tuning are shared with ``paged_decode`` (DESIGN.md
§Perf-kernels): head-fused ``(P, Hkv, page, D)`` pool blocks, a
``(B, padded_pages // pages_per_step)`` grid with the block table padded
to a multiple of ``pages_per_step`` using scratch-page entries, and the
same scalar-prefetch index maps.  The K query positions of all ``rep``
grouped heads ride in one ``(hkv, K*rep, d)`` q block.  The quantized
variant dequantizes int8 pages in-body via
``models.attention.kv_dequantize``, same as the decode kernel.

The jnp oracles are ``ref.paged_verify_ref`` / ``ref.paged_verify_quant_ref``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.models.attention import NEG_INF, kv_dequantize
from repro.kernels.paged_decode import _paged_attention


def _verify_kernel(bt_ref, len_ref, q_ref, *refs, page: int, pps: int,
                   quant: bool, scale: float, rep: int):
    """refs: k×pps, v×pps[, k_scale×pps, v_scale×pps], o, acc, m, l."""
    ip = pl.program_id(1)
    np_ = pl.num_programs(1)
    base_len = len_ref[pl.program_id(0)]
    n_in = pps * (4 if quant else 2)
    k_refs, v_refs = refs[:pps], refs[pps:2 * pps]
    ks_refs = refs[2 * pps:3 * pps] if quant else ()
    vs_refs = refs[3 * pps:4 * pps] if quant else ()
    o_ref, acc_ref, m_ref, l_ref = refs[n_in:]

    @pl.when(ip == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                   # (hkv, K*rep, d)
    # q-block row r is draft query j = r // rep, at absolute position
    # base_len + j, attending positions <= base_len + j
    q_idx = jax.lax.broadcasted_iota(jnp.int32, (1, q.shape[1], 1), 1) // rep
    for j in range(pps):
        if quant:
            k = kv_dequantize(k_refs[j][0], ks_refs[j][0],
                              jnp.float32)             # (hkv, page, d)
            v = kv_dequantize(v_refs[j][0], vs_refs[j][0],
                              jnp.float32)
        else:
            k = k_refs[j][0].astype(jnp.float32)
            v = v_refs[j][0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,)))) * scale
        k_pos = (ip * pps + j) * page + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, page), 2)
        s = jnp.where(k_pos <= base_len + q_idx, s, NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = (acc_ref[...] * alpha[..., None]
                        + jax.lax.dot_general(p, v,
                                              (((2,), (1,)), ((0,), (0,)))))
        m_ref[...] = m_new

    @pl.when(ip == np_ - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


def flash_paged_verify_tpu(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_tables: jax.Array,
                           lengths: jax.Array, *,
                           k_scale=None, v_scale=None,
                           pages_per_step=None,
                           interpret: Optional[bool] = None
                           ) -> jax.Array:
    """q: (B, K, H, D) — K new tokens per row, whose KV is already in the
    pool at positions ``lengths[b] .. lengths[b]+K-1``; pools:
    (P, page, Hkv, D); block_tables: (B, maxp) int32; lengths: (B,) int32
    valid tokens per row BEFORE the K new tokens.  For int8 pools pass
    ``k_scale``/``v_scale``: (P, page, Hkv, 1) per-token-per-head scales.
    ``pages_per_step`` overrides the recorded tuning.  Returns (B, K, H, D).
    """
    kq = q.shape[1]
    return _paged_attention(q, k_pool, v_pool, block_tables, lengths,
                            k_scale, v_scale, pages_per_step, interpret,
                            _verify_kernel, kq=kq)
