"""Pallas TPU flash-decode: one query token against a long KV cache.

Decode attention is HBM-bandwidth-bound (the whole KV cache streams through
once per token), so the kernel is shaped for streaming: the grid walks KV
blocks sequentially per (batch × kv_head), the online-softmax carry lives in
VMEM scratch, and the tiny (rep × d) output is written once at the end.
Sliding-window / partially-filled caches are handled by masking against
``cache_len`` (scalar-prefetched so the mask math happens on SREGs).

The seq-sharded distributed decode (shard_map + log-sum-exp combine, see
``repro.launch.sharding``) calls this kernel per shard on TPU; the jnp oracle
in ``ref.py`` is the interpret-mode / CPU path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat.pallascompat import resolve_interpret, tpu_compiler_params
from repro.models.attention import NEG_INF


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                   *, bk: int, window: Optional[int], scale: float):
    ik = pl.program_id(1)
    nk = pl.num_programs(1)
    cache_len = len_ref[0]

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                   # (rep, d)
    k = k_ref[0].astype(jnp.float32)                   # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (rep, bk)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    mask = k_pos < cache_len
    if window is not None:
        mask &= k_pos >= cache_len - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = (acc_ref[...] * alpha[..., None]
                    + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ()))))
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


def flash_decode_tpu(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len: jax.Array, *, window: Optional[int] = None,
                     block_k: int = 1024,
                     interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, 1, H, D); caches: (B, S, Hkv, D); cache_len: () int32.

    Returns (B, 1, H, D).
    """
    b, _, h, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    assert h % hkv == 0
    rep = h // hkv
    bk = min(block_k, s)
    pad = (-s) % bk
    if pad:
        kv_p = [(0, 0), (0, pad), (0, 0), (0, 0)]
        k_cache = jnp.pad(k_cache, kv_p)
        v_cache = jnp.pad(v_cache, kv_p)
    sp = s + pad

    qr = q.reshape(b, hkv, rep, d).reshape(b * hkv, rep, d)
    kr = k_cache.transpose(0, 2, 1, 3).reshape(b * hkv, sp, d)
    vr = v_cache.transpose(0, 2, 1, 3).reshape(b * hkv, sp, d)
    lens = jnp.broadcast_to(jnp.reshape(cache_len, (1,)), (1,)).astype(jnp.int32)

    grid = (b * hkv, sp // bk)
    kernel = functools.partial(_decode_kernel, bk=bk, window=window,
                               scale=d ** -0.5)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, rep, d), lambda bh, ik, lens: (bh, 0, 0)),
                pl.BlockSpec((1, bk, d), lambda bh, ik, lens: (bh, ik, 0)),
                pl.BlockSpec((1, bk, d), lambda bh, ik, lens: (bh, ik, 0)),
            ],
            out_specs=pl.BlockSpec((1, rep, d),
                                   lambda bh, ik, lens: (bh, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((rep, d), jnp.float32),
                pltpu.VMEM((rep,), jnp.float32),
                pltpu.VMEM((rep,), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b * hkv, rep, d), q.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(lens, qr, kr, vr)
    return out.reshape(b, hkv, rep, d).reshape(b, 1, h, d)
