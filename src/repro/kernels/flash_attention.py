"""Pallas TPU flash-attention (prefill): causal / windowed, GQA-native.

TPU adaptation notes (vs the paper's GPU serving stacks — FlashInfer/Triton):
no warps or shared-memory banking; instead the kernel is grid-blocked with
explicit VMEM tiles.  Block sizes default to (256, 512) so each tile's
working set — q (rep·bq·d) + k/v (bk·d) + scores (rep·bq·bk) f32 — stays well
under the ~16 MB VMEM budget, and all matmul dims are multiples of 128 for
MXU alignment.  The kv-block grid axis is 'arbitrary' (sequential) so the
online-softmax carry lives in VMEM scratch across kv steps.

GQA is native: the grid batches over (batch × kv_head) and the q tile carries
the ``rep = n_heads // n_kv_heads`` query heads that share the kv head, so K/V
tiles are fetched once per kv head (bandwidth = GQA's whole point).

Validated in interpret mode on CPU against ``ref.py`` (pure jnp oracle).
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  bq: int, bk: int, causal: bool, window: Optional[int],
                  sq: int, skv: int, scale: float):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                  # (rep, bq, d)
    k = k_ref[0].astype(jnp.float32)                  # (bk, d)
    v = v_ref[0].astype(jnp.float32)                  # (bk, d)
    s = jax.lax.dot_general(q, k, (((2,), (1,)), ((), ()))) * scale
    # s: (rep, bq, bk)
    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos < skv                                # kv padding
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask[None], s, NEG_INF)

    m_prev = m_ref[...]                               # (rep, bq)
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[..., None])                 # (rep, bq, bk)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)
    pv = jax.lax.dot_general(p, v, (((2,), (0,)), ((), ())))
    acc_ref[...] = acc_ref[...] * alpha[..., None] + pv
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


def flash_attention_tpu(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: Optional[int] = None,
                        block_q: int = 256, block_k: int = 512,
                        interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, Sq, H, D); k/v: (B, Skv, Hkv, D) -> (B, Sq, H, D).

    ``interpret`` defaults to the backend (``resolve_interpret``).
    """
    b, sq, h, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    assert h % hkv == 0
    rep = h // hkv
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    q_pad = (-sq) % bq
    kv_pad = (-skv) % bk
    if q_pad:
        q = jnp.pad(q, [(0, 0), (0, q_pad), (0, 0), (0, 0)])
    if kv_pad:
        kv_p = [(0, 0), (0, kv_pad), (0, 0), (0, 0)]
        k, v = jnp.pad(k, kv_p), jnp.pad(v, kv_p)
    sq_p, skv_p = sq + q_pad, skv + kv_pad

    # (B·Hkv, rep, Sq, D) / (B·Hkv, Skv, D)
    qr = q.reshape(b, sq_p, hkv, rep, d).transpose(0, 2, 3, 1, 4) \
        .reshape(b * hkv, rep, sq_p, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * hkv, skv_p, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * hkv, skv_p, d)

    grid = (b * hkv, sq_p // bq, skv_p // bk)
    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, causal=causal,
                               window=window, sq=sq, skv=skv,
                               scale=d ** -0.5)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, rep, bq, d), lambda bh, iq, ik: (bh, 0, iq, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, iq, ik: (bh, ik, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, iq, ik: (bh, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, rep, bq, d),
                               lambda bh, iq, ik: (bh, 0, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv, rep, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rep, bq, d), jnp.float32),
            pltpu.VMEM((rep, bq), jnp.float32),
            pltpu.VMEM((rep, bq), jnp.float32),
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(qr, kr, vr)
    out = out.reshape(b, hkv, rep, sq_p, d).transpose(0, 3, 1, 2, 4) \
        .reshape(b, sq_p, h, d)
    return out[:, :sq]
