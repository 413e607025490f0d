"""Pallas TPU paged flash-decode: block-table attention over a KV page pool.

The paged serving engine (DESIGN.md §6.1-paged) stores KV in a shared pool
of fixed-size pages; each sequence owns a per-row *block table* mapping
logical page index -> physical page.  Decode attention then has no
contiguous cache to stream — the kernel walks a sequence's pages in logical
order and resolves each one through the block table.

The resolution happens in the BlockSpec ``index_map`` via scalar prefetch:
the block table and per-row lengths are prefetched to SMEM before the body
runs, so the pager can issue the HBM->VMEM DMA for physical page
``bt[b, ip]`` while the previous page is still being processed.

Tuned layout (DESIGN.md §Perf-kernels): the pool is transposed to
``(P, Hkv, page, D)`` so one grid step DMAs **all kv heads of a page in a
single block** — the grid is ``(B, padded_pages // pages_per_step)``
instead of the old one-step-per-``(row × kv head × page)`` walk, and the
GQA score is a single batched ``dot_general`` over the kv-head axis.
``pages_per_step`` replicates the k/v operands with offset index maps so
one step covers several consecutive logical pages (multi-page DMA); the
block table is padded to a multiple of it with scratch-page entries, which
``lengths`` masks out.  The choice per ``(page_size, head_dim, hkv)``
comes from ``repro.kernels.tuning``.

The quantized variant streams int8 pages plus bf16 per-token-per-head
scale pages (a parallel pool indexed by the same block table) and
dequantizes in-body via ``models.attention.kv_dequantize`` — the same
helper the slot path uses, so quantized-paged matches quantized-slot
bit-for-bit at the model layer.  The jnp oracles are
``ref.paged_decode_ref`` / ``ref.paged_decode_quant_ref``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat.pallascompat import resolve_interpret, tpu_compiler_params
from repro.models.attention import NEG_INF, kv_dequantize
from repro.kernels.tuning import tuning_for


def _paged_kernel(bt_ref, len_ref, q_ref, *refs, page: int, pps: int,
                  quant: bool, scale: float, rep: int):
    """refs: k×pps, v×pps[, k_scale×pps, v_scale×pps], o, acc, m, l.

    ``rep`` (query heads per kv head) is unused here but part of the
    shared kernel signature — the verify kernel needs it to recover each
    q-block row's draft index.
    """
    ip = pl.program_id(1)
    np_ = pl.num_programs(1)
    cache_len = len_ref[pl.program_id(0)]
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

    q = q_ref[0].astype(jnp.float32)                   # (hkv, rep, d)
    for j in range(pps):
        if quant:
            k = kv_dequantize(k_refs[j][0], ks_refs[j][0],
                              jnp.float32)             # (hkv, page, d)
            v = kv_dequantize(v_refs[j][0], vs_refs[j][0],
                              jnp.float32)
        else:
            k = k_refs[j][0].astype(jnp.float32)
            v = v_refs[j][0].astype(jnp.float32)
        # batched over the kv-head axis: every kv head of this page in one
        # contraction — (hkv, rep, d) x (hkv, page, d) -> (hkv, rep, page)
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,)))) * scale
        # logical token positions of logical page ip*pps + j; garbage and
        # pad pages (block-table entries past the row's allocation) mask
        # out entirely here
        k_pos = (ip * pps + j) * page + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, page), 2)
        s = jnp.where(k_pos < cache_len, s, NEG_INF)

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


def _kv_index(bb, ip, bt_ref, len_ref, *, pps, j):
    # physical page for (row bb, logical page ip*pps + j), all kv heads
    return (bt_ref[bb, ip * pps + j], 0, 0, 0)


def _q_index(bb, ip, bt_ref, len_ref):
    return (bb, 0, 0, 0)


def _paged_attention(q, k_pool, v_pool, block_tables, lengths, k_scale,
                     v_scale, pages_per_step, interpret, kernel_fn,
                     kq: int):
    """Shared wrapper for decode (kq=1) and verify (kq=K) paged attention.

    q: (B, kq, H, D); pools: (P, page, Hkv, D); scales (quantized pools
    only): (P, page, Hkv, 1); block_tables: (B, maxp) int32; lengths:
    (B,) int32.  Returns (B, kq, H, D).
    """
    b, _, h, d = q.shape
    page, hkv = k_pool.shape[1], k_pool.shape[2]
    maxp = block_tables.shape[1]
    assert h % hkv == 0
    rep = h // hkv
    quant = k_scale is not None
    pps = pages_per_step or tuning_for(page, d, hkv).pages_per_step
    pps = max(1, min(int(pps), maxp))

    # (B, kq, H, D) -> (B, Hkv, kq*rep, D): group the rep query heads of
    # each kv head, K draft positions adjacent so a q-block row's draft
    # index is row // rep
    qr = (q.reshape(b, kq, hkv, rep, d).transpose(0, 2, 1, 3, 4)
          .reshape(b, hkv, kq * rep, d))
    # (P, page, Hkv, D) -> (P, Hkv, page, D): one block = one page across
    # ALL kv heads, so the per-page gather is head-fused into a single DMA
    kr = k_pool.transpose(0, 2, 1, 3)
    vr = v_pool.transpose(0, 2, 1, 3)
    # pad the page walk to a multiple of pps; pad entries point at the
    # scratch page 0 and are masked out via lengths
    pad = (-maxp) % pps
    bt = jnp.pad(block_tables.astype(jnp.int32), ((0, 0), (0, pad)))
    lens = lengths.astype(jnp.int32)

    grid = (b, (maxp + pad) // pps)
    kernel = functools.partial(kernel_fn, page=page, pps=pps, quant=quant,
                               scale=d ** -0.5, rep=rep)
    kv_spec = [pl.BlockSpec((1, hkv, page, d),
                            functools.partial(_kv_index, pps=pps, j=j))
               for j in range(pps)]
    in_specs = [pl.BlockSpec((1, hkv, kq * rep, d), _q_index)] \
        + kv_spec + kv_spec
    inputs = [qr] + [kr] * pps + [vr] * pps
    if quant:
        # scales keep their trailing 1-dim, so the in-body dequant
        # broadcasts along lanes with no rank-changing reshape (Mosaic
        # rejects the (hkv, page) -> (hkv, page, 1) shape cast)
        sc_spec = [pl.BlockSpec((1, hkv, page, 1),
                                functools.partial(_kv_index, pps=pps, j=j))
                   for j in range(pps)]
        in_specs += sc_spec + sc_spec
        ksr = k_scale.transpose(0, 2, 1, 3)            # (P, Hkv, page, 1)
        vsr = v_scale.transpose(0, 2, 1, 3)
        inputs += [ksr] * pps + [vsr] * pps

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, hkv, kq * rep, d), _q_index),
            scratch_shapes=[
                pltpu.VMEM((hkv, kq * rep, d), jnp.float32),
                pltpu.VMEM((hkv, kq * rep), jnp.float32),
                pltpu.VMEM((hkv, kq * rep), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, kq * rep, d), q.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(bt, lens, *inputs)
    return (out.reshape(b, hkv, kq, rep, d).transpose(0, 2, 1, 3, 4)
            .reshape(b, kq, h, d))


def flash_paged_decode_tpu(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_tables: jax.Array,
                           lengths: jax.Array, *,
                           k_scale=None, v_scale=None,
                           pages_per_step=None,
                           interpret: Optional[bool] = None
                           ) -> jax.Array:
    """q: (B, 1, H, D); pools: (P, page, Hkv, D); block_tables: (B, maxp)
    int32; lengths: (B,) int32 valid tokens per row.  For int8 pools pass
    ``k_scale``/``v_scale``: (P, page, Hkv, 1) per-token-per-head scales.
    ``pages_per_step`` overrides the recorded tuning.  Returns (B, 1, H, D).
    """
    return _paged_attention(q, k_pool, v_pool, block_tables, lengths,
                            k_scale, v_scale, pages_per_step, interpret,
                            _paged_kernel, kq=1)
