"""Pallas TPU paged flash-decode: block-table attention over a KV page pool.

The paged serving engine (DESIGN.md §6.1-paged) stores KV in a shared pool
of fixed-size pages; each sequence owns a per-row *block table* mapping
logical page index -> physical page.  Decode attention then has no
contiguous cache to stream — the kernel walks a sequence's pages in logical
order and resolves each one through the block table.

Layout (DESIGN.md §Perf-kernels): the kernel reads the pool **as it is
stored**, ``(L, P, page, Hkv, D)`` (``models.dense.init_paged_pools``), in
place in HBM.  The layer index, the block table, the per-row bounds and
page counts are scalar-prefetched to SMEM, and the body fetches page
``bt[b, i]`` of layer ``l`` by hand, so neither a per-layer slice nor a
transpose of the pool reaches the custom call: the decode scan copies no
pool.

The grid is one step per row.  A row walks only its valid pages,
``pages_per_step`` to a block, double-buffered: the DMAs of block ``i+1``
run while block ``i`` is scored.  A block's pages (all kv heads each) are
viewed as one ``(pps·page·Hkv, D)`` matrix; the ``(kq·H, D)`` query block
is scored against it in one 2-D matmul, the pairs whose kv heads differ
are masked out, and the online softmax takes one update a block — the
same scores as a per-head contraction, with ``Hkv``-fold flops in a
memory-bound step.  A table's empty tail costs nothing: the pipeline of
a BlockSpec grid would visit every ``(row, page)`` of the table.
``pages_per_step`` per ``(page_size, head_dim, hkv)`` comes from
``repro.kernels.tuning``.

One body serves single-token decode (``kq = 1``) and multi-token
speculative verify (``kq = K``, ``spec_verify``): query block row ``r`` is
draft ``r // H`` and attends positions ``< bound[b] + r // H``.

The quantized variant streams int8 pages plus the layer's per-token-per-
head scales, one lane-dense ``(1, page·Hkv)`` row a page fetched through
the same block table; each scale multiplies its column's score and
probability (dequantization factored out of the two matmuls).  The jnp
oracles are ``ref.paged_decode_ref`` / ``ref.paged_decode_quant_ref``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat.pallascompat import resolve_interpret, tpu_compiler_params
from repro.models.attention import NEG_INF
from repro.kernels.tuning import tuning_for


def _paged_kernel(layer_ref, bt_ref, bound_ref, npg_ref, q_ref, bias_ref,
                  tok_ref, draft_ref, *refs, page: int, pps: int,
                  quant: bool, scale: float, bf16_mxu: bool):
    """One grid step walks one row's pages, ``pps`` to a block.

    refs: k, v[, k_scale, v_scale] (HBM), o, then the VMEM buffers of
    those (2 slots × pps pages) and their DMA semaphores.  q block (R, D)
    with R = kq·H; bias (R, pps·page·Hkv): 0 where the query row's kv
    head is the column's, -inf elsewhere; tok (1, pps·page·Hkv): the
    column's position within a block; draft (R, 1): the row's draft
    index.

    ``bf16_mxu`` (bf16 queries, bf16 or int8 pages): the matmuls take
    bf16 operands with float32 accumulation, one MXU pass each.  The
    scores lose nothing — q and K are bf16-exact, so their products are
    exact in float32.  The probabilities go in as a bf16 head and a
    bf16 tail, p ≈ hi + lo, to 16 significant bits.
    """
    n_src = 4 if quant else 2
    hbm, o_ref = refs[:n_src], refs[n_src]
    bufs, sem = refs[n_src + 1:2 * n_src + 1], refs[2 * n_src + 1]
    row = pl.program_id(0)
    layer, n = layer_ref[0], npg_ref[row]
    mm = jnp.bfloat16 if bf16_mxu else jnp.float32

    @pl.when(row == 0)
    def _zero():
        # pages a block does not fill keep what the buffer held; start
        # from zeros so that is always finite (their columns are masked)
        for buf in bufs:
            buf[...] = jnp.zeros_like(buf)

    def copies(blk, slot, j):
        # page j of block blk: (layer, physical page) of the K/V pools;
        # the scale rows are the read layer's already
        p = bt_ref[row, blk * pps + j]
        return [pltpu.make_async_copy(
                    src.at[layer, p] if t < 2 else src.at[p],
                    buf.at[slot, j], sem.at[t, slot])
                for t, (src, buf) in enumerate(zip(hbm, bufs))]

    def each_page(blk, slot, act):
        def one(j, carry):
            @pl.when(blk * pps + j < n)
            def _go():
                for c in copies(blk, slot, j):
                    act(c)
            return carry
        jax.lax.fori_loop(0, pps, one, 0)

    each_page(0, 0, lambda c: c.start())
    q = q_ref[...].astype(mm)                          # (R, d)
    limit = bound_ref[row] + draft_ref[...]            # (R, 1)

    def flat(buf, slot):                               # (pps*page*hkv, d)
        x = buf[slot].astype(jnp.float32)
        return x.reshape(-1, x.shape[-1]).astype(mm)

    def lanes(buf, slot):                              # (1, pps*page*hkv)
        return jnp.concatenate([buf[slot, j].astype(jnp.float32)
                                for j in range(pps)], axis=1)

    def pv(p, v):
        if not bf16_mxu:
            return jnp.dot(p, v, preferred_element_type=jnp.float32)
        hi = p.astype(mm)
        lo = (p - hi.astype(jnp.float32)).astype(mm)
        return (jnp.dot(hi, v, preferred_element_type=jnp.float32)
                + jnp.dot(lo, v, preferred_element_type=jnp.float32))

    def block(blk, carry):
        m, l, acc = carry
        slot = blk % 2

        @pl.when((blk + 1) * pps < n)
        def _next():
            each_page(blk + 1, 1 - slot, lambda c: c.start())

        each_page(blk, slot, lambda c: c.wait())
        s = jax.lax.dot_general(q, flat(bufs[0], slot),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if quant:
            # int8 pages: a column's per-token-per-head scale multiplies
            # its score, and its probability before the value product —
            # the dequantized K and V, factored out of the matmuls
            s = s * lanes(bufs[2], slot)
        pos = blk * (pps * page) + tok_ref[...]
        # positions past the query's bound read NEG_INF (finite, as the
        # oracle masks); columns past the row's last page, and of another
        # kv head, read -inf and weigh nothing even in a row with no
        # valid position
        s = jnp.where(pos < limit, s, NEG_INF)
        s = jnp.where(pos < n * page, s, -jnp.inf) + bias_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        if quant:
            p = p * lanes(bufs[3], slot)
        return m_new, l, acc * alpha + pv(p, flat(bufs[1], slot))

    r, d = q.shape
    m, l, acc = jax.lax.fori_loop(
        0, (n + pps - 1) // pps, block,
        (jnp.full((r, 1), NEG_INF, jnp.float32),
         jnp.zeros((r, 1), jnp.float32), jnp.zeros((r, d), jnp.float32)))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _row_index(row, *_):
    return (row, 0, 0)


def _const_index(row, *_):
    return (0, 0)


def _paged_attention(q, k_pool, v_pool, block_tables, bound, k_scale,
                     v_scale, layer, pages_per_step, interpret):
    """Shared wrapper for decode (kq=1) and verify (kq=K) paged attention.

    q: (B, kq, H, D); pools: (L, P, page, Hkv, D), or (P, page, Hkv, D)
    for one layer; scales (quantized pools only): the same with a
    trailing 1; layer: () int32 layer of the pools to read (default 0);
    block_tables: (B, maxp) int32; bound: (B,) int32 — query ``j`` of row
    ``b`` attends logical positions ``< bound[b] + j``.  Returns
    (B, kq, H, D).
    """
    if k_pool.ndim == 4:                               # one layer: (1, P, ...)
        k_pool, v_pool = k_pool[None], v_pool[None]
        k_scale, v_scale = (None if s is None else s[None]
                            for s in (k_scale, v_scale))
    layer = jnp.reshape(jnp.asarray(0 if layer is None else layer,
                                    jnp.int32), (1,))
    b, kq, h, d = q.shape
    n_pages, page, hkv = k_pool.shape[1:4]
    maxp = block_tables.shape[1]
    assert h % hkv == 0
    rep = h // hkv
    R, C = kq * h, page * hkv
    quant = k_scale is not None
    pps = pages_per_step or tuning_for(page, d, hkv).pages_per_step
    pps = max(1, min(int(pps), maxp))

    bound = bound.astype(jnp.int32)
    # pages each row walks: those holding a position some query attends;
    # a row with none (a length-0 decode row) walks the whole table, so
    # it averages uniformly over it as the gather oracle does
    need = (bound + kq - 1 + page - 1) // page
    npg = jnp.where(need > 0, jnp.minimum(need, maxp), maxp).astype(jnp.int32)

    # static masks of the (R, pps*C) score block: q row r is draft r // h
    # of query head r % h, whose kv head is (r % h) // rep; column c is
    # position c // hkv of the block, of kv head c % hkv
    r_, c_ = np.arange(R), np.arange(pps * C)
    bias = np.where((r_[:, None] % h) // rep == c_[None, :] % hkv, 0.0,
                    -np.inf).astype(np.float32)
    tok = (c_ // hkv).astype(np.int32)[None, :]
    draft = (r_ // h).astype(np.int32)[:, None]

    bf16_mxu = (q.dtype == jnp.bfloat16
                and k_pool.dtype in (jnp.bfloat16, jnp.int8))
    kernel = functools.partial(_paged_kernel, page=page, pps=pps,
                               quant=quant, scale=d ** -0.5,
                               bf16_mxu=bf16_mxu)
    sq = pl.squeezed
    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
    inputs = [k_pool, v_pool]
    buffers = [pltpu.VMEM((2, pps, page, hkv, d), k_pool.dtype)] * 2
    if quant:
        # the layer's scales as one lane-dense (1, page*hkv) row a page,
        # in the score block's column order, in float32: the stored
        # (page, hkv, 1) minor dims do not tile, and a bf16 row is half a
        # packed tile no DMA can slice.  This relayout (4/d of the
        # layer's int8 bytes) is the one copy the kernel asks for
        def rows(s):
            return s[layer[0]].astype(jnp.float32).reshape(n_pages, 1, C)
        inputs += [rows(k_scale), rows(v_scale)]
        buffers += [pltpu.VMEM((2, pps, 1, C), jnp.float32)] * 2

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b,),
            in_specs=[pl.BlockSpec((sq, R, d), _row_index),
                      pl.BlockSpec((R, pps * C), _const_index),
                      pl.BlockSpec((1, pps * C), _const_index),
                      pl.BlockSpec((R, 1), _const_index)]
            + [hbm] * len(inputs),
            out_specs=pl.BlockSpec((sq, R, d), _row_index),
            scratch_shapes=buffers + [
                pltpu.SemaphoreType.DMA((len(inputs), 2))],
        ),
        out_shape=jax.ShapeDtypeStruct((b, R, d), q.dtype),
        # one row at a time: the first step zeroes the page buffers
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=resolve_interpret(interpret),
    )(layer, block_tables.astype(jnp.int32), bound, npg,
      q.reshape(b, R, d), jnp.asarray(bias), jnp.asarray(tok),
      jnp.asarray(draft), *inputs)
    return out.reshape(b, kq, h, d)


def flash_paged_decode_tpu(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_tables: jax.Array,
                           lengths: jax.Array, *,
                           k_scale=None, v_scale=None, layer=None,
                           pages_per_step=None,
                           interpret: Optional[bool] = None
                           ) -> jax.Array:
    """q: (B, 1, H, D); pools: (L, P, page, Hkv, D) read at layer
    ``layer`` (a traced int32 scalar), or one layer's (P, page, Hkv, D);
    block_tables: (B, maxp) int32; lengths: (B,) int32 valid tokens per
    row.  For int8 pools pass ``k_scale``/``v_scale``: the pools' shape
    with a trailing 1, per-token-per-head scales.  ``pages_per_step``
    overrides the recorded tuning.  Returns (B, 1, H, D).
    """
    return _paged_attention(q, k_pool, v_pool, block_tables, lengths,
                            k_scale, v_scale, layer, pages_per_step,
                            interpret)
