"""Jit'd dispatch wrappers: Pallas kernel on TPU, jnp oracle elsewhere.

``backend="auto"`` picks the Pallas kernel on a TPU backend and the jnp
reference elsewhere; ``backend="pallas"`` forces the kernel, which then
runs compiled on a TPU and interpreted elsewhere
(``repro.compat.pallascompat.resolve_interpret``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.compat.pallascompat import on_tpu
from repro.kernels.flash_attention import flash_attention_tpu
from repro.kernels.flash_decode import flash_decode_tpu
from repro.kernels.paged_decode import flash_paged_decode_tpu
from repro.kernels.ref import (decode_ref, flash_ref, paged_decode_quant_ref,
                               paged_decode_ref, paged_verify_quant_ref,
                               paged_verify_ref)
from repro.kernels.spec_verify import flash_paged_verify_tpu


@functools.partial(jax.jit, static_argnames=("causal", "window", "backend"))
def attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
              backend: str = "auto") -> jax.Array:
    """Prefill/train attention. q: (B,Sq,H,D); k/v: (B,Skv,Hkv,D)."""
    use_pallas = backend == "pallas" or (backend == "auto" and on_tpu())
    if use_pallas:
        return flash_attention_tpu(q, k, v, causal=causal, window=window)
    return flash_ref(q, k, v, causal=causal, window=window)


@functools.partial(jax.jit, static_argnames=("window", "backend"))
def decode(q, k_cache, v_cache, cache_len, *, window: Optional[int] = None,
           backend: str = "auto") -> jax.Array:
    """Single-token decode. q: (B,1,H,D); caches: (B,S,Hkv,D)."""
    use_pallas = backend == "pallas" or (backend == "auto" and on_tpu())
    if use_pallas:
        return flash_decode_tpu(q, k_cache, v_cache, cache_len, window=window)
    return decode_ref(q, k_cache, v_cache, cache_len, window=window)


def _at_layer(layer, *pools):
    """The jnp oracles take one layer's (P, page, Hkv, D|1) pools."""
    return pools if layer is None else tuple(p[layer] for p in pools)


@functools.partial(jax.jit, static_argnames=("backend", "pages_per_step"))
def paged_decode(q, k_pool, v_pool, block_tables, lengths, *, layer=None,
                 backend: str = "auto",
                 pages_per_step: Optional[int] = None) -> jax.Array:
    """Block-table paged decode. q: (B,1,H,D); pools: (L,P,page,Hkv,D)
    read at the int32 scalar ``layer``, or one layer's (P,page,Hkv,D);
    block_tables: (B,maxp) int32; lengths: (B,) int32.  The kernel reads
    the pool in place; ``pages_per_step`` overrides the recorded kernel
    tuning (Pallas path only)."""
    use_pallas = backend == "pallas" or (backend == "auto" and on_tpu())
    if use_pallas:
        return flash_paged_decode_tpu(q, k_pool, v_pool, block_tables,
                                      lengths, layer=layer,
                                      pages_per_step=pages_per_step)
    return paged_decode_ref(q, *_at_layer(layer, k_pool, v_pool),
                            block_tables, lengths)


@functools.partial(jax.jit, static_argnames=("backend", "pages_per_step"))
def paged_decode_quant(q, k_pool, v_pool, k_scale, v_scale, block_tables,
                       lengths, *, layer=None, backend: str = "auto",
                       pages_per_step: Optional[int] = None) -> jax.Array:
    """Int8 block-table paged decode (DESIGN.md §6.1-paged): int8 pools
    plus per-token-per-head scale pools (the pools' shape with a trailing
    1) riding the same block-table indirection; the kernel applies the
    scales in its body.  ``layer`` as in :func:`paged_decode`."""
    use_pallas = backend == "pallas" or (backend == "auto" and on_tpu())
    if use_pallas:
        return flash_paged_decode_tpu(q, k_pool, v_pool, block_tables,
                                      lengths, k_scale=k_scale,
                                      v_scale=v_scale, layer=layer,
                                      pages_per_step=pages_per_step)
    return paged_decode_quant_ref(
        q, *_at_layer(layer, k_pool, v_pool, k_scale, v_scale),
        block_tables, lengths)


@functools.partial(jax.jit, static_argnames=("backend", "pages_per_step"))
def paged_verify(q, k_pool, v_pool, block_tables, lengths, *,
                 backend: str = "auto",
                 pages_per_step: Optional[int] = None) -> jax.Array:
    """Multi-token speculative verify over paged KV (DESIGN.md §6.1-spec).
    q: (B,K,H,D) — K new tokens whose KV is already in the pool; pools:
    (P,page,Hkv,D); block_tables: (B,maxp) int32; lengths: (B,) int32
    valid tokens per row before the K new tokens."""
    use_pallas = backend == "pallas" or (backend == "auto" and on_tpu())
    if use_pallas:
        return flash_paged_verify_tpu(q, k_pool, v_pool, block_tables,
                                      lengths,
                                      pages_per_step=pages_per_step)
    return paged_verify_ref(q, k_pool, v_pool, block_tables, lengths)


@functools.partial(jax.jit, static_argnames=("backend", "pages_per_step"))
def paged_verify_quant(q, k_pool, v_pool, k_scale, v_scale, block_tables,
                       lengths, *, backend: str = "auto",
                       pages_per_step: Optional[int] = None) -> jax.Array:
    """Int8 multi-token speculative verify over paged KV: int8 pools plus
    scale pools, dequantized in the kernel body (DESIGN.md §6.1-spec)."""
    use_pallas = backend == "pallas" or (backend == "auto" and on_tpu())
    if use_pallas:
        return flash_paged_verify_tpu(q, k_pool, v_pool, block_tables,
                                      lengths, k_scale=k_scale,
                                      v_scale=v_scale,
                                      pages_per_step=pages_per_step)
    return paged_verify_quant_ref(q, k_pool, v_pool, k_scale, v_scale,
                                  block_tables, lengths)
