"""Dense decoder-only transformer family.

Covers: starcoder2 (LayerNorm+GeLU+bias), qwen3 (RMSNorm+SwiGLU+qk_norm),
command-r-plus (parallel attention/FFN block, no bias), qwen2-vl (M-RoPE,
embedding inputs), and the sliding-window long-context variants.

Parameters are stacked over layers (leading L axis) so the layer stack is a
single ``lax.scan`` — essential for 64-layer configs to compile quickly in the
multi-pod dry-run.  Activation checkpointing wraps the per-layer block.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import common as cm
from repro.models import runtime
from repro.models.attention import (decode_attention, flash_attention,
                                    verify_attention)
from repro.models.config import ModelConfig


def _dt(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# --------------------------------------------------------------------- init
def init(key: jax.Array, cfg: ModelConfig) -> Dict:
    dt = _dt(cfg)
    L, d, f = cfg.n_layers, cfg.d_model, cfg.d_ff
    keys = jax.random.split(key, 16)

    def stack(initfn, k, *shape_args, **kw):
        # vmapped over per-layer keys: under jit the stacked weight is
        # generated in place, with no per-layer copies to concatenate
        return jax.vmap(lambda kk: initfn(kk, *shape_args, **kw))(
            jax.random.split(k, L))

    p: Dict = {
        "embed": cm.embed_init(keys[0], cfg.padded_vocab, d, dt),
        "final_norm": cm.norm_params(d, cfg.norm_type, dt),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = cm.dense_init(keys[1], d, cfg.padded_vocab, dt)

    lyr: Dict = {
        "ln1": _stack_norm(L, d, cfg.norm_type, dt),
        "wq": stack(cm.dense_init, keys[2], d, cfg.q_dim, dt),
        "wk": stack(cm.dense_init, keys[3], d, cfg.kv_dim, dt),
        "wv": stack(cm.dense_init, keys[4], d, cfg.kv_dim, dt),
        "wo": stack(cm.dense_init, keys[5], cfg.q_dim, d, dt),
    }
    if not cfg.parallel_block:
        lyr["ln2"] = _stack_norm(L, d, cfg.norm_type, dt)
    if cfg.qk_norm:
        lyr["q_norm"] = jnp.ones((L, cfg.head_dim), dt)
        lyr["k_norm"] = jnp.ones((L, cfg.head_dim), dt)
    if cfg.use_bias:
        lyr["bq"] = jnp.zeros((L, cfg.q_dim), dt)
        lyr["bk"] = jnp.zeros((L, cfg.kv_dim), dt)
        lyr["bv"] = jnp.zeros((L, cfg.kv_dim), dt)
        lyr["bo"] = jnp.zeros((L, d), dt)
    if cfg.act == "swiglu":
        lyr["w_gate"] = stack(cm.dense_init, keys[6], d, f, dt)
        lyr["w_up"] = stack(cm.dense_init, keys[7], d, f, dt)
        lyr["w_down"] = stack(cm.dense_init, keys[8], f, d, dt)
    else:
        lyr["w_up"] = stack(cm.dense_init, keys[6], d, f, dt)
        lyr["w_down"] = stack(cm.dense_init, keys[7], f, d, dt)
        if cfg.use_bias:
            lyr["b_up"] = jnp.zeros((L, f), dt)
            lyr["b_down"] = jnp.zeros((L, d), dt)
    p["layers"] = lyr
    return p


def _stack_norm(L: int, d: int, norm_type: str, dt) -> Dict:
    if norm_type == "layernorm":
        return {"scale": jnp.ones((L, d), dt), "bias": jnp.zeros((L, d), dt)}
    return {"scale": jnp.ones((L, d), dt)}


# ---------------------------------------------------------------- sub-blocks
def _project_qkv(lp: Dict, cfg: ModelConfig, h: jax.Array, positions: jax.Array
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """h: (B,S,d) -> roped q (B,S,H,dh), k/v (B,S,Hkv,dh)."""
    b, s, _ = h.shape
    q = h @ lp["wq"]
    k = h @ lp["wk"]
    v = h @ lp["wv"]
    if cfg.use_bias:
        q = q + lp["bq"][None, None, :]
        k = k + lp["bk"][None, None, :]
        v = v + lp["bv"][None, None, :]
    if not runtime.attn_batch_only():
        q = cm.shard(q, "batch", None, "model")
        k = cm.shard(k, "batch", None, "model")
        v = cm.shard(v, "batch", None, "model")
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = cm.rms_norm(q, lp["q_norm"])
        k = cm.rms_norm(k, lp["k_norm"])
    if cfg.mrope:
        q = cm.apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = cm.apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = cm.apply_rope(q, positions, cfg.rope_theta)
        k = cm.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mlp(lp: Dict, cfg: ModelConfig, h: jax.Array) -> jax.Array:
    if cfg.act == "swiglu":
        g = cm.shard(h @ lp["w_gate"], "batch", None, "model")
        u = cm.shard(h @ lp["w_up"], "batch", None, "model")
        return (jax.nn.silu(g) * u) @ lp["w_down"]
    u = h @ lp["w_up"]
    if cfg.use_bias:
        u = u + lp["b_up"][None, None, :]
    u = cm.shard(u, "batch", None, "model")
    out = cm.gelu(u) @ lp["w_down"]
    if cfg.use_bias:
        out = out + lp["b_down"][None, None, :]
    return out


def _block_train(lp: Dict, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
                 q_chunk: int, kv_chunk: int, skip_masked: bool) -> jax.Array:
    h = cm.apply_norm(x, lp["ln1"], cfg.norm_type)
    q, k, v = _project_qkv(lp, cfg, h, positions)
    attn = flash_attention(q, k, v, causal=True, window=cfg.sliding_window,
                           q_chunk=q_chunk, kv_chunk=kv_chunk,
                           skip_masked_blocks=skip_masked)
    attn = attn.reshape(x.shape[0], x.shape[1], cfg.q_dim) @ lp["wo"]
    if cfg.use_bias:
        attn = attn + lp["bo"][None, None, :]
    if cfg.parallel_block:
        return cm.shard(x + attn + _mlp(lp, cfg, h), "batch", "seq", None)
    x = x + attn
    h2 = cm.apply_norm(x, lp["ln2"], cfg.norm_type)
    x = x + _mlp(lp, cfg, h2)
    return cm.shard(x, "batch", "seq", None)


# ------------------------------------------------------------------- forward
def apply(params: Dict, cfg: ModelConfig, batch: Dict, *,
          q_chunk: int = 1024, kv_chunk: int = 1024,
          skip_masked_blocks: bool = False) -> jax.Array:
    """Full-sequence forward -> logits (B, S, padded_vocab)."""
    x, positions = embed_inputs(params, cfg, batch)
    block_fn = functools.partial(_block_train, cfg=cfg, positions=positions,
                                 q_chunk=min(q_chunk, x.shape[1]),
                                 kv_chunk=min(kv_chunk, x.shape[1]),
                                 skip_masked=skip_masked_blocks)
    scan_body = jax.checkpoint(lambda carry, lp: (block_fn(lp, x=carry), None))
    x, _ = jax.lax.scan(scan_body, x, params["layers"],
                        unroll=runtime.scan_unroll())
    x = cm.apply_norm(x, params["final_norm"], cfg.norm_type)
    return logits_of(params, cfg, x)


def embed_inputs(params: Dict, cfg: ModelConfig, batch: Dict
                 ) -> Tuple[jax.Array, jax.Array]:
    if cfg.embeds_input and "embeds" in batch:
        x = batch["embeds"].astype(_dt(cfg))
        b, s = x.shape[:2]
    else:
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = jnp.take(params["embed"], tokens, axis=0)
    x = cm.shard(x, "batch", "seq", None)
    positions = batch.get("positions")
    if positions is None:
        shape = (b, s, 3) if cfg.mrope else (b, s)
        base = jnp.arange(s, dtype=jnp.int32)
        positions = jnp.broadcast_to(base[None, :, None] if cfg.mrope
                                     else base[None, :], shape)
    return x, positions


def logits_of(params: Dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return cm.shard(x @ head, "batch", None, "model")


# --------------------------------------------------------------- decode path
def _block_decode(lp: Dict, cfg: ModelConfig, x: jax.Array, kv: Dict,
                  length: jax.Array, position: jax.Array
                  ) -> Tuple[jax.Array, Dict]:
    """One layer, one token.  x: (B,1,d); kv holds this layer's cache slices
    (B,C,Hkv,dh) (+ per-token scales when cfg.kv_quant).

    ``length``/``position`` are () for lock-step decode or (B,) for
    slot-based continuous batching, where each row sits at its own depth
    (the serving engine admits new requests into freed slots mid-decode).
    """
    from repro.models.attention import kv_dequantize, kv_quantize
    b = x.shape[0]
    cap = kv["k"].shape[1]
    h = cm.apply_norm(x, lp["ln1"], cfg.norm_type)
    pos = jnp.broadcast_to(jnp.reshape(position, (-1, 1)), (b, 1))
    if cfg.mrope:
        pos = jnp.broadcast_to(jnp.reshape(position, (-1, 1, 1)), (b, 1, 3))
    q, k, v = _project_qkv(lp, cfg, h, pos)
    slot = jnp.mod(length, cap)                      # ring write (window cache)
    n_valid = jnp.minimum(length + 1, cap)
    writes = {"k": k, "v": v}
    if cfg.kv_quant:
        writes["k"], writes["k_scale"] = kv_quantize(k)
        writes["v"], writes["v_scale"] = kv_quantize(v)
    if jnp.ndim(length) > 0:
        # per-row depths: scatter each row's token at its own slot, attend
        # its own valid prefix (decode_attention takes (B,) cache lengths)
        rows = jnp.arange(b)
        kv = {name: kv[name].at[rows, slot].set(w[:, 0])
              for name, w in writes.items()}
        if cfg.kv_quant:
            kf = kv_dequantize(kv["k"], kv["k_scale"], _dt(cfg))
            vf = kv_dequantize(kv["v"], kv["v_scale"], _dt(cfg))
        else:
            kf, vf = kv["k"], kv["v"]
        attn = decode_attention(q, kf, vf, n_valid)
    elif runtime.decode_seq_shard():
        # §Perf: shard-local ring write + LSE-combined partial attention —
        # avoids GSPMD's cache-sized collectives for the seq-sharded update
        from repro.models.attention import decode_attention_seqsharded
        if cfg.kv_quant:
            attn, kc, vc, ks_, vs_ = decode_attention_seqsharded(
                q, kv["k"], kv["v"], writes["k"], writes["v"], slot, n_valid,
                scales=(kv["k_scale"], kv["v_scale"],
                        writes["k_scale"], writes["v_scale"]))
            kv = {"k": kc, "v": vc, "k_scale": ks_, "v_scale": vs_}
        else:
            attn, kc, vc = decode_attention_seqsharded(
                q, kv["k"], kv["v"], k, v, slot, n_valid)
            kv = {"k": kc, "v": vc}
    else:
        kv = {name: jax.lax.dynamic_update_slice(
            kv[name], w, (0, slot, 0, 0)) for name, w in writes.items()}
        if cfg.kv_quant:
            # int8 cache stream; dequant fuses into the attention read on TPU
            kf = kv_dequantize(kv["k"], kv["k_scale"], _dt(cfg))
            vf = kv_dequantize(kv["v"], kv["v_scale"], _dt(cfg))
        else:
            kf, vf = kv["k"], kv["v"]
        attn = decode_attention(q, kf, vf, n_valid)
    attn = attn.reshape(b, 1, cfg.q_dim) @ lp["wo"]
    if cfg.use_bias:
        attn = attn + lp["bo"][None, None, :]
    if cfg.parallel_block:
        return x + attn + _mlp(lp, cfg, h), kv
    x = x + attn
    h2 = cm.apply_norm(x, lp["ln2"], cfg.norm_type)
    return x + _mlp(lp, cfg, h2), kv


def decode_step(params: Dict, cfg: ModelConfig, cache: Dict, token: jax.Array
                ) -> Tuple[jax.Array, Dict]:
    """cache: {"k": (L,B,C,Hkv,dh), "v": ..., "length": () or (B,)} ;
    token: (B,1).  A (B,) length decodes each row at its own depth (slot
    continuous batching).  With cfg.kv_quant the caches are int8 plus
    "k_scale"/"v_scale"."""
    x = jnp.take(params["embed"], token, axis=0)
    length = cache["length"]
    kv_names = [n for n in ("k", "v", "k_scale", "v_scale") if n in cache]

    def step(x, xs):
        lp, kv = xs
        x, kv = _block_decode(lp, cfg, x, kv, length, length)
        return x, kv

    x, kv_new = jax.lax.scan(
        step, x, (params["layers"], {n: cache[n] for n in kv_names}),
        unroll=runtime.scan_unroll())
    x = cm.apply_norm(x, params["final_norm"], cfg.norm_type)
    logits = logits_of(params, cfg, x)
    return logits, {**kv_new, "length": length + 1}


# ---------------------------------------------------------------- paged decode
PAGED_POOL_NAMES = ("k_pool", "v_pool", "k_scale_pool", "v_scale_pool")


def init_paged_pools(cfg: ModelConfig, num_pages: int, page_size: int,
                     dtype=None) -> Dict:
    """Allocate the shared KV page pools: {"k_pool","v_pool"} each
    (L, P, page, Hkv, dh).  Page 0 is conventionally the engine's scratch
    page (writes for unallocated rows land there and are never attended).

    With ``cfg.kv_quant`` the pools are int8 and two parallel *scale pools*
    {"k_scale_pool","v_scale_pool"} (L, P, page, Hkv, 1) bf16 ride the same
    block-table indirection — one per-(token, head) scale per pool entry
    (DESIGN.md §6.1-paged).
    """
    shape = (cfg.n_layers, num_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
    if cfg.kv_quant:
        sshape = shape[:-1] + (1,)
        return {"k_pool": jnp.zeros(shape, jnp.int8),
                "v_pool": jnp.zeros(shape, jnp.int8),
                "k_scale_pool": jnp.zeros(sshape, jnp.bfloat16),
                "v_scale_pool": jnp.zeros(sshape, jnp.bfloat16)}
    dt = dtype or _dt(cfg)
    return {"k_pool": jnp.zeros(shape, dt), "v_pool": jnp.zeros(shape, dt)}


def prefill_to_pages(pools: Dict, kv: Dict, phys_pages: jax.Array) -> Dict:
    """Scatter a contiguous prefill cache into pool pages.

    pools: {"k_pool","v_pool"[,"k_scale_pool","v_scale_pool"]}
    (L, P, page, Hkv, dh|1); kv: {"k","v"[,"k_scale","v_scale"]}
    (L, n, plen, Hkv, dh|1) with plen a multiple of the page size — a
    quantized prefill cache is scattered as-is, NOT re-quantized, so paged
    pages hold bit-identical values to the slot cache;
    phys_pages: (n, plen//page) int32 physical page per (row, logical page).
    Entries for pages past a row's real prompt point at the scratch page 0
    (several rows may alias it; the garbage is masked by per-row lengths).
    """
    page = pools["k_pool"].shape[2]
    out = {}
    for pname in PAGED_POOL_NAMES:
        if pname not in pools:
            continue
        name = pname[:-5]                              # strip "_pool"
        L, n, plen = kv[name].shape[:3]
        src = kv[name].reshape((L, n, plen // page, page) + kv[name].shape[3:])
        out[pname] = pools[pname].at[:, phys_pages].set(src)
    return out


def _gather_layer_pages(pools: Dict, l: jax.Array, block_tables: jax.Array,
                        cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """Gather layer ``l``'s pages into contiguous (B, maxp*page, Hkv, dh)
    K/V, dequantizing int8 pools through their scale pools (the same
    ``kv_dequantize`` the slot path uses, so quantized-paged stays
    bit-identical to quantized-slot)."""
    from repro.models.attention import kv_dequantize
    b, maxp = block_tables.shape
    page = pools["k_pool"].shape[2]

    def gather(pname):
        p = pools[pname][l][block_tables]
        return p.reshape((b, maxp * page) + p.shape[3:])

    kg, vg = gather("k_pool"), gather("v_pool")
    if "k_scale_pool" in pools:
        kg = kv_dequantize(kg, gather("k_scale_pool"), _dt(cfg))
        vg = kv_dequantize(vg, gather("v_scale_pool"), _dt(cfg))
    return kg, vg


def _scatter_pool_writes(pools: Dict, l: jax.Array, phys_page: jax.Array,
                         page_slot: jax.Array, k: jax.Array, v: jax.Array,
                         squeeze: bool) -> Dict:
    """Write new-token KV into layer ``l``'s pages, quantizing on page
    write for int8 pools.  k/v: (B, K, Hkv, dh); phys_page/page_slot: (B,)
    when ``squeeze`` (single token) else (B, K)."""
    from repro.models.attention import kv_quantize
    writes = {"k_pool": k, "v_pool": v}
    if "k_scale_pool" in pools:
        writes["k_pool"], writes["k_scale_pool"] = kv_quantize(k)
        writes["v_pool"], writes["v_scale_pool"] = kv_quantize(v)
    return {name: pools[name].at[l, phys_page, page_slot].set(
                w[:, 0] if squeeze else w)
            for name, w in writes.items()}


def _block_decode_paged(lp: Dict, cfg: ModelConfig, x: jax.Array, pools: Dict,
                        l: jax.Array, block_tables: jax.Array,
                        lengths: jax.Array, phys_page: jax.Array,
                        page_slot: jax.Array) -> Tuple[jax.Array, Dict]:
    """One layer, one token, against layer ``l`` of the KV page pools.

    x: (B,1,d); pools: full (L, P, page, Hkv, dh|1) arrays carried through
    the layer scan — indexing layer ``l`` here (instead of slicing pools as
    scan xs) keeps the update in-place under buffer donation, so decode
    cost does not scale with pool size (§Perf-kernels); block_tables:
    (B, maxp); lengths: (B,) valid tokens per row; phys_page/page_slot:
    (B,) physical page and in-page slot where this token's KV is written
    (rows without an allocated page are pointed at the scratch page 0 by
    the engine — their write is garbage that a later real write or mask
    supersedes).
    """
    b = x.shape[0]
    h = cm.apply_norm(x, lp["ln1"], cfg.norm_type)
    pos = jnp.broadcast_to(jnp.reshape(lengths, (-1, 1)), (b, 1))
    if cfg.mrope:
        pos = jnp.broadcast_to(jnp.reshape(lengths, (-1, 1, 1)), (b, 1, 3))
    q, k, v = _project_qkv(lp, cfg, h, pos)
    pools = _scatter_pool_writes(pools, l, phys_page, page_slot, k, v,
                                 squeeze=True)
    # the kernel reads layer ``l`` of the whole carried pool through the
    # block table; off the TPU ``ops`` runs the gather oracle
    from repro.kernels import ops
    attend = (ops.paged_decode_quant if "k_scale_pool" in pools
              else ops.paged_decode)
    attn = attend(q, *(pools[n] for n in PAGED_POOL_NAMES if n in pools),
                  block_tables, lengths + 1, layer=l)
    attn = attn.reshape(b, 1, cfg.q_dim) @ lp["wo"]
    if cfg.use_bias:
        attn = attn + lp["bo"][None, None, :]
    if cfg.parallel_block:
        return x + attn + _mlp(lp, cfg, h), pools
    x = x + attn
    h2 = cm.apply_norm(x, lp["ln2"], cfg.norm_type)
    return x + _mlp(lp, cfg, h2), pools


def paged_decode_step(params: Dict, cfg: ModelConfig, cache: Dict,
                      token: jax.Array) -> Tuple[jax.Array, Dict]:
    """One decode step against paged KV (DESIGN.md §6.1, paged backend).

    cache: {"k_pool"/"v_pool": (L, P, page, Hkv, dh)
            [, "k_scale_pool"/"v_scale_pool": (L, P, page, Hkv, 1)],
            "block_tables": (B, maxp) int32, "lengths": (B,) int32};
    token: (B,1).  Every row decodes at its own depth; the new token's KV is
    scattered into physical page ``bt[b, lengths[b] // page]`` at slot
    ``lengths[b] % page`` (quantize-on-write for int8 pools).  The engine
    guarantees that page is allocated for rows that are actually decoding;
    riding-along rows resolve to the scratch page 0.

    The pools ride the layer scan as **carry** (layer picked by index), not
    as sliced xs — under ``jax.jit(..., donate_argnums=...)`` the scatter
    is then a true in-place update and step cost is independent of pool
    size (§Perf-kernels).  Attention is ``repro.kernels.ops.paged_decode``
    (``_quant`` for int8 pools): the Pallas block-table kernel on a TPU,
    the gather oracle elsewhere.  Returns (logits, cache with lengths+1).
    """
    x = jnp.take(params["embed"], token, axis=0)
    bt = cache["block_tables"]
    lengths = cache["lengths"]
    page = cache["k_pool"].shape[2]
    maxp = bt.shape[1]
    rows = jnp.arange(bt.shape[0])
    page_idx = jnp.minimum(lengths // page, maxp - 1)
    phys_page = bt[rows, page_idx]
    page_slot = lengths % page
    pool_names = [n for n in PAGED_POOL_NAMES if n in cache]

    def step(carry, xs):
        x, pools = carry
        lp, l = xs
        x, pools = _block_decode_paged(lp, cfg, x, pools, l, bt, lengths,
                                       phys_page, page_slot)
        return (x, pools), None

    (x, pools_new), _ = jax.lax.scan(
        step, (x, {n: cache[n] for n in pool_names}),
        (params["layers"], jnp.arange(cfg.n_layers)),
        unroll=runtime.scan_unroll())
    x = cm.apply_norm(x, params["final_norm"], cfg.norm_type)
    logits = logits_of(params, cfg, x)
    return logits, {**pools_new, "block_tables": bt, "lengths": lengths + 1}


def _block_verify_paged(lp: Dict, cfg: ModelConfig, x: jax.Array, pools: Dict,
                        l: jax.Array, block_tables: jax.Array,
                        lengths: jax.Array, phys_page: jax.Array,
                        page_slot: jax.Array) -> Tuple[jax.Array, Dict]:
    """One layer, K new tokens, against layer ``l`` of the KV page pools
    (speculative verify, DESIGN.md §6.1-spec).

    x: (B,K,d); pools: full (L, P, page, Hkv, dh|1) arrays carried through
    the layer scan (same in-place-under-donation layout as
    ``_block_decode_paged``); block_tables: (B, maxp); lengths: (B,) valid
    tokens per row BEFORE the K new tokens; phys_page/page_slot: (B,K)
    physical page and in-page slot where token j's KV is written (position
    ``lengths[b]+j``; rows without an allocated page there are pointed at
    the scratch page 0 by the engine).
    """
    b, kq = x.shape[:2]
    h = cm.apply_norm(x, lp["ln1"], cfg.norm_type)
    pos = lengths[:, None] + jnp.arange(kq, dtype=lengths.dtype)[None, :]
    if cfg.mrope:
        pos = jnp.broadcast_to(pos[..., None], (b, kq, 3))
    q, k, v = _project_qkv(lp, cfg, h, pos)
    pools = _scatter_pool_writes(pools, l, phys_page, page_slot, k, v,
                                 squeeze=False)
    kg, vg = _gather_layer_pages(pools, l, block_tables, cfg)
    attn = verify_attention(q, kg, vg, lengths)
    attn = attn.reshape(b, kq, cfg.q_dim) @ lp["wo"]
    if cfg.use_bias:
        attn = attn + lp["bo"][None, None, :]
    if cfg.parallel_block:
        return x + attn + _mlp(lp, cfg, h), pools
    x = x + attn
    h2 = cm.apply_norm(x, lp["ln2"], cfg.norm_type)
    return x + _mlp(lp, cfg, h2), pools


def paged_verify_step(params: Dict, cfg: ModelConfig, cache: Dict,
                      tokens: jax.Array) -> Tuple[jax.Array, Dict]:
    """One speculative verify step against paged KV (DESIGN.md §6.1-spec).

    cache: {"k_pool"/"v_pool": (L, P, page, Hkv, dh)
            [, "k_scale_pool"/"v_scale_pool": (L, P, page, Hkv, 1)],
            "block_tables": (B, maxp) int32, "lengths": (B,) int32};
    tokens: (B, K) — the pending token followed by the k draft tokens.
    Token j's KV is scattered into physical page
    ``bt[b, (lengths[b]+j) // page]`` at slot ``(lengths[b]+j) % page``
    (quantize-on-write for int8 pools), then all K positions attend the
    gathered pages with per-query causal bounds (query j sees positions
    ``<= lengths[b]+j``).  The engine guarantees pages are allocated
    through ``lengths+K`` for verifying rows; riding-along rows resolve to
    the scratch page 0.  Pools are scan carry, in-place under donation
    (§Perf-kernels).  Returns (logits (B,K,V), cache) — ``lengths`` is NOT
    advanced: the engine owns advancement, which depends on how many draft
    tokens were accepted.
    """
    x = jnp.take(params["embed"], tokens, axis=0)
    bt = cache["block_tables"]
    lengths = cache["lengths"]
    page = cache["k_pool"].shape[2]
    maxp = bt.shape[1]
    b, kq = tokens.shape
    rows = jnp.arange(b)
    pos_abs = lengths[:, None] + jnp.arange(kq, dtype=lengths.dtype)[None, :]
    page_idx = jnp.minimum(pos_abs // page, maxp - 1)
    phys_page = bt[rows[:, None], page_idx]
    page_slot = pos_abs % page
    pool_names = [n for n in PAGED_POOL_NAMES if n in cache]

    def step(carry, xs):
        x, pools = carry
        lp, l = xs
        x, pools = _block_verify_paged(lp, cfg, x, pools, l, bt, lengths,
                                       phys_page, page_slot)
        return (x, pools), None

    (x, pools_new), _ = jax.lax.scan(
        step, (x, {n: cache[n] for n in pool_names}),
        (params["layers"], jnp.arange(cfg.n_layers)),
        unroll=runtime.scan_unroll())
    x = cm.apply_norm(x, params["final_norm"], cfg.norm_type)
    logits = logits_of(params, cfg, x)
    return logits, {**pools_new, "block_tables": bt, "lengths": lengths}


def init_cache(cfg: ModelConfig, batch: int, capacity: int, dtype=None) -> Dict:
    dt = dtype or _dt(cfg)
    cap = capacity if cfg.sliding_window is None else min(capacity,
                                                          cfg.sliding_window)
    shape = (cfg.n_layers, batch, cap, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt),
            "length": jnp.zeros((), jnp.int32)}


def prefill(params: Dict, cfg: ModelConfig, batch: Dict, *,
            q_chunk: int = 1024, kv_chunk: int = 1024,
            capacity: Optional[int] = None,
            last_positions: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, Dict]:
    """Run the prompt, build the KV cache, return last-position logits.

    ``capacity`` is the cache size to allocate (>= prompt length for full
    attention; defaults to the prompt length, which leaves no room to decode —
    the serving engine passes prompt+max_new).  Sliding-window configs use a
    ring cache of size ``sliding_window`` with the invariant
    ``slot(position p) = p % window``.

    ``last_positions`` ((B,) int32) extracts each row's logits at its own
    final *real* token instead of the batch's last column — the slot engine
    right-pads mixed-length prompts, which causal masking keeps inert, so a
    row's true continuation point is ``len(prompt_i) - 1``.
    """
    x, positions = embed_inputs(params, cfg, batch)
    b, s = x.shape[:2]
    if cfg.sliding_window is None:
        cap = max(s, capacity or s)
    else:
        cap = min(cfg.sliding_window, capacity or cfg.sliding_window)

    def step(carry, lp):
        x = carry
        h = cm.apply_norm(x, lp["ln1"], cfg.norm_type)
        q, k, v = _project_qkv(lp, cfg, h, positions)
        attn = flash_attention(q, k, v, causal=True, window=cfg.sliding_window,
                               q_chunk=min(q_chunk, s), kv_chunk=min(kv_chunk, s))
        attn = attn.reshape(b, s, cfg.q_dim) @ lp["wo"]
        if cfg.use_bias:
            attn = attn + lp["bo"][None, None, :]
        if cfg.parallel_block:
            x = x + attn + _mlp(lp, cfg, h)
        else:
            x = x + attn
            x = x + _mlp(lp, cfg, cm.apply_norm(x, lp["ln2"], cfg.norm_type))
        x = cm.shard(x, "batch", "seq", None)

        def ring(a):
            if cap <= s:
                # keep the last ``cap`` tokens, ring-rotated so that the
                # token at absolute position p sits at slot p % cap.
                return jnp.roll(a[:, -cap:], shift=s % cap, axis=1)
            padw = [(0, 0), (0, cap - s)] + [(0, 0)] * (a.ndim - 2)
            return jnp.pad(a, padw)

        out = {"k": k, "v": v}
        if cfg.kv_quant:
            from repro.models.attention import kv_quantize
            out["k"], out["k_scale"] = kv_quantize(k)
            out["v"], out["v_scale"] = kv_quantize(v)
        return x, {n: ring(a) for n, a in out.items()}

    step = jax.checkpoint(step)
    x, kvs = jax.lax.scan(step, x, params["layers"],
                          unroll=runtime.scan_unroll())
    x = cm.apply_norm(x, params["final_norm"], cfg.norm_type)
    if last_positions is None:
        x_last = x[:, -1:]
    else:
        x_last = x[jnp.arange(b), last_positions][:, None]
    logits = logits_of(params, cfg, x_last)
    cache = {**kvs, "length": jnp.asarray(s, jnp.int32)}
    return logits, cache
