"""Plain float32 reference forward for the dense decoder (qwen3 layout).

Straightforward ``jax.numpy``: embedding, then per layer RMSNorm, q/k/v
projections, per-head q/k RMSNorm, rotary embedding, causal softmax
attention with grouped kv heads, output projection and a SwiGLU MLP, then
the final RMSNorm and the output head.  No kernels, cache, batching or
chunking — it shares no code with the served path beyond the parameter
layout of ``models.dense``.

The weights stay in their stored dtype (bf16 at published widths: a
float32 copy of 18 layers of ``qwen3-8b`` would not fit one chip).  Each
layer's weights are upcast inside the layer scan, and the output head one
vocabulary block at a time, so only one layer or block is ever held in
float32.  Callers run it under ``jax.default_matmul_precision("highest")``
so that float32 matmuls are float32 on a TPU too.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig

_F32 = jnp.float32


def _check_supported(cfg: ModelConfig) -> None:
    if (cfg.family != "dense" or cfg.norm_type != "rmsnorm"
            or cfg.act != "swiglu" or cfg.use_bias or cfg.parallel_block
            or cfg.mrope or cfg.sliding_window is not None):
        raise ValueError(f"the reference covers the qwen3 dense layout, "
                         f"not {cfg.name}")


def _rms(x: jax.Array, scale: jax.Array) -> jax.Array:
    scale = scale.astype(_F32).reshape((1,) * (x.ndim - 1) + (-1,))
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) \
        * scale


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D) rotated by position 0..S-1 (half-split layout)."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=_F32) / d)
    ang = jnp.arange(s, dtype=_F32)[:, None] * inv[None, :]       # (S, D/2)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg: ModelConfig, x: jax.Array, lp: Dict) -> jax.Array:
    lp = jax.tree_util.tree_map(lambda w: w.astype(_F32), lp)
    b, s, _ = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    a = _rms(x, lp["ln1"]["scale"])
    q = (a @ lp["wq"]).reshape(b, s, h, dh)
    k = (a @ lp["wk"]).reshape(b, s, hkv, dh)
    v = (a @ lp["wv"]).reshape(b, s, hkv, dh)
    if cfg.qk_norm:
        q, k = _rms(q, lp["q_norm"]), _rms(k, lp["k_norm"])
    q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
    # every query head reads kv head (head // rep)
    k = jnp.repeat(k, h // hkv, axis=2)
    v = jnp.repeat(v, h // hkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(_F32(dh))
    causal = jnp.tril(jnp.ones((s, s), bool))[None, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, h * dh)
    x = x + attn @ lp["wo"]
    m = _rms(x, lp["ln2"]["scale"])
    mlp = jax.nn.silu(m @ lp["w_gate"]) * (m @ lp["w_up"])
    return x + mlp @ lp["w_down"]


def forward_f32(params: Dict, cfg: ModelConfig, tokens: jax.Array,
                start: int, count: int, vocab_block: int) -> jax.Array:
    """Float32 logits (B, count, padded_vocab) at positions
    ``start .. start+count-1`` of ``tokens`` (B, S).  Only those positions
    go through the output head, ``vocab_block`` columns at a time
    (``vocab_block`` must divide the padded vocabulary)."""
    _check_supported(cfg)
    x = jnp.take(params["embed"], tokens, axis=0).astype(_F32)
    x, _ = jax.lax.scan(lambda c, lp: (_layer(cfg, c, lp), None), x,
                        params["layers"])
    x = _rms(x, params["final_norm"]["scale"])[:, start:start + count]
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    vocab = head.shape[1]
    if vocab % vocab_block:
        raise ValueError(f"vocab_block {vocab_block} does not divide {vocab}")

    def block(i):
        w = jax.lax.dynamic_slice_in_dim(head, i * vocab_block, vocab_block,
                                         axis=1)
        return x @ w.astype(_F32)

    logits = jax.lax.map(block, jnp.arange(vocab // vocab_block))
    return jnp.moveaxis(logits, 0, 2).reshape(x.shape[0], count, vocab)
