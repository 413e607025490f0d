"""Plain float32 reference of a Qwen3 dense decoder, computed in blocks so
that it fits beside the served weights on one chip.

It follows the published architecture (Qwen3, as in the Hugging Face
``Qwen3ForCausalLM``): token embedding; per layer RMSNorm, q/k/v
projections, per-head RMSNorm of q and k, rotary embedding (half-split,
base ``rope_theta``), causal softmax attention with grouped kv heads,
output projection, residual, RMSNorm, SwiGLU MLP, residual; final RMSNorm
and the output head.  RMSNorm epsilon 1e-6.  It imports nothing of the
program: it reads the weight arrays the benchmark drew and the sizes of
the configuration file.

Each layer runs as one jitted call on the whole (padded) sequence with
its weights upcast to float32 inside; attention is computed over blocks
of query rows and the MLP over blocks of rows, and the output head over
blocks of the vocabulary at the scored positions only.  Callers hold the
default matmul precision at ``highest``.  ``control`` gives the control,
the same computation with every weight matmul a precision step below the
served bfloat16: ``"int8"`` (weights per output channel, activations per
row, symmetric) or ``"fp8"`` (float8 e4m3, scaled the same way).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.work import Sizes

F32 = jnp.float32
EPS = 1e-6
BLOCK = 256          # query rows / MLP rows per block
SEQ_BUCKET = 1024    # sequences pad to a multiple of this (few programs)
SCORED_MIN = 128     # scored positions pad to a power of two >= this


def _rms(x, g):
    g = g.astype(F32).reshape((1,) * (x.ndim - 1) + (-1,))
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * g


def _int8(a, axis):
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(a / scale), -127, 127) * scale


def _fp8(a, axis):
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


LOWER = {"int8": _int8, "fp8": _fp8}


def _mm(a, w, quant):
    w = w.astype(F32)
    if quant:
        low = LOWER[quant]
        return low(a, -1) @ low(w, 0)
    return a @ w


def _rope(x, theta):
    """x: (S, H, D) at positions 0..S-1."""
    s, d = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("s", "theta", "quant"))
def _layer(layers: Dict, l, x, *, s: Sizes, theta: float, quant):
    lp = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, l, keepdims=False), layers)
    n = x.shape[0]
    hkv, rep, dh = s.kv_heads, s.heads // s.kv_heads, s.head_dim
    h = _rms(x, lp["ln1"]["scale"])
    q = _mm(h, lp["wq"], quant).reshape(n, s.heads, dh)
    k = _mm(h, lp["wk"], quant).reshape(n, hkv, dh)
    v = _mm(h, lp["wv"], quant).reshape(n, hkv, dh)
    q = _rope(_rms(q, lp["q_norm"]), theta).reshape(n, hkv, rep, dh)
    k = _rope(_rms(k, lp["k_norm"]), theta)
    kpos = jnp.arange(n)

    def attend(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * BLOCK, BLOCK)
        sc = jnp.einsum("qgrd,kgd->grqk", qb, k) / jnp.sqrt(F32(dh))
        qpos = i * BLOCK + jnp.arange(BLOCK)
        causal = kpos[None, :] <= qpos[:, None]
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", p, v).reshape(BLOCK, -1)

    attn = jax.lax.map(attend, jnp.arange(n // BLOCK)).reshape(n, -1)
    x = x + _mm(attn, lp["wo"], quant)

    def mlp(xb):
        m = _rms(xb, lp["ln2"]["scale"])
        g = jax.nn.silu(_mm(m, lp["w_gate"], quant)) * _mm(m, lp["w_up"],
                                                           quant)
        return xb + _mm(g, lp["w_down"], quant)

    return jax.lax.map(mlp, x.reshape(n // BLOCK, BLOCK, -1)).reshape(n, -1)


@functools.partial(jax.jit, static_argnames=("s", "quant"))
def _head(params: Dict, x, *, s: Sizes, quant):
    x = _rms(x, params["final_norm"]["scale"])
    head = params["embed"].T if s.tied else params["lm_head"]
    nb = 8
    vb = s.padded_vocab // nb

    def block(i):
        return _mm(x, jax.lax.dynamic_slice_in_dim(head, i * vb, vb, axis=1),
                   quant)

    out = jax.lax.map(block, jnp.arange(nb))           # (nb, n, vb)
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], -1)


@jax.jit
def _embed(embed, tokens):
    return jnp.take(embed, tokens, axis=0).astype(F32)


def logits(params: Dict, s: Sizes, theta: float, tokens: Sequence[int],
           start: int, count: int, control: Optional[str] = None
           ) -> np.ndarray:
    """Float32 logits over the real vocabulary, (count, vocab), at
    positions ``start .. start+count-1`` of ``tokens``; with ``control``
    the control's."""
    quant = control
    toks = np.asarray(tokens, np.int32)
    n = -(-len(toks) // SEQ_BUCKET) * SEQ_BUCKET
    padded = np.zeros(n, np.int32)
    padded[: len(toks)] = toks        # trailing pads: inert under causality
    dev = jax.tree_util.tree_leaves(params)[0].devices().pop()
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jax.device_put(padded, dev))
        for l in range(s.layers):
            x = _layer(params["layers"], l, x, s=s, theta=theta, quant=quant)
        m = SCORED_MIN
        while m < count:
            m *= 2
        idx = np.minimum(np.arange(start, start + m), len(toks) - 1)
        rows = jnp.take(x, jax.device_put(idx.astype(np.int32), dev), axis=0)
        out = _head(params, rows, s=s, quant=quant)
    return np.asarray(out)[:count, : s.vocab]


def gaps(ref: np.ndarray, chosen: Sequence[int]) -> np.ndarray:
    """How far below the reference's best each chosen token's reference
    logit lies, in standard deviations of that position's logits."""
    chosen = np.asarray(chosen, np.int64)
    best = ref.max(axis=1)
    got = ref[np.arange(len(chosen)), chosen]
    return (best - got) / ref.std(axis=1)
