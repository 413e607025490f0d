"""Random weights of a Qwen3-style dense decoder, drawn from the seed on
the device in one jitted call, in the served dtype.

The layout is the serving engine's: layer weights stacked on a leading
layer axis, projections stored (in, out).  Projections are normal with
standard deviation 1/sqrt(fan_in), the embedding 0.02; norm gains are
1 + 0.1 * normal, so that the RMS norms and the per-head q/k norms are
exercised and not the identity.  The benchmark makes these weights and
hands the same arrays to the engine and to the reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.work import Sizes


def seed32(seed: int) -> int:
    """A 31-bit seed derived from a seed of any size."""
    return int(np.random.SeedSequence(int(seed) % (1 << 64))
               .generate_state(1)[0] & 0x7FFFFFFF)


def _draw(key: jax.Array, s: Sizes, dtype) -> dict:
    ks = iter(jax.random.split(key, 16))
    L, d, f = s.layers, s.d_model, s.d_ff
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim

    def proj(k, shape, fan_in):
        return (jax.random.normal(k, shape, dtype)
                * jnp.asarray(fan_in ** -0.5, dtype))

    def gain(k, shape):
        return (1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
                ).astype(dtype)

    p = {"embed": proj(next(ks), (s.padded_vocab, d), 2500.0),
         "final_norm": {"scale": gain(next(ks), (d,))}}
    if not s.tied:
        p["lm_head"] = proj(next(ks), (d, s.padded_vocab), d)
    p["layers"] = {
        "ln1": {"scale": gain(next(ks), (L, d))},
        "ln2": {"scale": gain(next(ks), (L, d))},
        "q_norm": gain(next(ks), (L, s.head_dim)),
        "k_norm": gain(next(ks), (L, s.head_dim)),
        "wq": proj(next(ks), (L, d, q), d),
        "wk": proj(next(ks), (L, d, kv), d),
        "wv": proj(next(ks), (L, d, kv), d),
        "wo": proj(next(ks), (L, q, d), q),
        "w_gate": proj(next(ks), (L, d, f), d),
        "w_up": proj(next(ks), (L, d, f), d),
        "w_down": proj(next(ks), (L, f, d), f),
    }
    return p


def draw(sizes: Sizes, seed: int, device, dtype=jnp.bfloat16) -> dict:
    """The weights for ``seed``, made on ``device``.  The key is an
    argument of the jitted call, so every seed reuses one program."""
    key = jax.device_put(jax.random.PRNGKey(seed32(seed)), device)
    fn = jax.jit(functools.partial(_draw, s=sizes, dtype=dtype))
    return fn(key)
