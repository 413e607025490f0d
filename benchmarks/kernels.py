"""Substrate kernels: Pallas (interpreted off the TPU) vs jnp oracle — allclose + µs."""

from __future__ import annotations

import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention import flash_attention_tpu
from repro.kernels.flash_decode import flash_decode_tpu
from repro.models.attention import (decode_attention, flash_attention,
                                    reference_attention)


def _time(fn, *args, iters=3, **kw):
    fn(*args, **kw)[0].block_until_ready() if isinstance(fn(*args, **kw),
                                                         tuple) else \
        jax.block_until_ready(fn(*args, **kw))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args, **kw))
    return (time.perf_counter() - t0) / iters * 1e6


def main(rows: List[str]) -> None:
    key = jax.random.PRNGKey(0)
    # prefill kernel
    b, s, h, hkv, d = 2, 512, 8, 2, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32)
    ref = reference_attention(q, k, v, causal=True)
    out = flash_attention_tpu(q, k, v, causal=True, block_q=128, block_k=128)
    err = float(jnp.max(jnp.abs(out - ref)))
    us_pallas = _time(lambda: flash_attention_tpu(
        q, k, v, causal=True, block_q=128, block_k=128))
    us_ref = _time(lambda: flash_attention(q, k, v, causal=True,
                                           q_chunk=128, kv_chunk=128))
    rows.append(f"kernel_flash_prefill,{us_pallas:.0f},"
                f"max_err={err:.2e};jnp_oracle_us={us_ref:.0f};"
                f"allclose={err < 2e-5}")

    # decode kernel
    b, s, h, hkv, d = 4, 2048, 8, 2, 64
    ks = jax.random.split(key, 3)
    q1 = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32)
    kc = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32)
    vc = jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32)
    cl = jnp.asarray(1536, jnp.int32)
    refd = decode_attention(q1, kc, vc, cl)
    outd = flash_decode_tpu(q1, kc, vc, cl, block_k=512)
    errd = float(jnp.max(jnp.abs(outd - refd)))
    us_pallas = _time(lambda: flash_decode_tpu(q1, kc, vc, cl, block_k=512))
    us_ref = _time(lambda: decode_attention(q1, kc, vc, cl))
    rows.append(f"kernel_flash_decode,{us_pallas:.0f},"
                f"max_err={errd:.2e};jnp_oracle_us={us_ref:.0f};"
                f"allclose={errd < 2e-5}")


if __name__ == "__main__":
    rows: List[str] = []
    main(rows)
    print("\n".join(rows))
