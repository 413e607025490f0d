"""The served paged decode step through the Pallas block-table kernel.

``dense.paged_decode_step`` dispatches its attention through
``repro.kernels.ops``: the Pallas kernel on a TPU, the gather oracle
elsewhere.  Here the test forces the kernel (``backend="pallas"``, so it
runs interpreted on the CPU) and holds the whole step to the jnp path
for bf16 and int8 pools, on a table that holds a row of length 0, a row
whose tokens exactly fill its pages, pad columns past every row's last
page and a width that is not a multiple of ``pages_per_step``.  Every
layer after the first reads its pages through a layer index other than
0, inside the kernel; the pools differ between layers, so a kernel that
read the wrong layer would not match.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.tuning import KernelTuning, record_tuning, tuning_for
from repro.models import dense
from repro.models.config import ModelConfig

PAGE, N_POOL, WIDTH = 4, 24, 7
# new-token positions: a row of length 0, a row whose 8 tokens exactly
# fill its two pages (its write opens a third), partial pages, and a row
# past every other; WIDTH leaves pad columns past every row's last page
LENGTHS = (0, 8, 5, 13, 3)
# kernel-test tolerances (tests/test_kernels.py), per pool dtype
TOL = {"float32": dict(atol=2e-5, rtol=1e-3),
       "bfloat16": dict(atol=2e-2, rtol=1e-2)}


def _cfg(dtype, kv_quant):
    return ModelConfig(name="tiny", family="dense", n_layers=3, d_model=64,
                       n_heads=4, n_kv_heads=2, d_ff=96, vocab_size=96,
                       head_dim=16, qk_norm=True, kv_quant=kv_quant,
                       dtype=dtype)


def _case(cfg, seed=0):
    """Params, a cache whose pools hold distinct random KV per layer and
    block tables covering each row's write, and the fed tokens."""
    key = jax.random.PRNGKey(seed)
    params = dense.init(key, cfg)
    pools = dense.init_paged_pools(cfg, N_POOL, PAGE)
    ks = jax.random.split(jax.random.fold_in(key, 1), len(pools))
    pools = {n: (jax.random.randint(k, p.shape, -127, 128, jnp.int8)
                 if p.dtype == jnp.int8 else
                 (0.01 + jax.random.uniform(k, p.shape)).astype(p.dtype)
                 if n.endswith("scale_pool") else
                 jax.random.normal(k, p.shape).astype(p.dtype))
             for k, (n, p) in zip(ks, sorted(pools.items()))}
    bt = np.zeros((len(LENGTHS), WIDTH), np.int32)
    free = iter(range(1, N_POOL))
    for i, n in enumerate(LENGTHS):
        for j in range(n // PAGE + 1):       # pages through the new token
            bt[i, j] = next(free)
    cache = {**pools, "block_tables": jnp.asarray(bt),
             "lengths": jnp.asarray(LENGTHS, jnp.int32)}
    tokens = jax.random.randint(jax.random.fold_in(key, 2),
                                (len(LENGTHS), 1), 0, cfg.vocab_size)
    return params, cache, tokens


@pytest.fixture
def pages_per_step():
    """Record a tuning for the tiny shape (page, head_dim, hkv) for the
    test, and put back what was there."""
    key = (PAGE, 16, 2)
    before = tuning_for(*key)

    def use(pps):
        record_tuning(*key, KernelTuning(pages_per_step=pps))
    yield use
    record_tuning(*key, before)


# (model dtype, int8 pools).  The int8 case runs a float32 model: the
# oracle rounds dequantized K/V to the model dtype, the kernel keeps them
# in float32 (the scales multiply scores and probabilities), so under a
# bf16 model the two differ by bf16 rounding carried through the layers
POOLS = {"f32": ("float32", False), "bf16": ("bfloat16", False),
         "int8": ("float32", True)}


@pytest.mark.parametrize("pools", sorted(POOLS))
@pytest.mark.parametrize("pps", [1, 3, 4])
def test_kernel_step_matches_jnp_step(pools, pps, pages_per_step,
                                      monkeypatch):
    dtype, kv_quant = POOLS[pools]
    assert WIDTH % pps or pps == 1                  # 3 and 4 leave a tail
    pages_per_step(pps)
    cfg = _cfg(dtype, kv_quant)
    params, cache, tokens = _case(cfg)

    def step():
        return jax.jit(lambda p, c, t: dense.paged_decode_step(
            p, cfg, c, t))(params, cache, tokens)

    want_logits, want = step()                      # the CPU's oracle
    for name in ("paged_decode", "paged_decode_quant"):
        monkeypatch.setattr(ops, name, functools.partial(
            getattr(ops, name), backend="pallas"))
    got_logits, got = step()
    np.testing.assert_allclose(np.asarray(got_logits, np.float32),
                               np.asarray(want_logits, np.float32),
                               **TOL[dtype])
    # each layer writes K/V projected from what the layers below attended
    for name in want:
        np.testing.assert_allclose(np.asarray(got[name], np.float32),
                                   np.asarray(want[name], np.float32),
                                   err_msg=name, **TOL[dtype])


@pytest.mark.parametrize("kv_quant", [False, True], ids=["bf16", "int8"])
def test_kernel_resolves_layer_inside(kv_quant):
    """ops.paged_decode[_quant] on the whole (L, ...) pool at layer 2
    equals the oracle on that layer's pools, and not on layer 0's."""
    cfg = _cfg("float32", kv_quant)
    _, cache, _ = _case(cfg, seed=3)
    q = jax.random.normal(jax.random.PRNGKey(4),
                          (len(LENGTHS), 1, cfg.n_heads, cfg.head_dim))
    bt, lens = cache["block_tables"], cache["lengths"] + 1
    names = [n for n in dense.PAGED_POOL_NAMES if n in cache]
    fn = ops.paged_decode_quant if kv_quant else ops.paged_decode

    def at(layer, backend):
        return fn(q, *(cache[n] for n in names), bt, lens, layer=layer,
                  backend=backend)

    got = at(jnp.int32(2), "pallas")
    want = fn(q, *(cache[n][2] for n in names), bt, lens, backend="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **TOL["float32"])
    np.testing.assert_allclose(np.asarray(at(2, "ref")), np.asarray(want),
                               **TOL["float32"])
    assert not np.allclose(np.asarray(got), np.asarray(at(0, "pallas")),
                           atol=1e-3)
