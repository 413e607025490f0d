"""The Pallas kernels compile for a described TPU v5e at qwen3-8b widths.

Interpret mode accepts kernels the chip's compiler refuses (the int8 scale
reshape of the paged kernels was one), so each kernel is also compiled
here for one chip of a described ``v5e:2x2`` topology — no chip attached,
nothing runs — and must lower to a Mosaic ``tpu_custom_call``.

The topology is described only inside the module fixture: the TPU
library may be loaded by one process at a time, so describing it while a
module is imported would make pytest-xdist workers disagree on what they
collected.  The persistent compilation cache is off around these
compiles: an entry written for a described chip cannot be read back
without one.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_tpu
from repro.kernels.flash_decode import flash_decode_tpu
from repro.kernels.paged_decode import flash_paged_decode_tpu
from repro.kernels.spec_verify import flash_paged_verify_tpu

# qwen3-8b attention widths
H, HKV, D = 32, 8, 128
PAGE, POOL, MAXP, B, SPEC_K = 16, 256, 16, 8, 4
BF16 = jnp.bfloat16


def _paged_case(fn, kq, quant):
    pool = jnp.int8 if quant else BF16
    shapes = [((B, kq, H, D), BF16), ((POOL, PAGE, HKV, D), pool),
              ((POOL, PAGE, HKV, D), pool), ((B, MAXP), jnp.int32),
              ((B,), jnp.int32)]
    if quant:
        shapes += [((POOL, PAGE, HKV, 1), BF16)] * 2
        return (lambda q, k, v, bt, ln, ks, vs: fn(
            q, k, v, bt, ln, k_scale=ks, v_scale=vs, interpret=False),
            shapes)
    return (lambda q, k, v, bt, ln: fn(q, k, v, bt, ln, interpret=False),
            shapes)


KERNELS = {
    "flash_attention": (
        lambda q, k, v: flash_attention_tpu(q, k, v, causal=True,
                                            interpret=False),
        [((1, 2048, H, D), BF16), ((1, 2048, HKV, D), BF16),
         ((1, 2048, HKV, D), BF16)]),
    "flash_decode": (
        lambda q, k, v, n: flash_decode_tpu(q, k, v, n, interpret=False),
        [((B, 1, H, D), BF16), ((B, 4096, HKV, D), BF16),
         ((B, 4096, HKV, D), BF16), ((), jnp.int32)]),
    "paged_decode_bf16": _paged_case(flash_paged_decode_tpu, 1, False),
    "paged_decode_int8": _paged_case(flash_paged_decode_tpu, 1, True),
    "spec_verify_bf16": _paged_case(flash_paged_verify_tpu, SPEC_K + 1,
                                    False),
    "spec_verify_int8": _paged_case(flash_paged_verify_tpu, SPEC_K + 1,
                                    True),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


# the served decode reads the whole (L, P, page, Hkv, D) pool at a traced
# layer index: the benchmark's two configurations, at their widest tables
SERVED = {  # name: (layers, pool pages, table width, query heads)
    "qwen3-8b.l18": (18, 2800, 320, 32),
    "qwen3-32b.l8": (8, 6800, 256, 64),
}
for _name, (_l, _p, _w, _h) in SERVED.items():
    KERNELS[f"paged_decode_layer_{_name}"] = (
        lambda q, k, v, bt, ln, l: flash_paged_decode_tpu(
            q, k, v, bt, ln, layer=l, interpret=False),
        [((32, 1, _h, D), BF16), ((_l, _p, PAGE, HKV, D), BF16),
         ((_l, _p, PAGE, HKV, D), BF16), ((32, _w), jnp.int32),
         ((32,), jnp.int32), ((), jnp.int32)])
# what the gather path's decode step held in temporaries at the chat
# configuration's widest table (memory_analysis() for a described v5e)
GATHER_TEMP_BYTES = 3.44e9


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kv_quant", [False, True], ids=["bf16", "int8"])
def test_served_decode_step_compiles_without_pool_copies(
        kv_quant, one_chip, no_persistent_cache, monkeypatch):
    """The whole ``jit_paged_decode_step`` of qwen3-8b.l18 (abstract
    weights, 2,800-page pool, 320-page table) lowers its attention to the
    Mosaic kernel, and its temporaries hold no pool-sized copy."""
    import repro.compat.pallascompat as pallascompat
    from repro.kernels import ops
    from repro.models import dense
    from repro.models.config import ModelConfig

    # this process's backend is the CPU: steer the dispatch to the kernel
    # and its compilation for the described chip
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setattr(pallascompat, "on_tpu", lambda: True)
    layers, pages, width, heads = SERVED["qwen3-8b.l18"]
    cfg = ModelConfig(name="qwen3-8b.l18", family="dense", n_layers=layers,
                      d_model=4096, n_heads=heads, n_kv_heads=HKV,
                      d_ff=12288, vocab_size=151936, head_dim=D,
                      qk_norm=True, kv_quant=kv_quant)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: dense.init(jax.random.PRNGKey(0), cfg)))
    cache = on_chip({
        **jax.eval_shape(lambda: dense.init_paged_pools(cfg, pages, PAGE)),
        "block_tables": jax.ShapeDtypeStruct((32, width), jnp.int32),
        "lengths": jax.ShapeDtypeStruct((32,), jnp.int32)})
    token = on_chip(jax.ShapeDtypeStruct((32, 1), jnp.int32))

    def paged_decode_step(p, c, t):
        return dense.paged_decode_step(p, cfg, c, t)
    compiled = jax.jit(paged_decode_step, donate_argnums=(1,)).lower(
        params, cache, token).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < (
        GATHER_TEMP_BYTES / 100)
