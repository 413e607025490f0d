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


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
