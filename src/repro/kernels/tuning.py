"""Kernel tuning registry + autotune sweep for the paged kernels.

The paged flash-decode and spec-verify kernels (DESIGN.md §Perf-kernels)
expose one tunable: **pages_per_step** — how many physical pages one grid
step DMAs and reduces.  More pages per step amortizes grid overhead and
lets the pager batch HBM->VMEM transfers; fewer keeps VMEM pressure down
for large ``page_size * head_dim`` blocks.  The right choice depends only
on the static shape triple ``(page_size, head_dim, n_kv_heads)``, so the
choice is recorded per-triple in a module-level registry that both kernel
wrappers consult when the caller does not pass ``pages_per_step``
explicitly.

``autotune_paged_decode`` is the sweep helper: it times the real kernel
(compiled on a TPU, interpreted elsewhere) over candidate values on
caller-supplied arrays and records the winner.  ``benchmarks/run.py
--bench`` runs it at the bench's pinned shapes and publishes the chosen
tuning in the ``kernel`` section of ``BENCH_scheduling.json`` so the
choice is tracked PR over PR.
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Dict, Iterable, Optional, Tuple

import jax


@dataclass(frozen=True)
class KernelTuning:
    """Static kernel shape choices for one (page_size, head_dim, hkv)."""
    pages_per_step: int = 1


DEFAULT_TUNING = KernelTuning(pages_per_step=1)

# winners of ``autotune_paged_decode`` on a TPU v5e at the benchmark
# cells' tables (PERF.md, section 6): page 16, head_dim 128 and 8 kv
# heads is the shape of qwen3-8b and qwen3-32b
RECORDED: Dict[Tuple[int, int, int], KernelTuning] = {
    (16, 128, 8): KernelTuning(pages_per_step=16),
}

_REGISTRY: Dict[Tuple[int, int, int], KernelTuning] = dict(RECORDED)


def tuning_key(page_size: int, head_dim: int, hkv: int) -> Tuple[int, int, int]:
    return (int(page_size), int(head_dim), int(hkv))


def record_tuning(page_size: int, head_dim: int, hkv: int,
                  tuning: KernelTuning) -> None:
    _REGISTRY[tuning_key(page_size, head_dim, hkv)] = tuning


def tuning_for(page_size: int, head_dim: int, hkv: int) -> KernelTuning:
    """Recorded tuning for the shape triple, or the safe default."""
    return _REGISTRY.get(tuning_key(page_size, head_dim, hkv),
                         DEFAULT_TUNING)


def clear_tunings() -> None:
    """Reset the registry to the recorded entries (test isolation)."""
    _REGISTRY.clear()
    _REGISTRY.update(RECORDED)


def autotune_paged_decode(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                          block_tables: jax.Array, lengths: jax.Array, *,
                          candidates: Iterable[int] = (1, 2, 4),
                          iters: int = 3, layer=None,
                          interpret: Optional[bool] = None
                          ) -> KernelTuning:
    """Sweep ``pages_per_step`` candidates on real arrays, record + return
    the fastest.  Pools are one layer's (P, page, Hkv, D), or the stacked
    (L, P, page, Hkv, D) read at ``layer`` as the served decode does.
    The winner is keyed by ``(page_size, head_dim, hkv)`` so every later
    kernel call at this shape in the process picks it up; ``RECORDED``
    keeps the winners measured on the chip.
    """
    # function-level import: the kernel wrapper consults this registry for
    # its default, so a module-level import would be circular
    from repro.kernels.paged_decode import flash_paged_decode_tpu

    page_size, hkv, d = k_pool.shape[-3:]
    best, best_t = DEFAULT_TUNING, float("inf")
    for pps in candidates:
        # one compiled program a candidate, the pools passed once
        run = jax.jit(functools.partial(
            flash_paged_decode_tpu, pages_per_step=pps, interpret=interpret))
        args = (q, k_pool, v_pool, block_tables, lengths)
        run(*args, layer=layer).block_until_ready()    # warm / compile
        t0 = time.perf_counter()
        for _ in range(iters):
            run(*args, layer=layer).block_until_ready()
        dt = (time.perf_counter() - t0) / iters
        if dt < best_t:
            best, best_t = KernelTuning(pages_per_step=pps), dt
    record_tuning(page_size, d, hkv, best)
    return best


__all__ = ["KernelTuning", "DEFAULT_TUNING", "RECORDED", "tuning_key",
           "record_tuning", "tuning_for", "clear_tunings",
           "autotune_paged_decode"]
