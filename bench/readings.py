"""Quantities of a measured window that the metric readers share.  Each
takes the harness's ``Run``; each returns None where the window holds
nothing to read."""

from __future__ import annotations

from typing import List, Optional

from bench import stats


def ms(x: Optional[float]) -> Optional[float]:
    return None if x is None else 1000.0 * x


def ttft_values(run) -> List[float]:
    due = run.due_in_window()
    return stats.ttfts([r.due for r in due],
                       [r.first_token_at for r in due], run.end)


def itl_values(run) -> List[float]:
    return stats.inter_token_gaps([r.emits for r in run.requests],
                                  run.lo, run.hi)


def output_tokens_per_s(run) -> float:
    return stats.tokens_in([r.emits for r in run.requests],
                           run.lo, run.hi) / run.seconds


def occupancy(run) -> Optional[float]:
    """Rows fed to each decode forward, on average (engine counters)."""
    steps = run.stats["decode_steps"]
    return run.stats["decode_tokens"] / steps if steps else None


def decode_step_s(run) -> Optional[float]:
    steps = run.stats["decode_steps"]
    return run.stats["decode_wall_s"] / steps if steps else None


def prefill_tokens(run) -> List[int]:
    """Real prompt lengths of the prefills that ended in the window."""
    return [n for r in run.requests for t, n in r.prefills
            if run.lo <= t < run.hi]


def decode_work(run):
    """(flops, bytes) the window's decode steps required, as the cell's
    architecture counts them (``bench/arch/<model_type>.py``)."""
    return run.cell.arch.decode_work(run)


def decode_share(run, of: str) -> Optional[float]:
    """Share (%) of the peak FLOP/s (``of="flops"``) or HBM bytes/s
    (``of="bytes"``) that the decode steps' required work reached over the
    engine's decode wall time."""
    wall = run.stats["decode_wall_s"]
    if not wall:
        return None
    flops, nbytes = decode_work(run)
    if of == "flops":
        return 100.0 * flops / wall / run.peaks["bf16_flops_per_s"]
    return 100.0 * nbytes / wall / run.peaks["hbm_bytes_per_s"]


def prefill_mfu(run) -> Optional[float]:
    wall = run.stats["prefill_wall_s"]
    lens = prefill_tokens(run)
    if not wall or not lens:
        return None
    return (100.0 * run.sizes.prefill_flops(lens) / wall
            / run.peaks["bf16_flops_per_s"])


def prefill_ms_per_ktok(run) -> Optional[float]:
    wall = run.stats["prefill_wall_s"]
    lens = prefill_tokens(run)
    if not wall or not lens:
        return None
    return 1000.0 * wall / (sum(lens) / 1000.0)


def idle_share(run) -> Optional[float]:
    return None if run.trace is None else 100.0 * run.trace.idle_share
