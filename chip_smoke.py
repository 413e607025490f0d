#!/usr/bin/env python3
"""Bring-up check: serve qwen3-8b at its published widths on a TPU.

Drives the normal serving path, ``repro.launch.serve.serve_fleet``
(``Network``/``Node`` -> ``EngineExecutor`` -> paged ``Engine``), with
random bf16 weights from seed 0, and holds every generated token to a
float32 reference forward (``repro.models.reference``) over prompt and
output, run at ``highest`` matmul precision.

Configuration: qwen3-8b at its published widths (d_model 4096, 32 query
and 8 kv heads of 128, d_ff 12288, vocabulary 151,936), bf16.  Depth is
the only cut: 18 of its 36 layers.  All 36 layers in bf16 weigh about
16.4 GB, more than the 16 GB of one v5e; 18 weigh about 9.4 GB.  The run
stands for a deployment of two chips as two pipeline stages of 18 layers
each, and serves one stage's depth end to end.

    python chip_smoke.py                # one chip: 8 prompts of 128 tokens,
                                        # 16 new tokens each, 2 paged nodes
    python chip_smoke.py --four-chips   # 4 nodes, one replica per chip,
                                        # against one replica on chip 0

It exits non-zero, printing no result, unless JAX's first device is a TPU.
The times it prints are bring-up numbers of one run, compilation
included, not benchmarks.  The last line of standard output is the
result: ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

try:
    import jax
    import numpy as np

    from repro.launch import serve
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import reference, registry
except ImportError as e:
    sys.exit(f"chip_smoke: cannot import the repository's code ({e}); "
             f"run it from the root of a checkout")

ARCH = "qwen3-8b"
LAYERS = 18
NODES, REQUESTS = 2, 8
# with 12 requests the protocol gives each of 4 nodes work, so every
# replica's page pools exist and are placed
FOUR_CHIP_NODES, FOUR_CHIP_REQUESTS = 4, 12
PROMPT_LEN, MAX_NEW = 128, 16
# A greedy token passes when its reference logit lies within GAP_TOL
# standard deviations of the reference logits below the reference maximum
# at its position.  bf16 serving at half these widths and 18 layers stays
# under 0.04 of them (CPU measurement); a token read from a wrong position
# or cache page sits several deviations below the maximum.
GAP_TOL = 0.25


class CompileStats:
    """Backend compile seconds, compile count and persistent-cache hits,
    from JAX's monitoring events, since construction."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def serve_phase(nodes: int, requests: int, layers: Optional[int],
                devices: Optional[Sequence] = None,
                made: Optional[Dict] = None, prompt_len: int = PROMPT_LEN,
                max_new: int = MAX_NEW) -> serve.FleetRun:
    """Serve ``requests`` prompts over ``nodes`` paged nodes through
    ``repro.launch.serve`` (``layers=None``: the float32 smoke preset)."""
    argv = ["--arch", ARCH, "--nodes", str(nodes), "--requests",
            str(requests), "--prompt-len", str(prompt_len), "--max-new",
            str(max_new), "--paged"]
    if layers is not None:
        argv += ["--layers", str(layers)]
    return serve.serve_fleet(serve.parse_args(argv), devices=devices,
                             made=made)


@dataclass
class RefCheck:
    """Every served token held to the float32 reference."""

    requests: int = 0
    tokens: int = 0
    # max over tokens of (reference max - reference logit of the token),
    # in standard deviations of that position's reference logits
    worst_gap: float = 0.0
    # max |served prefill logit - reference logit| at the first new
    # token, over the vocabulary and the requests
    worst_first_err: float = 0.0
    finite: bool = True
    failures: List[str] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return self.finite and not self.failures and self.tokens > 0


def reference_check(run: serve.FleetRun, device, made: Dict) -> RefCheck:
    """Re-run every completed request of ``run`` (prompt + output) through
    the float32 reference on ``device``, under the fleet's weights, and
    check each greedy token against the reference."""
    cfg, args = run.cfg, run.args
    plen, vocab = args.prompt_len, cfg.vocab_size
    reqs = run.completed()
    chk = RefCheck(requests=len(reqs))
    if not reqs:
        return chk
    params = serve.params_on(cfg, 0, device, made)
    toks = np.full((len(reqs), plen + args.max_new), cfg.eos_id, np.int32)
    for b, r in enumerate(reqs):
        assert len(r.tokens) == plen, (r.rid, len(r.tokens))
        toks[b, :plen] = r.tokens
        toks[b, plen:plen + len(r.result)] = r.result
    toks = jax.device_put(toks, device)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(reference.forward_f32, static_argnums=(1, 3, 4, 5))(
            params, cfg, toks, plen - 1, args.max_new, cfg.padded_vocab // 8)
    ref = np.asarray(ref)[..., :vocab]
    # the engine's own prefill (same chunking) gives the served logits of
    # the first new token
    fam = registry.get_family(cfg)
    first = jax.jit(lambda p, t: fam.prefill(
        p, cfg, {"tokens": t}, q_chunk=256, kv_chunk=256)[0])(
            params, toks[:, :plen])
    first = np.asarray(first, np.float32)[:, 0, :vocab]
    chk.finite = bool(np.isfinite(ref).all() and np.isfinite(first).all())
    chk.worst_first_err = float(np.abs(first - ref[:, 0]).max())
    for b, r in enumerate(reqs):
        for j, tok in enumerate(r.result):
            row = ref[b, j]
            gap = float((row.max() - row[tok]) / row.std())
            chk.tokens += 1
            chk.worst_gap = max(chk.worst_gap, gap)
            if not gap <= GAP_TOL:
                chk.failures.append(f"{r.rid} token {j} ({tok}): "
                                    f"{gap:.3f} std below the max")
    return chk


def describe(chk: RefCheck) -> str:
    return (f"reference check: {chk.tokens} tokens of {chk.requests} "
            f"requests, worst greedy gap {chk.worst_gap:.4f} std "
            f"(tolerance {GAP_TOL}), worst first-token logit error "
            f"{chk.worst_first_err:.4f} (served prefill vs float32 "
            f"reference), logits finite: {chk.finite} -> "
            f"{'PASS' if chk.ok else 'FAIL ' + '; '.join(chk.failures[:5])}")


def params_bytes(params) -> int:
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(params))


def peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def one_chip(stats: CompileStats) -> bool:
    """2 paged nodes on chip 0 serve 8 prompts; every token is checked."""
    dev = jax.devices()[0]
    cfg = serve.serve_config(ARCH, LAYERS)
    print(f"config: {ARCH} published widths (d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads / {cfg.n_kv_heads} kv heads of "
          f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}), "
          f"{cfg.dtype}; cut: depth only, {cfg.n_layers} of 36 layers "
          f"(one of two 18-layer pipeline stages)")
    made: Dict = {}
    t0 = time.time()
    params = serve.params_on(cfg, 0, dev, made)
    jax.block_until_ready(params)
    print(f"parameter bytes: {params_bytes(params)}; peak bytes in use "
          f"after init: {peak_bytes(dev)}; init {time.time() - t0:.1f}s")
    t0 = time.time()
    run = serve_phase(NODES, REQUESTS, LAYERS, devices=[dev], made=made)
    serve_s = time.time() - t0
    done = run.completed()
    for nid, reqs in sorted(run.done.items()):
        print(f"  {nid}: {len(reqs)} requests, "
              f"{sum(len(r.result) for r in reqs)} tokens")
    print(f"tokens generated: {sum(len(r.result) for r in done)} over "
          f"{len(done)}/{REQUESTS} requests")
    chk = reference_check(run, dev, made)
    print(describe(chk))
    print(f"peak bytes in use: {peak_bytes(dev)}")
    print(f"bring-up timings, not benchmarks: serve wall {serve_s:.1f}s "
          f"(compilation included); compile {stats.seconds:.1f}s over "
          f"{stats.compiles} compiles, {stats.cache_hits} persistent-cache "
          f"hits")
    return len(done) == REQUESTS and chk.ok


@dataclass
class FourChipResult:
    placement: Dict[str, List[str]]    # node -> devices its engine uses
    placed: bool                       # node i wholly on device i, served
    identical: int                     # requests with equal outputs
    matching_prefix: List[int]         # per request: equal leading tokens
    requests: int
    four: RefCheck
    one: RefCheck

    @property
    def ok(self) -> bool:
        return (self.placed and self.four.ok and self.one.ok
                and self.requests == FOUR_CHIP_REQUESTS)


def four_chip_phase(layers: Optional[int] = LAYERS,
                    devices: Optional[Sequence] = None) -> FourChipResult:
    """4 nodes, node i on device i, then the same requests on one replica
    on device 0; both runs are held to the reference on device 0."""
    devices = list(jax.devices() if devices is None else devices)[:4]
    if len(devices) < FOUR_CHIP_NODES:
        raise SystemExit(f"--four-chips needs {FOUR_CHIP_NODES} devices, "
                         f"found {len(devices)}")
    made: Dict = {}
    run4 = serve_phase(FOUR_CHIP_NODES, FOUR_CHIP_REQUESTS, layers, devices,
                       made)
    placement, placed = {}, True
    for nid, ex in sorted(run4.executors.items()):
        i = int(nid[len("node"):]) - 1
        got = ex.engine.devices()
        placement[nid] = sorted(str(d) for d in got)
        placed &= got == {devices[i]} and bool(run4.done.get(nid))
    run1 = serve_phase(1, FOUR_CHIP_REQUESTS, layers, devices[:1], made)
    r4, r1 = run4.completed(), run1.completed()
    prefix = []
    for a, b in zip(r4, r1):
        assert a.rid == b.rid
        n = min(len(a.result), len(b.result))
        eq = np.asarray(a.result[:n]) == np.asarray(b.result[:n])
        prefix.append(int(n if eq.all() else eq.argmin()))
    identical = sum(np.array_equal(a.result, b.result)
                    for a, b in zip(r4, r1))
    return FourChipResult(
        placement=placement, placed=placed, identical=int(identical),
        matching_prefix=prefix, requests=min(len(r4), len(r1)),
        four=reference_check(run4, devices[0], made),
        one=reference_check(run1, devices[0], made))


def four_chips(stats: CompileStats) -> bool:
    t0 = time.time()
    res = four_chip_phase()
    for nid, devs in res.placement.items():
        print(f"  {nid} engine on {devs}")
    print(f"placement: node i wholly on device i, every node served: "
          f"{res.placed}")
    print(f"greedy agreement, 4 replicas vs 1 replica on device 0: "
          f"{res.identical}/{res.requests} requests identical; matching "
          f"leading tokens per request: {res.matching_prefix}")
    print("4 replicas " + describe(res.four))
    print("1 replica " + describe(res.one))
    for d in jax.devices()[:4]:
        print(f"peak bytes in use on {d}: {peak_bytes(d)}")
    print(f"bring-up timings, not benchmarks: phase wall "
          f"{time.time() - t0:.1f}s (compilation included); compile "
          f"{stats.seconds:.1f}s over {stats.compiles} compiles, "
          f"{stats.cache_hits} persistent-cache hits")
    return res.ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve 4 nodes, one replica per chip, and compare "
                         "with one replica on chip 0 (this phase only)")
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (first device: "
              f"{devices[0].platform}); this check runs on the chip only",
              file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}")
    stats = CompileStats()
    ok = four_chips(stats) if args.four_chips else one_chip(stats)
    if not ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
