"""The benchmark harness: one WWW.Serve node under a traffic mix.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix; the
harness finds everything else by those names:

* ``bench/configs/<config>.json`` (the path in the config's entry): the
  model's published sizes (Hugging Face key names), the depth cut, dtype,
  the engine settings and the limit of the output check;
* ``bench/arch/<model_type>.py``, by the configuration's ``model_type``:
  the architecture's model configuration, sizes, work counts, weights and
  float32 reference (the interface is ``bench/arch/__init__.py``'s
  docstring);
* ``bench/traffic/<traffic>.json``: the mix, read by ``bench.traffic``;
* ``bench/metrics/<metric>.py``: one reader per metric, ``read(run)``
  returning a number or None (nothing to read: the metric is left out).

The node is the program's own serving path: a paged
``repro.serving.Engine`` behind a ``repro.serving.EngineExecutor``, with
weights the benchmark draws on the device from the seed.  The harness
drives it in wall-clock time: each request is offered to ``admit()`` when
it is due (and again after every step while the executor pushes back),
then ``step()`` runs one engine iteration.

A run: draw the weights; warm every shape the mix can produce (prefill
lengths, decode table widths, resident-row counts); serve the mix's warm
segment; measure ``--seconds``; for an open loop keep serving until every
request due in the window has its first token; read the peak memory; free
the engine; hold a sample of the finished requests to the
architecture's float32 reference.
"""

from __future__ import annotations

import argparse
import collections
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from bench import readings, stats, trace as tracemod
from bench.reference import gaps as reference_gaps
from bench.traffic import Item, Traffic, prompt_range, seed_rng

ROOT = Path(__file__).resolve().parents[1]
REQUIRED_PLATFORM = "tpu"
TRACE_SECONDS = 4.0       # device trace of the window's last seconds
GRACE_S = 60.0            # open loop: wait this long past the window for
                          # the first tokens of requests due in it
CHECK_MIN_TOKENS = 256    # served tokens the output check covers at least
CHECK_MIN_REQUESTS = 4
CHECK_MAX_REQUESTS = 16


class NoAccelerator(RuntimeError):
    pass


# ------------------------------------------------------------------ cells
@dataclass
class Cell:
    root: Path
    name: str
    chips: int
    config_name: str
    config: Dict
    arch: ModuleType               # bench/arch/<model_type>.py
    mix_name: str
    mix: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def _load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: Path, workload: str) -> Cell:
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    config = _load_json(root / conf["file"])
    return Cell(root=root, name=workload, chips=int(w["chips"]),
                config_name=w["config"], config=config,
                arch=architecture(root, config["model_type"]),
                mix_name=w["traffic"],
                mix=_load_json(root / "bench" / "traffic"
                               / f"{w['traffic']}.json"),
                end_to_end=e2e, per_layer=per_layer)


def load_module(path: Path, prefix: str) -> ModuleType:
    """The Python file at ``path``, loaded by path under a module name made
    from ``prefix`` and the file's name."""
    name = f"{prefix}_{path.stem.replace('.', '_').replace('-', '_')}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(root: Path, metric: str) -> Callable:
    """``bench/metrics/<metric>.py``, else the reader of the quantity the
    name starts with (``decode_mfu.batch`` reads ``decode_mfu.py``): a
    suffix only says which cells report it and what it moves."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    if not path.exists():
        path = path.with_name(f"{metric.split('.')[0]}.py")
    return load_module(path, "bench_metric").read


def architectures(root: Path) -> List[str]:
    """The ``model_type`` of every architecture module under ``root``:
    the files in ``bench/arch`` are the list."""
    return sorted(p.stem for p in (root / "bench" / "arch").glob("*.py")
                  if p.stem != "__init__")


def architecture(root: Path, model_type: str) -> ModuleType:
    """``bench/arch/<model_type>.py``; an unknown ``model_type`` fails
    with the names that are known."""
    if model_type not in architectures(root):
        raise ValueError(f"no architecture module for model_type "
                         f"{model_type!r} in bench/arch; known: "
                         f"{architectures(root)}")
    return load_module(root / "bench" / "arch" / f"{model_type}.py",
                       "bench_arch")


def accelerator(chips: int):
    """The devices the cell runs on; refuses anything but the chip."""
    devs = jax.devices()
    if devs[0].platform != REQUIRED_PLATFORM:
        raise NoAccelerator(f"JAX found no {REQUIRED_PLATFORM} (first "
                            f"device: {devs[0].platform}); the benchmark "
                            f"measures on the chip only")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devs)}")
    return devs[:chips]


# ------------------------------------------------------------ compiles
class CompileStats:
    """Backend compiles, their seconds and persistent-cache hits, from
    JAX's monitoring events (registered once per process)."""

    _self: Optional["CompileStats"] = None

    def __init__(self) -> None:
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    @classmethod
    def get(cls) -> "CompileStats":
        if cls._self is None:
            cls._self = cls()
        return cls._self

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> Tuple[int, int]:
        return self.compiles, self.cache_hits


# ------------------------------------------------------------------ node
@dataclass
class Node:
    sizes: Any                    # the architecture's sizes(config)
    cfg: object
    weights: Dict
    engine: object
    executor: object
    device: object


def build_node(cell: Cell, seed: int, device) -> Node:
    from bench.weights import seed32
    from repro.serving import Engine, EngineExecutor
    sizes = cell.arch.sizes(cell.config)
    cfg = cell.arch.model_config(cell.config)
    if cfg.padded_vocab != sizes.padded_vocab:
        raise ValueError(f"padded vocabulary {sizes.padded_vocab} in the "
                         f"config, {cfg.padded_vocab} in the program")
    weights = cell.arch.draw(cell.config, seed, device)
    jax.block_until_ready(weights)
    eng = cell.config["engine"]
    engine = Engine(cfg, weights, max_batch=eng["max_batch"],
                    bucket=eng["bucket"], seed=seed32(seed), paged=True,
                    page_size=eng["page_size"], num_pages=eng["num_pages"])
    executor = EngineExecutor(engine,
                              max_pending_tokens=eng["max_pending_tokens"])
    return Node(sizes, cfg, weights, engine, executor, device)


# ---------------------------------------------------------------- pump
@dataclass
class Req:
    """The harness's record of one request."""

    item: Item
    due: float                        # absolute (perf_counter) due time
    gen: object                       # the program's GenRequest
    emits: List[float] = field(default_factory=list)
    prefills: List[Tuple[float, int]] = field(default_factory=list)
    last_started: float = 0.0
    finished_at: Optional[float] = None

    @property
    def first_token_at(self) -> Optional[float]:
        return self.gen.first_token_at or None

    @property
    def started_at(self) -> Optional[float]:
        return self.gen.started_at or None


@dataclass
class Step:
    t: float              # when the engine step returned
    emitted: int          # tokens emitted in it (resident rows sampled)
    decoded: int          # rows fed to its decode forward
    context: int          # valid KV positions of those rows before it
    width: int = 0        # pages its longest row needs, as a power of two
                          # (the decode block table's width)


class Pump:
    """Offers requests to the executor, steps it, and records what every
    step emitted, on the host clock."""

    def __init__(self, node: Node) -> None:
        self.node = node
        self.ex = node.executor
        self.holding: collections.deque = collections.deque()
        self.inflight: List[Req] = []
        self.requests: List[Req] = []
        self.steps: List[Step] = []
        self.preemptions = 0
        # host-clock (perf_counter) bounds of the bench.traced span
        self.traced: Optional[Tuple[float, float]] = None

    def offer(self, item: Item, due: float) -> Req:
        from repro.serving import GenRequest
        r = Req(item, due, GenRequest(rid=f"r{item.index}",
                                      tokens=item.tokens,
                                      max_new=item.max_new))
        self.holding.append(r)
        self.requests.append(r)
        return r

    def _submit(self) -> None:
        with jax.profiler.TraceAnnotation("bench.submit"):
            while self.holding and self.ex.admit(self.holding[0].gen):
                self.inflight.append(self.holding.popleft())

    def busy(self) -> bool:
        return bool(self.holding) or self.ex.has_work()

    def backlog(self) -> int:
        """Requests offered and not yet admitted by the engine."""
        return len(self.holding) + sum(1 for r in self.inflight
                                       if not r.gen.started_at)

    def step(self) -> Optional[List[Req]]:
        """One engine iteration; None when there was nothing to do."""
        self._submit()
        if not self.ex.has_work():
            return None
        with jax.profiler.TraceAnnotation("bench.step"):
            finished = self.ex.step()
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.bookkeeping"):
            return self._account(t, {id(g) for g in finished})

    def _account(self, t: float, fin: set) -> List[Req]:
        emitted = decoded = context = 0
        pages = 1
        ps = self.node.engine.page_size
        keep, done = [], []
        for r in self.inflight:
            g = r.gen
            if g.started_at and g.started_at != r.last_started:
                r.prefills.append((t, len(r.item.tokens)))
                r.last_started = g.started_at
            if g.first_token_at:
                r.emits.append(t)
                emitted += 1
                # the position this step wrote (the prompt's last, for a
                # row prefilled in it)
                pos = r.item.prompt_len + len(r.emits) - 2
                pages = max(pages, pos // ps + 1)
                if id(g) not in fin:
                    decoded += 1
                    context += r.item.prompt_len + len(r.emits) - 1
            elif r.emits:
                # preempted: the engine discards its tokens and restarts it
                r.emits.clear()
                self.preemptions += 1
            if id(g) in fin:
                r.finished_at = t
                done.append(r)
            else:
                keep.append(r)
        self.inflight = keep
        self.steps.append(Step(t, emitted, decoded, context,
                               1 << (pages - 1).bit_length()))
        return done

    def drain(self) -> None:
        while self.busy():
            self.step()


# -------------------------------------------------------------- warm-up
def _pad(n: int, bucket: int) -> int:
    return max(bucket, -(-n // bucket) * bucket)


def shape_plan(config: Dict, mix: Dict) -> List[Tuple[int, int]]:
    """(prompt length, max_new) of warm-up requests that, served one at a
    time, reach every prefill length and every decode table width the mix
    can produce (largest first: it also fixes the engine's widest table)."""
    eng = config["engine"]
    ps, bucket = eng["page_size"], eng["bucket"]
    pmin, pmax = prompt_range(mix)
    plan = [(pmax, 2)]
    for plen in sorted({_pad(p, bucket) for p in (pmin, pmax)}
                       | set(range(_pad(pmin, bucket), pmax + 1, bucket))):
        plan.append((min(plen, pmax), 2))
    # decode widths: powers of two of pages, from the shortest prompt's
    # to the longest prompt's (longer rows are reached by decoding)
    w = 1
    while w < -(-(pmin + 1) // ps):
        w *= 2
    while w * ps - ps // 2 <= pmax:
        plan.append((max(pmin, w * ps - ps // 2), 2))
        w *= 2
    seen, out = set(), []
    for p in plan:
        if p not in seen:
            seen.add(p)
            out.append(p)
    return out


def warm_shapes(pump: Pump, config: Dict, mix: Dict,
                tokens: Callable[[int], np.ndarray]) -> None:
    """Serve the shape plan, then a ramp that fills every row one
    admission at a time and empties it one completion at a time, so each
    resident-row count the engine's per-step bookkeeping sees is warm."""
    for i, (p, o) in enumerate(shape_plan(config, mix)):
        pump.offer(Item(-1 - i, 0.0, p, o, tokens(p)), time.perf_counter())
        pump.drain()
    rows = config["engine"]["max_batch"]
    pmin = prompt_range(mix)[0]
    for j in range(rows):
        pump.offer(Item(-1000 - j, 0.0, pmin, rows + 2, tokens(pmin)),
                     time.perf_counter())
        pump.step()
    pump.drain()


# -------------------------------------------------------------- the run
@dataclass
class Run:
    """What the metric readers read."""

    cell: Cell
    seconds: float
    lo: float                  # window start (perf_counter seconds)
    hi: float                  # window end
    end: float                 # last moment served (open loop: >= hi)
    requests: List[Req]
    steps: List[Step]
    stats: Dict[str, float]    # EngineStats deltas over the window
    sizes: Any                 # the architecture's sizes(config)
    setup_s: float
    preemptions: int
    compiles_in_window: int
    cache_hits_in_window: int
    peaks: Optional[Dict] = None
    trace: Optional[tracemod.Reduced] = None
    # host-clock bounds of the traced span (``--trace 1``): the steps
    # between them are the ones the trace's device ops belong to
    traced: Optional[Tuple[float, float]] = None

    @property
    def window_steps(self) -> List[Step]:
        return [s for s in self.steps if self.lo <= s.t < self.hi]

    def due_in_window(self) -> List[Req]:
        return [r for r in self.requests if self.lo <= r.due < self.hi]


def window_shape(run: Run) -> str:
    """What the window's steps held: the pages their longest row needed
    (the decode table's width, which the engine caps at its host table's),
    the longest context, the prefills, the requests that ended at the
    end-of-sequence token before their budget, and the gaps between
    steps (a stall shows as one long gap, a slower host as a longer
    median)."""
    steps = run.window_steps
    widths = collections.Counter(s.width for s in steps)
    longest = max((r.item.prompt_len + sum(1 for t in r.emits if t < run.hi)
                   for r in run.requests
                   if any(run.lo <= t < run.hi for t in r.emits)), default=0)
    eos = sum(1 for r in run.requests
              if r.finished_at is not None and run.lo <= r.finished_at < run.hi
              and len(r.gen.result) < r.item.max_new)
    gaps = np.diff([s.t for s in steps]) * 1000.0
    if len(gaps):
        med = float(np.median(gaps))
        slow = gaps[gaps > 4.0 * med]
        gap = (f"median {med:.2f} ms, longest {float(gaps.max()):.2f} ms, "
               f"{len(slow)} over 4x the median totalling "
               f"{float(slow.sum()):.1f} ms")
    else:
        gap = "none"
    return (f"steps by the longest row's pages, as a power of two: "
            f"{dict(sorted(widths.items()))}; longest context {longest} "
            f"tokens; {len(readings.prefill_tokens(run))} prefills; {eos} "
            f"requests ended at end-of-sequence before their budget; gaps "
            f"between steps: {gap}")


def _engine_stats(engine) -> Dict[str, float]:
    from dataclasses import asdict
    return {k: float(v) for k, v in asdict(engine.stats).items()}


def serve(pump: Pump, traffic: Traffic, seconds: float,
          trace_dir: Optional[str] = None, grace_s: float = GRACE_S,
          backlog: Optional[List[Tuple[float, int]]] = None):
    """Serve the traffic: its warm segment, then the window.  Returns the
    window's bounds, the serving end and the stats at both bounds.  With
    ``backlog`` a list, the requests due and not yet admitted are sampled
    into it after every step."""
    node = pump.node
    t0 = time.perf_counter()
    lo = t0 + traffic.warm_s
    hi = lo + seconds
    trace_at = max(lo, hi - TRACE_SECONDS) if trace_dir else None
    tracing = None
    snap: Dict[str, Dict] = {}
    comp: Dict[str, Tuple[int, int]] = {}
    cs = CompileStats.get()
    closed = traffic.kind == "closed_loop"
    if closed:
        for it in traffic.first():
            pump.offer(it, t0)
        items: List[Item] = []
    else:
        items = traffic.items
    i = 0
    while True:
        now = time.perf_counter()
        if "lo" not in snap and now >= lo:
            snap["lo"], comp["lo"] = _engine_stats(node.engine), cs.snapshot()
        if trace_at is not None and tracing is None and now >= trace_at:
            jax.profiler.start_trace(trace_dir)
            tracing = jax.profiler.TraceAnnotation(tracemod.WINDOW_SPAN)
            tracing.__enter__()
            traced_lo = time.perf_counter()
        if "hi" not in snap and now >= hi:
            snap["hi"], comp["hi"] = _engine_stats(node.engine), cs.snapshot()
            if tracing is not None:
                pump.traced = (traced_lo, time.perf_counter())
                tracing.__exit__(None, None, None)
                jax.profiler.stop_trace()
                trace_at = None
            if closed:
                break
        if "hi" in snap and not closed:
            waiting = [r for r in pump.requests
                       if lo <= r.due < hi and r.first_token_at is None]
            if not waiting or now >= hi + grace_s:
                break
        while i < len(items) and t0 + items[i].due <= now:
            pump.offer(items[i], t0 + items[i].due)
            i += 1
        done = pump.step()
        if done is None:
            nxt = t0 + items[i].due if i < len(items) else now + 1e-3
            time.sleep(max(0.0, min(nxt - now, 1e-3)))
            continue
        if backlog is not None:
            backlog.append((time.perf_counter(), pump.backlog()))
        if closed:
            t = time.perf_counter()
            for r in done:
                pump.offer(traffic.next_for(r.item.client, t - t0), t)
    return lo, hi, time.perf_counter(), snap, comp


def peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks)


# --------------------------------------------------------- output check
@dataclass
class Check:
    requests: int
    tokens: int
    worst_gap_std: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.tokens > 0 and self.worst_gap_std <= self.limit


def check_sample(reqs: Sequence[Req], seed: int, since: float
                 ) -> List[Req]:
    """The requests the check covers, among those that finished from
    ``since`` on: the one with the longest context, then others drawn from
    the seed until the sample holds ``CHECK_MIN_TOKENS`` served tokens and
    ``CHECK_MIN_REQUESTS`` requests."""
    done = [r for r in reqs if r.gen.result is not None
            and len(r.gen.result) > 0 and r.finished_at is not None
            and r.finished_at >= since]
    if not done:
        return []
    longest = max(done, key=lambda r: r.item.prompt_len
                  + len(r.gen.result))
    rest = [r for r in done if r is not longest]
    order = seed_rng(seed, 3).permutation(len(rest))
    out = [longest]
    for j in order:
        if (sum(len(r.gen.result) for r in out) >= CHECK_MIN_TOKENS
                and len(out) >= CHECK_MIN_REQUESTS) \
                or len(out) >= CHECK_MAX_REQUESTS:
            break
        out.append(rest[j])
    return out


def served_gaps(cell: Cell, weights: Dict, reqs: List[Req],
                control: Optional[str] = None) -> Tuple[int, float]:
    """(tokens, worst gap) of the served tokens of ``reqs`` against the
    architecture's float32 reference.  With ``control`` ("int8", "fp8")
    the control's: the gap of the token that the reference in that
    precision puts first at each of those positions."""
    tokens, worst = 0, 0.0
    for r in reqs:
        out = np.asarray(r.gen.result, np.int64)
        seq = np.concatenate([r.item.tokens, out])
        p = r.item.prompt_len
        ref = cell.arch.reference_logits(weights, cell.config, seq, p - 1,
                                         len(out))
        chosen = out
        if control:
            low = cell.arch.reference_logits(weights, cell.config, seq,
                                             p - 1, len(out),
                                             control=control)
            chosen = low.argmax(axis=1)
        g = reference_gaps(ref, chosen)
        tokens += len(out)
        worst = max(worst, float(g.max()))
    return tokens, worst


# ------------------------------------------------------------------ main
def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="one cell of BENCHMARK.json")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, unless ``JAX_COMPILATION_CACHE_DIR`` names one; every
    program is kept, however quick to compile, so a second run of a cell
    compiles nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def measure(cell: Cell, seed: int, seconds: float, trace: bool,
            t_start: float, log=print) -> Tuple[Run, Node, Pump]:
    devices = accelerator(cell.chips)
    mem = devices[0].memory_stats() or {}
    log(f"device: {devices[0].platform} {devices[0].device_kind} x "
        f"{len(devices)}; bytes limit {mem.get('bytes_limit', 'not given')}")
    log(f"compile cache: {enable_cache(cell.root)}")
    cs = CompileStats.get()
    node = build_node(cell, seed, devices[0])
    log(f"weights: {node.sizes.param_bytes()} bytes "
        f"({node.sizes.layers} layers, {node.cfg.dtype}); pool "
        f"{cell.config['engine']['num_pages']} pages of "
        f"{cell.config['engine']['page_size']} tokens")
    pump = Pump(node)
    traffic = Traffic(cell.mix, seed, seconds, node.sizes.vocab,
                      node.cfg.eos_id)
    warm_rng = np.random.default_rng(12345)
    warm_shapes(pump, cell.config, cell.mix,
                lambda n: warm_rng.integers(0, node.sizes.vocab, n,
                                            dtype=np.int64).astype(np.int32))
    log(f"shapes warm: {len(pump.requests)} requests, compiles "
        f"{cs.compiles}, persistent-cache hits {cs.cache_hits}")
    pump.requests.clear()
    pump.steps.clear()
    pump.preemptions = 0
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    lo, hi, end, snap, comp = serve(pump, traffic, seconds, trace_dir)
    setup_s = lo - t_start
    run = Run(cell=cell, seconds=seconds, lo=lo, hi=hi, end=end,
              requests=list(pump.requests), steps=list(pump.steps),
              stats=stats.deltas(snap["hi"], snap["lo"]), sizes=node.sizes,
              setup_s=setup_s, preemptions=pump.preemptions,
              compiles_in_window=comp["hi"][0] - comp["lo"][0],
              cache_hits_in_window=comp["hi"][1] - comp["lo"][1],
              traced=pump.traced)
    if trace_dir:
        try:
            red = tracemod.reduce(tracemod.load(
                tracemod.find_xplane(trace_dir)))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        run.trace = red
    return run, node, pump


def calibrate(root: Path, workload: str, seeds: Sequence[int],
              seconds: float, log=print) -> List[Tuple[int, Check, Check]]:
    """For each seed: one run of the cell at its own load and sizes, then
    the output check of the program and of the control (the reference in
    the configuration's ``check.control`` precision put in the program's
    place) over the same sample of finished requests, each held to the
    configuration's limit as a run holds the program.  Returns ``(seed,
    program check, control check)``; the limit is set between the largest
    program gap and the smallest control gap, and the control's check has
    to come out not correct."""
    cell = load_cell(root, workload)
    control = cell.config["check"]["control"]
    limit = float(cell.config["check"]["worst_gap_std"])
    out = []
    for seed in seeds:
        run, node, pump = measure(cell, seed, seconds, False,
                                    time.perf_counter(), log=lambda *_: None)
        sample = check_sample(run.requests, seed, run.lo)
        weights = node.weights
        del pump, node, run
        gc.collect()
        prog = Check(len(sample), *served_gaps(cell, weights, sample), limit)
        ctrl = Check(len(sample), *served_gaps(cell, weights, sample,
                                               control=control), limit)
        del weights
        gc.collect()
        log(f"seed {seed}: {prog.tokens} tokens of {prog.requests} requests; "
            f"program worst gap {prog.worst_gap_std} (correct {prog.ok}); "
            f"{control} control worst gap {ctrl.worst_gap_std} (correct "
            f"{ctrl.ok}); limit {limit}")
        out.append((seed, prog, ctrl))
    log(f"program: largest {max(p.worst_gap_std for _, p, _ in out)} over "
        f"{len(out)} seeds; control: smallest "
        f"{min(c.worst_gap_std for _, _, c in out)}; control correct on "
        f"{sum(c.ok for _, _, c in out)} of {len(out)} seeds")
    return out


def peak_table(root: Path, device) -> Dict:
    table = _load_json(root / "bench" / "peaks.json")
    kinds = {k: v for k, v in table.items() if not k.startswith("_")}
    if device.device_kind not in kinds:
        raise KeyError(f"no peaks for device kind {device.device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(kinds)})")
    return kinds[device.device_kind]


def _number(x: float):
    return int(x) if float(x).is_integer() and abs(x) < 2 ** 53 else float(x)


def main(argv=None, root: Path = ROOT, t_start: Optional[float] = None
         ) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    cell = load_cell(root, args.workload)
    try:
        run, node, pump = measure(cell, args.seed, args.seconds,
                                    bool(args.trace), t_start)
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    devices = accelerator(cell.chips)
    mem_peak = peak_bytes(devices)
    due = run.due_in_window()
    attempted = len(due)
    # an open loop's request fails when it has no first token by the end
    # of the grace period; a closed loop's callers just wait their turn
    failed = (0 if cell.mix["kind"] == "closed_loop"
              else sum(1 for r in due if r.first_token_at is None))
    ttft = readings.ttft_values(run)
    print(f"window: {run.seconds}s, {len(run.window_steps)} steps, "
          f"{len(due)} requests due (time to first token p50 "
          f"{stats.percentile(ttft, 50)} s, p90 {stats.percentile(ttft, 90)}"
          f" s), {failed} without a first token by "
          f"{run.end - run.hi:.1f}s past the window, "
          f"{run.preemptions} preemptions; backend compiles in the window: "
          f"{run.compiles_in_window} (persistent-cache loads "
          f"{run.cache_hits_in_window}); set-up {run.setup_s:.3f}s; "
          f"peak bytes in use {mem_peak}")
    print(f"window held: {window_shape(run)}")
    # the output check runs once the engine and its page pool are freed
    sample = check_sample(run.requests, args.seed, run.lo)
    weights = node.weights
    del pump, node
    gc.collect()
    tokens, worst = served_gaps(cell, weights, sample)
    chk = Check(len(sample), tokens, worst,
                float(cell.config["check"]["worst_gap_std"]))
    metrics: Dict[str, Dict] = {}
    if args.trace:
        run.peaks = peak_table(root, devices[0])
        wanted = cell.per_layer
    else:
        wanted = cell.end_to_end
    for m in wanted:
        v = reader(root, m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": _number(v), "unit": m["unit"]}
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": mem_peak}
    result = {"correct": chk.ok, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": dev}
    if args.trace and run.trace is not None:
        dev["busy_s"] = run.trace.busy_s
        dev["window_s"] = run.trace.window_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in run.trace.top_ops],
            "idle_gaps": [[n, s] for n, s in run.trace.idle_gaps]}
    result["compared"] = {"worst_gap_std": {"value": chk.worst_gap_std,
                                            "limit": chk.limit}}
    print(f"output check: {chk.tokens} served tokens of {chk.requests} "
          f"requests against the float32 reference")
    print(json.dumps(result))
    sys.stdout.flush()
    print(f"worst_gap_std {chk.worst_gap_std} limit {chk.limit}",
          file=sys.stderr)
    return 0
