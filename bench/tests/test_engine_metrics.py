"""The per-layer metrics read from the engine's step counters, end to end
on the CPU: ``step_host_ms`` (the step's wall time outside its forwards)
and ``prefill_real_share`` (real prompt tokens over padded prefill
positions).  A program without those counters reads nothing, and the run
leaves the metrics out rather than failing."""

import json
from types import SimpleNamespace

from bench import harness
from bench.tests.test_run import (E2E, PER_LAYER, cpu,  # noqa: F401
                                  result, run, tree, write_benchmark)

NEW = [
    {"name": "step_host_ms.batch", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "engine step",
     "moves": "output_tok_s", "workloads": ["smoke.batch"]},
    {"name": "prefill_real_share", "unit": "%", "better": "higher",
     "source": "program_counter", "layer": "engine step",
     "moves": "output_tok_s", "workloads": ["smoke.batch"]},
]


def test_traced_run_reads_step_host_and_real_prefill_share(tree, cpu,
                                                           capsys):
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    write_benchmark(tree, bench["workloads"], E2E, PER_LAYER + NEW)
    rc, out, _ = run(tree, "smoke.batch", capsys, trace=1)
    assert rc == 0
    res = result(out)
    assert res["correct"] is True
    got = res["metrics"]
    # no TPU plane in a CPU trace: device_idle_share reads nothing
    assert set(got) == {"decode_step_ms.batch", "batch_occupancy.batch",
                        "decode_hbm_share.batch", "prefill_ms_per_ktok",
                        "step_host_ms.batch", "prefill_real_share"}
    assert got["step_host_ms.batch"]["value"] > 0
    assert got["step_host_ms.batch"]["unit"] == "ms"
    # smoke prompts (lognormal around 24 tokens) pad to the 64-token bucket
    assert 0 < got["prefill_real_share"]["value"] < 100


def _run_with(stats):
    return SimpleNamespace(stats=stats)


def test_readers_read_nothing_from_a_program_without_the_counters():
    old = {"decode_steps": 10.0, "decode_wall_s": 1.0,
           "prefill_wall_s": 0.5, "prefill_tokens": 4096.0}
    for name in ("step_host_ms.batch", "step_host_ms.chat",
                 "prefill_real_share"):
        assert harness.reader(harness.ROOT, name)(_run_with(old)) is None


def test_reader_arithmetic():
    st = {"steps": 4.0, "step_wall_s": 1.0, "decode_wall_s": 0.7,
          "prefill_wall_s": 0.1, "prefill_tokens": 8192.0,
          "prefill_prompt_tokens": 1024.0}
    host = harness.reader(harness.ROOT, "step_host_ms.chat")(_run_with(st))
    assert abs(host - 50.0) < 1e-9              # (1 - 0.7 - 0.1) / 4 s
    share = harness.reader(harness.ROOT, "prefill_real_share")(_run_with(st))
    assert share == 12.5
    assert harness.reader(harness.ROOT, "prefill_real_share")(
        _run_with(dict(st, prefill_tokens=0.0))) is None
