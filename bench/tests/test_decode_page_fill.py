"""The per-layer metric ``decode_page_fill``, read from the engine's
decode page counters, end to end on the CPU: the share of the decode
block tables that holds KV.  A program without those counters reads
nothing, and the run leaves the metric out rather than failing."""

import json
from types import SimpleNamespace

from bench import harness
from bench.tests.test_run import (E2E, PER_LAYER, cpu,  # noqa: F401
                                  result, run, tree, write_benchmark)

PAGE_FILL = {"name": "decode_page_fill.batch", "unit": "%",
             "better": "higher", "source": "program_counter",
             "layer": "model step", "moves": "output_tok_s",
             "workloads": ["smoke.batch"]}


def _run_with(stats):
    return SimpleNamespace(stats=stats)


def test_traced_run_reads_decode_page_fill(tree, cpu, capsys):
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    write_benchmark(tree, bench["workloads"], E2E, PER_LAYER + [PAGE_FILL])
    rc, out, _ = run(tree, "smoke.batch", capsys, trace=1)
    assert rc == 0
    got = result(out)["metrics"]["decode_page_fill.batch"]
    assert got["unit"] == "%"
    assert 0 < got["value"] <= 100


def test_decode_page_fill_arithmetic_and_a_program_without_it():
    read = harness.reader(harness.ROOT, "decode_page_fill.chat")
    assert read(_run_with({"decode_kv_pages": 96.0,
                           "decode_table_pages": 1024.0})) == 9.375
    assert read(_run_with({"decode_kv_pages": 0.0,
                           "decode_table_pages": 0.0})) is None
    assert read(_run_with({"decode_steps": 10.0})) is None
