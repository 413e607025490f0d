"""The harness end to end on the CPU, at a test size.

Each test copies ``bench/`` into a temporary checkout with a smoke-size
configuration and mixes (``fixtures/``), and calls ``harness.main`` in
this process.  The device check is steered here, in the test: the
measured path itself refuses anything but a TPU, which the first test
shows.  The persistent compilation cache stays off.
"""

import json
import shutil
from pathlib import Path

import pytest

from bench import harness

BENCH = Path(__file__).resolve().parents[1]
FIXTURES = Path(__file__).resolve().parent / "fixtures"
SEED = 3_000_000_019          # larger than 32 signed bits hold

E2E = [
    {"name": "itl_p50_ms", "unit": "ms", "better": "lower", "bound": 0.1,
     "source": "host_clock", "workloads": ["smoke.chat"]},
    {"name": "output_tok_s", "unit": "tokens/s", "better": "higher",
     "bound": 0.1, "source": "host_clock", "workloads": ["smoke.batch"]},
    {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
     "source": "host_clock"},
]
PER_LAYER = [
    {"name": "prefill_ms_per_ktok", "unit": "ms/ktok", "better": "lower",
     "source": "program_span", "layer": "engine step",
     "moves": "output_tok_s", "workloads": ["smoke.batch"]},
    {"name": "decode_step_ms.batch", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "engine step",
     "moves": "output_tok_s", "workloads": ["smoke.batch"]},
    {"name": "batch_occupancy.batch", "unit": "rows", "better": "higher",
     "source": "program_counter", "layer": "admission",
     "moves": "output_tok_s", "workloads": ["smoke.batch"]},
    {"name": "decode_hbm_share.batch", "unit": "%", "better": "higher",
     "source": "program_span", "layer": "model step",
     "moves": "output_tok_s", "workloads": ["smoke.batch"]},
    {"name": "device_idle_share.batch", "unit": "%", "better": "lower",
     "source": "device_trace", "layer": "device", "moves": "output_tok_s",
     "workloads": ["smoke.batch"]},
]


@pytest.fixture
def tree(tmp_path, monkeypatch):
    """A checkout holding ``bench/`` and a BENCHMARK.json of smoke cells."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(FIXTURES / "smoke.json", tmp_path / "bench/configs/smoke.json")
    for mix in ("smoke_chat", "smoke_batch"):
        shutil.copy(FIXTURES / f"{mix}.json",
                    tmp_path / "bench/traffic" / f"{mix}.json")
    peaks = json.loads((tmp_path / "bench/peaks.json").read_text())
    peaks["cpu"] = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    (tmp_path / "bench/peaks.json").write_text(json.dumps(peaks))
    write_benchmark(tmp_path, [
        {"name": "smoke.chat", "config": "smoke", "traffic": "smoke_chat",
         "chips": 1, "why": "test"},
        {"name": "smoke.batch", "config": "smoke", "traffic": "smoke_batch",
         "chips": 1, "why": "test"}], E2E, PER_LAYER)
    monkeypatch.setattr(harness, "enable_cache", lambda root: "off")
    return tmp_path


def write_benchmark(root, workloads, e2e, per_layer, configs=None):
    configs = configs or [{"name": "smoke", "source": "test",
                           "file": "bench/configs/smoke.json",
                           "reduced": [], "why": "test"}]
    (root / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 2, "configs": configs, "workloads": workloads,
        "end_to_end": e2e, "per_layer": per_layer}))


def run(root, workload, capsys, trace=0, seed=SEED):
    rc = harness.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", "2", "--trace", str(trace)], root=root)
    out, err = capsys.readouterr()
    return rc, out, err


def result(out):
    return json.loads(out.strip().splitlines()[-1])


def test_refuses_to_measure_off_the_chip(tree, capsys):
    rc, out, err = run(tree, "smoke.chat", capsys)
    assert rc != 0
    assert "no tpu" in err
    assert not any(line.startswith("{") for line in out.splitlines())


@pytest.fixture
def cpu(monkeypatch):
    monkeypatch.setattr(harness, "REQUIRED_PLATFORM", "cpu")


def test_open_loop_cell_end_to_end(tree, cpu, capsys):
    rc, out, err = run(tree, "smoke.chat", capsys)
    assert rc == 0
    res = result(out)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"itl_p50_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    assert list(res)[-1] == "compared"
    gap = res["compared"]["worst_gap_std"]
    assert gap["value"] <= gap["limit"]
    assert err.strip().splitlines()[-1].startswith("worst_gap_std ")


def test_new_cell_from_added_files_only(tree, cpu, capsys):
    """A later change adds a configuration, a mix and a metric as files,
    and entries in BENCHMARK.json; no file of the harness changes."""
    cfg = json.loads((FIXTURES / "smoke.json").read_text())
    cfg.update(name="smoke.l3", num_hidden_layers=3)
    (tree / "bench/configs/smoke.l3.json").write_text(json.dumps(cfg))
    mix = json.loads((FIXTURES / "smoke_batch.json").read_text())
    mix.update(clients=6, steady_start=False)
    (tree / "bench/traffic/smoke_few.json").write_text(json.dumps(mix))
    (tree / "bench/metrics/finished_per_s.py").write_text(
        "def read(run):\n"
        "    done = [r for r in run.requests if r.finished_at is not None\n"
        "            and run.lo <= r.finished_at < run.hi]\n"
        "    return len(done) / run.seconds or None\n")
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "smoke.l3", "source": "test",
                             "file": "bench/configs/smoke.l3.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "smoke.l3.few", "config": "smoke.l3",
                               "traffic": "smoke_few", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "finished_per_s", "unit": "1/s",
                                "better": "higher", "bound": 0.1,
                                "source": "host_clock",
                                "workloads": ["smoke.l3.few"]})
    # a new suffix of a quantity that has a reader needs no file
    bench["per_layer"].append(dict(PER_LAYER[2], name="batch_occupancy.few",
                                   moves="finished_per_s",
                                   workloads=["smoke.l3.few"]))
    write_benchmark(tree, bench["workloads"], bench["end_to_end"],
                    bench["per_layer"], bench["configs"])
    rc, out, _ = run(tree, "smoke.l3.few", capsys)
    assert rc == 0
    res = result(out)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"finished_per_s", "setup_s"}
    assert "3 layers" in out
    rc, out, _ = run(tree, "smoke.l3.few", capsys, trace=1)
    assert rc == 0
    res = result(out)
    assert set(res["metrics"]) == {"batch_occupancy.few"}
    assert 1 <= res["metrics"]["batch_occupancy.few"]["value"] <= 4


def test_closed_loop_cell_per_layer_metrics(tree, cpu, capsys):
    rc, out, _ = run(tree, "smoke.batch", capsys, trace=1)
    assert rc == 0
    held = [ln for ln in out.splitlines() if ln.startswith("window held: ")]
    assert len(held) == 1
    assert "steps by the longest row's pages, as a power of two: {" \
        in held[0]
    res = result(out)
    assert res["correct"] is True
    # no TPU plane in a CPU trace: the device reader finds nothing
    assert set(res["metrics"]) == {"decode_step_ms.batch",
                                   "batch_occupancy.batch",
                                   "decode_hbm_share.batch",
                                   "prefill_ms_per_ktok"}
    assert res["failed"] == 0


def test_a_token_altered_where_it_is_produced_fails_the_check(
        tree, cpu, capsys, monkeypatch):
    import repro.serving.engine as engine
    calls = {"n": 0}
    real = engine.sample

    def altered(key, logits, **kw):
        tok = real(key, logits, **kw)
        calls["n"] += 1
        if calls["n"] % 7 == 0:          # now and then, row 0's token
            tok = tok.at[0, 0].set((tok[0, 0] + 1) % kw["vocab_size"])
        return tok

    monkeypatch.setattr(engine, "sample", altered)
    rc, out, err = run(tree, "smoke.chat", capsys)
    assert rc == 0
    res = result(out)
    assert res["correct"] is False
    gap = res["compared"]["worst_gap_std"]
    assert gap["value"] > gap["limit"]


def test_int8_control_fails_the_limit_that_the_program_meets(tree, cpu):
    """The control: the reference in a lower precision put in the
    program's place, on the same requests, is held to the configuration's
    limit by the run's own check and comes out not correct on every
    seed, where the program comes out correct."""
    limit = json.loads((FIXTURES / "smoke.json").read_text())[
        "check"]["worst_gap_std"]
    got = harness.calibrate(tree, "smoke.chat", [11, 12, 13], 2.0,
                            log=lambda *_: None)
    for _, prog, ctrl in got:
        assert prog.limit == ctrl.limit == limit
        assert prog.ok and not ctrl.ok
        assert prog.tokens == ctrl.tokens > 0
