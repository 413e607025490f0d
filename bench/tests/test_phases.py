"""bench/phases.py: the device's idle split by the engine's phase spans,
the idle inside the forward's spans, the clock skew, and the per-step
arithmetic over the tracer's wall spans."""

from pathlib import Path

import pytest

from bench import phases, trace
from bench.tests.test_run import SEED, cpu, tree  # noqa: F401
from repro.obs import WALL, Tracer

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "small.xplane.pb"


@pytest.fixture
def step():
    """One engine step inside ``bench.step``, in ns: every phase holds a
    device op except retire and pages; the forward runs 450-790."""
    return phases.PhaseTrace(
        ops={"/device:TPU:0": [("sample", 210, 280), ("forward", 450, 790),
                               ("carry", 810, 850)]},
        modules={"/device:TPU:0": [("jit_argmax", 210, 280),
                                   ("jit_paged_decode_step", 450, 790),
                                   ("jit_scatter", 810, 850)]},
        spans=[("bench.traced", 0, 1000), ("bench.step", 100, 900),
               ("engine.step", 110, 890), ("engine.admit", 110, 200),
               ("engine.sample", 200, 300), ("engine.retire", 300, 350),
               ("engine.pages", 350, 400), ("engine.decode_step", 400, 800),
               ("engine.carry", 800, 880)])


def test_idle_split_by_innermost_engine_span(step):
    split = {k: round(v * 1e9) for k, v in phases.idle_split(step).items()}
    assert split == {"outside any bench span": 200, "bench.step": 20,
                     "engine.step": 10, "engine.admit": 90,
                     "engine.sample": 30, "engine.retire": 50,
                     "engine.pages": 50, "engine.decode_step": 60,
                     "engine.carry": 40}
    red = trace.reduce(step)
    assert sum(phases.idle_split(step).values()) == pytest.approx(
        red.window_s - red.busy_s)


def test_breakdown_gaps_take_the_engine_labels(step):
    """The harness's reduction labels a whole gap by its middle: with the
    engine's spans loaded, by the innermost engine phase there."""
    gaps = {n: round(s * 1e9) for n, s in trace.reduce(step).idle_gaps}
    assert gaps == {"bench.step": 210,          # 0-210, middle 105
                    "engine.pages": 170,        # 280-450, middle 365
                    "engine.carry": 20,         # 790-810, middle 800
                    "outside any bench span": 150}


def test_a_gap_across_phases_is_cut_at_their_edge():
    tr = trace.Trace(ops={"/device:TPU:0": [("a", 0, 10), ("b", 90, 100)]},
                     spans=[("bench.traced", 0, 100),
                            ("engine.retire", 10, 40),
                            ("engine.pages", 40, 90)])
    split = {k: round(v * 1e9) for k, v in phases.idle_split(tr).items()}
    assert split == {"engine.retire": 30, "engine.pages": 50}


def test_idle_inside_named_spans_and_devices_averaged(step):
    assert phases.idle_inside(step, "engine.decode_step") == (
        pytest.approx(60e-9), 1)
    two = trace.Trace(ops={"/device:TPU:0": [("f", 0, 50)],
                           "/device:TPU:1": [("f", 0, 100)]},
                      spans=[("bench.traced", 0, 200),
                             ("engine.decode_step", 0, 100),
                             ("engine.decode_step", 150, 250)])
    # 50 and 0 idle in the first span, 50 of the second's 100 in the window
    idle, n = phases.idle_inside(two, "engine.decode_step")
    assert n == 2 and idle == pytest.approx((25 + 50) * 1e-9)


def test_skew_readings_from_the_sampler_and_the_forward(step):
    assert phases.sample_skew_ms(step) == [pytest.approx(-20e-6)]
    assert phases.forward_skew_ms(step) == [
        (pytest.approx(50e-6), pytest.approx(-10e-6))]
    # device times 100 ns early: the forward seems to start inside the
    # sampler's span, and the sampler's program is still the one read
    early = phases.PhaseTrace(
        ops=step.ops, spans=step.spans,
        modules={"/device:TPU:0": [("jit_argmax", 110, 180),
                                   ("jit_paged_decode_step", 280, 690)]})
    assert phases.sample_skew_ms(early) == [pytest.approx(-120e-6)]
    assert phases.forward_skew_ms(early) == [
        (pytest.approx(-120e-6), pytest.approx(-110e-6))]
    # no program recorded: nothing to read
    bare = phases.PhaseTrace(ops=step.ops, spans=step.spans)
    assert phases.sample_skew_ms(bare) == []
    assert phases.forward_skew_ms(bare) == []


def test_shifted_moves_device_ops_only(step):
    moved = phases.shifted(step, 10)
    assert moved.spans == step.spans
    assert moved.ops["/device:TPU:0"][1] == ("forward", 460, 800)
    split = phases.idle_split(moved)
    assert round(split["engine.decode_step"] * 1e9) == 60
    assert round(split["engine.sample"] * 1e9) == 30


def test_step_phases_over_the_tracers_wall_spans():
    tr = Tracer()
    for k in range(3):
        t = 10.0 * k
        tr.span("engine.step", "", "n", t, t + 8.0, clock=WALL)
        tr.span("engine.admit", "", "n", t, t + 2.0, clock=WALL)
        tr.span("engine.prefill", "", "n", t + 0.5, t + 1.5, clock=WALL)
        tr.span("engine.decode_step", "", "n", t + 3.0, t + 7.0, clock=WALL)
    n, per, host = phases.step_phases(tr.spans, 0.0, 25.0)
    assert n == 3
    assert per == {"engine.admit": 2000.0, "engine.decode_step": 4000.0,
                   "engine.prefill": 1000.0, "engine.step": 8000.0}
    assert host == pytest.approx(3000.0)     # 8 - 4 - 1 s, in ms
    assert phases.step_phases(tr.spans, 50.0, 60.0) == (0, {}, 0.0)


def test_recorded_trace_loads_as_the_harness_loads_it():
    """The small TPU recording holds no engine spans: the loader keeps
    the same ops and bench spans, and the split sums to the idle."""
    mine, theirs = phases.load(str(FIXTURE)), trace.load(str(FIXTURE))
    assert mine.ops == theirs.ops
    assert sorted(mine.spans) == sorted(theirs.spans)
    assert [n for n, _, _ in mine.modules["/device:TPU:0"]] == \
        ["jit__lambda"] * 6
    red = trace.reduce(mine)
    split = phases.idle_split(mine)
    assert set(split) <= {"bench.step", "bench.bookkeeping",
                          "outside any bench span"}
    assert sum(split.values()) == pytest.approx(red.window_s - red.busy_s)


def test_report_on_a_traced_step(step):
    lines = []
    tr = Tracer()
    tr.span("engine.step", "", "n", 0.0, 0.1, clock=WALL)
    tr.span("engine.decode_step", "", "n", 0.02, 0.08, clock=WALL)
    phases.report(step, tr.spans, 0.0, 1.0, log=lines.append)
    text = "\n".join(lines)
    assert "device times as recorded: window 1e-06 s" in text
    assert "device times moved by 1e-05 ms" in text

    def number(after):
        return float(text.split(after, 1)[1].split()[0].rstrip(","))
    assert number("idle under engine.decode_step: ") == pytest.approx(6e-8)
    assert number("forward_idle_ms ") == pytest.approx(6e-5)
    assert number("forward program end - engine.decode_step end: n 1, "
                  "min ") == pytest.approx(-1e-5)
    assert number("step_host_ms ") == pytest.approx(40.0)
    assert number("  engine.decode_step: ") == pytest.approx(60.0)


def test_the_tool_runs_a_cell_end_to_end_on_the_cpu(tree, cpu, capsys,
                                                     monkeypatch):
    """The smoke batch cell, with the program's tracer on: a CPU trace
    has no TPU plane, so the device part reads nothing, and the spans of
    the window are still read."""
    from bench import harness
    monkeypatch.setattr(harness, "ROOT", tree)
    assert phases.main(["--workload", "smoke.batch", "--seed",
                        str(SEED), "--seconds", "2"]) == 0
    out = capsys.readouterr().out
    assert "smoke.batch, seed 3000000019, tracer on:" in out
    assert "device trace: no device operation in the window" in out
    steps = [ln for ln in out.splitlines()
             if ln.startswith("window, from the tracer's spans:")]
    assert len(steps) == 1 and " 0 steps" not in steps[0]
    assert "  engine.sample: " in out and "  engine.carry: " in out
