"""The host replay that chooses a closed-loop mix's schedule seed."""

import json
from pathlib import Path

from bench import replay

MIX = json.loads((Path(__file__).resolve().parents[1] / "traffic"
                  / "batch.json").read_text())
DECODE_S = {128: 0.051, 256: 0.105, 512: 0.2, 1024: 0.38}


def test_replay_is_deterministic_and_widths_are_powers_of_two():
    a = replay.replay(MIX, 3009, 51.0, DECODE_S, 0.014, 0.25)
    assert a == replay.replay(MIX, 3009, 51.0, DECODE_S, 0.014, 0.25)
    widths, longest, rate = a
    assert abs(sum(widths.values()) - 1.0) < 1e-9
    assert all(w & (w - 1) == 0 for w in widths)
    # the window's longest row fits the widest table it reports
    assert longest <= max(widths) * 16 and rate > 0


def test_the_batch_mix_serves_the_median_schedule(capsys):
    assert replay.main(["--mix", "batch"]) == 0
    out = capsys.readouterr().out
    assert f"median schedule_seed {MIX['schedule_seed']}:" in out
