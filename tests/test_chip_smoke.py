"""CPU rehearsals of ``chip_smoke.py`` and the compile-cache rule.

The chip runs ``chip_smoke.py`` at qwen3-8b's published widths; here the
same phases run at the float32 smoke preset: serving through
``repro.launch.serve``, the float32 reference check, the refusal to run
without a TPU, and the four-replica placement on four virtual CPU devices
(in a child process, since the device count is fixed when JAX starts).
"""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

import chip_smoke
from repro.launch import compile_cache, serve

REPO = pathlib.Path(__file__).resolve().parents[1]
PROMPT_LEN, MAX_NEW = 32, 8


@pytest.fixture(scope="module")
def smoke_run():
    made = {}
    run = chip_smoke.serve_phase(2, 8, None, made=made,
                                 prompt_len=PROMPT_LEN, max_new=MAX_NEW)
    return run, made


def _child_env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONPATH")}
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


class TestServeAndReference:
    def test_serve_and_reference_phases_pass_at_smoke_preset(self,
                                                             smoke_run):
        run, made = smoke_run
        done = run.completed()
        assert [r.rid for r in done] == [f"r{i}" for i in range(8)]
        assert all(len(r.result) >= 1 for r in done)
        chk = chip_smoke.reference_check(run, jax.devices()[0], made)
        assert chk.ok, chip_smoke.describe(chk)
        assert chk.requests == 8
        assert chk.tokens == sum(len(r.result) for r in done)
        assert chk.worst_gap <= 1e-3 and chk.worst_first_err <= 1e-3

    def test_reference_check_rejects_a_wrong_token(self, smoke_run):
        run, made = smoke_run
        r = run.completed()[3]
        kept = r.result.copy()
        try:
            r.result[-1] = (int(kept[-1]) + run.cfg.vocab_size // 2) \
                % run.cfg.vocab_size
            chk = chip_smoke.reference_check(run, jax.devices()[0], made)
        finally:
            r.result = kept
        assert not chk.ok
        assert len(chk.failures) == 1 and chk.failures[0].startswith("r3 ")

    def test_serve_main_reports_each_node_and_its_device(self, capsys,
                                                         monkeypatch):
        monkeypatch.setattr(serve, "enable_compile_cache", lambda: "")
        assert serve.main(["--nodes", "2", "--requests", "4",
                           "--max-new", "4", "--paged"]) == 0
        out = capsys.readouterr().out
        dev = str(jax.devices()[0])
        assert f"node1 on {dev}: served" in out
        assert "generated" in out


class TestRefusesWithoutChip:
    def test_main_on_cpu_exits_nonzero_without_result(self, capsys):
        assert chip_smoke.main([]) != 0
        out = capsys.readouterr().out
        assert '"ok": true' not in out

    def test_script_alone_exits_nonzero(self, tmp_path):
        shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
        p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                           env=_child_env(), capture_output=True, text=True,
                           timeout=120)
        assert p.returncode != 0
        assert '"ok"' not in p.stdout


def test_four_replicas_each_on_own_device():
    """Node i's engine holds only device i, and the four-replica outputs
    equal one replica's on device 0."""
    code = ("import json, chip_smoke\n"
            "res = chip_smoke.four_chip_phase(layers=None)\n"
            "print(json.dumps(dict(placement=res.placement, placed=res.placed,"
            " identical=res.identical, requests=res.requests,"
            " four=res.four.ok, one=res.one.ok, ok=res.ok)))\n")
    p = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=600,
        env=_child_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["placement"] == {f"node{i + 1}": [f"TFRT_CPU_{i}"]
                                for i in range(4)}
    assert res["placed"] and res["four"] and res["one"] and res["ok"]
    assert res["identical"] == res["requests"] == \
        chip_smoke.FOUR_CHIP_REQUESTS


class TestCompileCache:
    @pytest.fixture
    def keep_cache_dir(self):
        before = jax.config.jax_compilation_cache_dir
        yield before
        jax.config.update("jax_compilation_cache_dir", before)

    def test_env_var_wins_and_nothing_is_set(self, monkeypatch, tmp_path,
                                             keep_cache_dir):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == keep_cache_dir

    def test_default_is_the_fixed_checkout_dir(self, monkeypatch,
                                               keep_cache_dir):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(REPO / ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        ignored = (REPO / ".gitignore").read_text().splitlines()
        assert ".jax_cache/" in ignored
