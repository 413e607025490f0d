"""The architecture registry (``bench/arch``): the Qwen3 module gives what
the dense helpers gave before the harness went through it, bit for bit,
and a module placed in ``bench/arch/`` is found by its ``model_type`` with
no other file changed."""

import json
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import harness, reference, weights, work
from bench.harness import Step
from bench.tests.test_run import (FIXTURES, cpu, result,  # noqa: F401
                                  run, tree, write_benchmark)

ROOT = Path(__file__).resolve().parents[2]
CONFIGS = ["qwen3-8b.l18", "qwen3-32b.l8"]
# the widths a CPU can run, in each configuration's own layout
SMALL = {"hidden_size": 64, "intermediate_size": 128,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "num_hidden_layers": 2, "vocab_size": 500, "padded_vocab_size": 512}


def config(name, small=False):
    cfg = json.loads((ROOT / "bench/configs" / f"{name}.json").read_text())
    return dict(cfg, **SMALL) if small else cfg


@pytest.fixture(scope="module")
def qwen3():
    return harness.architecture(ROOT, "qwen3")


@pytest.mark.parametrize("name", CONFIGS)
def test_sizes_are_the_dense_counts(qwen3, name):
    cfg = config(name)
    s = qwen3.sizes(cfg)
    assert s == work.Sizes.of(cfg)
    assert s.layers == cfg["num_hidden_layers"]
    assert s.vocab == 151936 and s.padded_vocab == 152064
    assert s.prefill_flops([1000, 24]) == work.Sizes.of(cfg).prefill_flops(
        [1000, 24])


@pytest.mark.parametrize("name", CONFIGS)
def test_model_config_is_the_program_dense_family(qwen3, name):
    from repro.models.config import ModelConfig
    cfg = config(name)
    want = ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["head_dim"], qk_norm=True,
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        eos_id=int(cfg["eos_token_id"]), dtype=cfg["torch_dtype"])
    assert qwen3.model_config(cfg) == want
    assert want.padded_vocab == qwen3.sizes(cfg).padded_vocab


@pytest.mark.parametrize("name", CONFIGS)
def test_decode_work_is_the_per_step_sum(qwen3, name):
    s = qwen3.sizes(config(name))
    steps = [Step(1.0, 3, 3, 3000), Step(2.0, 2, 0, 0),
             Step(3.0, 32, 31, 60000), Step(9.0, 5, 5, 500)]
    run = SimpleNamespace(sizes=s, window_steps=steps[:3])
    flops = nbytes = 0.0
    for st in steps[:3]:
        if st.decoded:
            flops += s.decode_flops(st.decoded, st.context)
            nbytes += s.decode_bytes(st.decoded, st.context)
    assert qwen3.decode_work(run) == (flops, nbytes)


@pytest.mark.parametrize("name", CONFIGS)
def test_draw_and_reference_are_the_dense_helpers_bit_for_bit(qwen3, name):
    cfg = config(name, small=True)
    dev = jax.devices()[0]
    seed = 3_000_000_019
    got = qwen3.draw(cfg, seed, dev)
    again = qwen3.draw(cfg, seed, dev)
    old = weights.draw(work.Sizes.of(cfg), seed, dev,
                       dtype=cfg["torch_dtype"])
    leaves = jax.tree_util.tree_leaves_with_path(got)
    assert {jax.tree_util.keystr(p) for p, _ in leaves} >= {
        "['layers']['wq']", "['layers']['q_norm']", "['lm_head']"}
    for tree_ in (again, old):
        assert jax.tree_util.tree_structure(tree_) == \
            jax.tree_util.tree_structure(got)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(tree_)):
            assert a.dtype == b.dtype == jax.numpy.bfloat16
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    toks = np.random.default_rng(5).integers(0, 500, 40)
    for control in (None, "int8"):
        new = qwen3.reference_logits(got, cfg, toks, 29, 11, control=control)
        ref = reference.logits(old, work.Sizes.of(cfg),
                               float(cfg["rope_theta"]), toks, 29, 11,
                               control=control)
        assert new.shape == (11, 500)
        np.testing.assert_array_equal(new, ref)


def test_unknown_model_type_fails_with_the_known_names(tmp_path):
    with pytest.raises(ValueError, match=r"known: \['qwen3'\]"):
        harness.architecture(ROOT, "mixtral")
    for bad in ("../qwen3", "__init__", ""):
        with pytest.raises(ValueError, match="no architecture module"):
            harness.architecture(ROOT, bad)
    (tmp_path / "bench/arch").mkdir(parents=True)
    (tmp_path / "bench/arch/toy.py").write_text("X = 1\n")
    assert harness.architectures(tmp_path) == ["toy"]
    assert harness.architecture(tmp_path, "toy").X == 1


def test_a_module_placed_in_bench_arch_is_taken_with_no_other_edit(
        tree, cpu, capsys):
    """A later change adds ``bench/arch/<model_type>.py`` and a
    configuration naming it; a cell on it runs through the harness."""
    (tree / "bench/arch/toy_dense.py").write_text(
        "from bench.arch.qwen3 import (decode_work, draw, model_config,\n"
        "                              reference_logits)\n"
        "from bench.arch.qwen3 import sizes as _sizes\n\n\n"
        "def sizes(config):\n"
        "    print('toy_dense sizes for', config['name'])\n"
        "    return _sizes(config)\n")
    cfg = json.loads((FIXTURES / "smoke.json").read_text())
    cfg.update(name="toy", model_type="toy_dense")
    (tree / "bench/configs/toy.json").write_text(json.dumps(cfg))
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    write_benchmark(tree, [{"name": "toy.chat", "config": "toy",
                            "traffic": "smoke_chat", "chips": 1,
                            "why": "test"}],
                    [dict(m, workloads=["toy.chat"]) for m in
                     bench["end_to_end"] if m["name"] == "itl_p50_ms"]
                    + [m for m in bench["end_to_end"]
                       if m["name"] == "setup_s"], [],
                    [{"name": "toy", "source": "test",
                      "file": "bench/configs/toy.json", "reduced": [],
                      "why": "test"}])
    rc, out, _ = run(tree, "toy.chat", capsys)
    assert rc == 0
    assert "toy_dense sizes for toy" in out
    res = result(out)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"itl_p50_ms", "setup_s"}


def test_a_config_naming_an_unknown_architecture_is_refused(tree):
    cfg = json.loads((tree / "bench/configs/smoke.json").read_text())
    cfg["model_type"] = "nope"
    (tree / "bench/configs/smoke.json").write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match=r"'nope'.*known: \['qwen3'\]"):
        harness.load_cell(tree, "smoke.chat")
