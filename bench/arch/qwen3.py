"""Qwen3 dense decoders (``model_type`` "qwen3", Hugging Face
``Qwen3ForCausalLM``): the program's ``dense`` family with per-head q/k
norms.  Binds the dense helpers of the benchmark: the work counts of
``bench/work.py``, the weights of ``bench/weights.py`` and the float32
reference of ``bench/reference.py``."""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from bench import reference
from bench.weights import draw as draw_dense
from bench.work import Sizes


def model_config(config: Dict):
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=config["name"], family="dense",
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"], head_dim=config["head_dim"],
        qk_norm=True, rope_theta=float(config["rope_theta"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        eos_id=int(config["eos_token_id"]), dtype=config["torch_dtype"])


def sizes(config: Dict) -> Sizes:
    return Sizes.of(config)


def decode_work(run) -> Tuple[float, float]:
    """Sum over the window's decode steps of each step's required work:
    every weight read once, the rows' valid KV read once."""
    flops = nbytes = 0.0
    for s in run.window_steps:
        if s.decoded:
            flops += run.sizes.decode_flops(s.decoded, s.context)
            nbytes += run.sizes.decode_bytes(s.decoded, s.context)
    return flops, nbytes


def draw(config: Dict, seed: int, device) -> dict:
    return draw_dense(Sizes.of(config), seed, device,
                      dtype=config["torch_dtype"])


def reference_logits(weights: Dict, config: Dict, tokens: Sequence[int],
                     start: int, n: int, control: Optional[str] = None
                     ) -> np.ndarray:
    return reference.logits(weights, Sizes.of(config),
                            float(config["rope_theta"]), tokens, start, n,
                            control=control)
