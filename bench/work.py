"""The work a step of a dense decoder requires, from the configuration's
sizes alone: operations and bytes that any implementation must spend,
whatever it pads or copies.

* Prompt tokens are the real ones, not the bucket padding; rows are the
  resident ones, not the engine's static batch.
* A decode step reads every weight once, reads each resident row's valid
  KV once and writes the new token's KV once.
* Causal prefill attention counts half of the square.
* The output head counts the real vocabulary, at the positions whose
  logits are used (the last prompt position, each decoded row).

A kernel that stops over-reading raises the share of the peak these give;
no implementation can push it past 100%.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterable


@dataclass(frozen=True)
class Sizes:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    padded_vocab: int
    tied: bool
    dtype_bytes: int = 2

    @classmethod
    def of(cls, config: Dict) -> "Sizes":
        """From a configuration file (Hugging Face key names)."""
        return cls(layers=config["num_hidden_layers"],
                   d_model=config["hidden_size"],
                   heads=config["num_attention_heads"],
                   kv_heads=config["num_key_value_heads"],
                   head_dim=config["head_dim"],
                   d_ff=config["intermediate_size"],
                   vocab=config["vocab_size"],
                   padded_vocab=config["padded_vocab_size"],
                   tied=bool(config["tie_word_embeddings"]),
                   dtype_bytes={"bfloat16": 2, "float32": 4}[
                       config["torch_dtype"]])

    # ------------------------------------------------------- parameters
    @property
    def layer_matmul_params(self) -> int:
        d, q, kv = self.d_model, self.heads * self.head_dim, \
            self.kv_heads * self.head_dim
        return d * (q + 2 * kv) + q * d + 3 * d * self.d_ff

    @property
    def layer_params(self) -> int:
        # + ln1, ln2 (d each) and the per-head q/k norms (head_dim each)
        return self.layer_matmul_params + 2 * self.d_model \
            + 2 * self.head_dim

    def stored_params(self) -> int:
        """Parameters as the served model stores them: the embedding and
        output head at the padded vocabulary."""
        emb = self.padded_vocab * self.d_model * (1 if self.tied else 2)
        return emb + self.d_model + self.layers * self.layer_params

    def param_bytes(self) -> int:
        return self.stored_params() * self.dtype_bytes

    @property
    def kv_bytes_per_token(self) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim \
            * self.dtype_bytes

    # ------------------------------------------------------- operations
    @property
    def head_flops(self) -> int:
        return 2 * self.d_model * self.vocab

    def _attn_flops(self, pairs: float) -> float:
        """QK^T and PV over ``pairs`` (query, key) pairs, all layers."""
        return 4.0 * self.layers * self.heads * self.head_dim * pairs

    def prefill_flops(self, prompt_lens: Iterable[int]) -> float:
        """Prefill of prompts of these real lengths, each producing the
        logits of its last position."""
        total = 0.0
        for p in prompt_lens:
            total += 2.0 * self.layers * self.layer_matmul_params * p
            total += self._attn_flops(p * (p + 1) / 2.0)
            total += self.head_flops
        return total

    def decode_flops(self, rows: int, context: int) -> float:
        """One decode step of ``rows`` resident rows whose valid KV before
        the new token sums to ``context`` positions."""
        return (rows * (2.0 * self.layers * self.layer_matmul_params
                        + self.head_flops)
                + self._attn_flops(context + rows))

    # ------------------------------------------------------------ bytes
    def decode_bytes(self, rows: int, context: int) -> float:
        """One decode step: every weight read once (the embedding only at
        the rows' tokens, the head at the real vocabulary), the valid KV
        read once and the new KV written once."""
        weights = (self.layers * self.layer_params + self.d_model
                   + self.d_model * self.vocab
                   + rows * self.d_model) * self.dtype_bytes
        return weights + (context + rows) * self.kv_bytes_per_token
