"""Architectures the benchmark can serve, one module each, found by name.

A configuration file names its architecture by Hugging Face's
``model_type``; the harness loads ``bench/arch/<model_type>.py`` by path
(``harness.architecture``), as it loads a metric's reader, so a new
architecture is that file and a configuration, and no other file
changes.  No list of architectures is kept anywhere: the files in this
directory are the list.

An architecture module provides:

* ``model_config(config)``: the program's ``repro.models.config.
  ModelConfig`` for the configuration file ``config`` (a dict of Hugging
  Face key names, the engine settings and the check's limit);
* ``sizes(config)``: an object with ``layers``, ``vocab`` (real
  vocabulary, the prompt token ids' range), ``padded_vocab``,
  ``param_bytes()``, ``kv_bytes_per_token`` and ``prefill_flops(lens)``
  (FLOPs of prefills of these real prompt lengths); the harness keeps it
  as ``Node.sizes`` and ``Run.sizes``;
* ``decode_work(run)``: ``(flops, bytes)`` that the decode steps of the
  measured window (``run.window_steps``) require; it takes the whole
  ``Run``, so work that depends on a program counter reads
  ``run.stats``;
* ``draw(config, seed, device)``: random weights from the seed, in the
  engine's layout and the configuration's ``torch_dtype``, made on
  ``device``;
* ``reference_logits(weights, config, tokens, start, n, control=None)``:
  the plain float32 reference's logits over the real vocabulary,
  ``(n, vocab)``, at positions ``start .. start+n-1`` of ``tokens``, from
  those weights; with ``control`` ("int8", "fp8") the same computation a
  precision step below the served one (the output check's control).
"""
