"""One module per architecture, ``bench/arch/<arch>.py``, found by the
``arch`` of an LLM in a configuration file (``bench/configs/<name>.json``).
``c`` below is that LLM's ``config`` object, the published keys as run.

A module holds the benchmark's own plain reference of the architecture,
its seeded weights and the work of each serving step, and provides:

* ``dims(c) -> dict``: sizes by letter; ``dims(c)["V"]`` is the
  vocabulary the traffic draws token ids from.
* ``program_config(c) -> dict``: every keyword of the serving system's
  ``repro.config.ModelConfig`` but ``name`` and ``source`` (taken from the
  LLM's entry), sub-configurations (``ssm``, ``moe``) built here.
* ``init_weights(key, c, dtype)``: the weight tree the serving engine
  takes, made from a PRNG key in ``dtype``, traceable under ``jax.jit``.
* ``forward(w, c, tokens, quantize=None)``: the float32 reference,
  logits ``[batch, positions, V]`` for int32 ``tokens [batch,
  positions]``; ``quantize``, where given, rounds every weight matrix
  (the control).  It imports nothing of the system under test.
* ``weight_bytes(c, dtype_bytes=2) -> int``: weight bytes one step reads.
* ``decode_work(c, lens, dtype_bytes=2)``: (FLOPs, HBM bytes) of one
  decode step over rows of context lengths ``lens``.
* ``prefill_work(c, rows, dtype_bytes=2)``: (FLOPs, HBM bytes) of one
  prefill chunk step over ``(offset, tokens)`` rows.
"""
