"""Plain references, one module per family of architectures. A
configuration's file names its own: ``"reference": {"module": "<stem>",
...}`` is the file ``benchmarks/reference/<stem>.py``, loaded by
``lib.common.Cell.reference()``; the rest of that group is the module's
to read. No other file of the benchmark imports a reference by name, so a
later PR brings a new architecture's reference as one added file.

**What a reference module is.** The architecture's forward pass in
straightforward ``jax.numpy`` and float32 (``Precision.HIGHEST`` on every
contraction): no kernels, no cache, no batching, nothing imported from the
program and nothing the program computed but its weights, which arrive as
``lib.build.plain_state(model)`` (a dict of plain ``jax`` arrays by the
program's parameter names, in the type they are served in) and are widened
inside, layer by layer, so that it fits beside the served model.

**What it must define.**

- ``Spec.from_config(cfg) -> spec``: what the reference needs of the
  configuration's file (the rehearsal's overrides already laid over it);
  hashable; raises ``ValueError`` on a file it does not cover.
- ``forward_logprobs(spec, state, ids, last) -> float32 [last, vocab]``:
  log-softmax over the vocabulary at the last ``last`` positions of ONE
  sequence of token ids. ``lib.serve.compare_logprobs`` teacher-forces it
  on the engine's own tokens.
- ``next_token_loss(spec, state, ids) -> float``: mean cross entropy of
  ``ids[1:]`` given ``ids[:-1]``; only where the configuration has a
  ``train_job`` cell.

**What it may define**, for the per-layer readers of its configuration's
cells. A reader asks through ``lib.common.reference_function``; where the
cell's module lacks the function the reader returns None and notes which:
it never falls back to another module's arithmetic.

- ``matmul_params(spec)``, ``train_flops_per_token(spec, seq)``
  (``train_mfu``); ``serve_flops_per_token(spec, context, sampled_share)``
  (``serve_mfu``): the operations forward (and backward) REQUIRE per
  token; nothing recomputed and nothing dropped counts.
- ``flash_train_cost(spec, batch, seq)``, ``paged_decode_cost(spec,
  context_tokens, rows)`` -> ``{"flops", "bytes"}`` of what a kernel must
  do (``flash_attention_roofline``, ``paged_attention_roofline``);
  ``_costs.roofline_seconds`` turns a cost into the chip's least time.

``decoder.py`` covers pre- and post-norm blocks of grouped-query attention
and dense SwiGLU (Mistral, OLMo 2). ``benchmarks/tests/test_extend.py``
adds a second module beside it without touching a file.
"""
