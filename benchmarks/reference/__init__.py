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

**What a reference with a top-k router defines besides** (``cohere2_moe``;
``decoder`` has no router and none of this). A top-k choice is a
discontinuous function: where the k-th and the (k+1)-th score lie closer
than the error of a bf16 pipeline, both choices are the model's answer,
as both sides of a near-tied argmax are (which is why the comparison
teacher-forces the engine's TOKENS). So such a reference is set-valued at
its OWN near-ties, and only there; it is never shown the program's
routing.

- ``near_tie_alternatives(spec, state, ids, position) -> [{"forced":
  ..., "swaps": [{"layer", "out", "in", "gap"}]}, ...]``: the alternate
  routings of ONE position of ``ids``: ONE swap each across the top-k
  boundary, in any layer, between a chosen and an unchosen expert whose
  router LOGITS (before the activation) lie less than the module's
  ``ROUTING_TIE_GAP`` apart and of which at least one is held here;
  nearest tie first, a bounded few (every one offered is one more chance
  that a faulty token is excused). The constant is MEASURED
  (``tools/routing_diff.py`` on the chip; PERF.md section 2 has the
  readings) and stated with the share of positions and of answered tokens
  that have an alternative at all.
- ``forward_logprobs(..., forced=alternative["forced"])``: the same pass
  with the rows ``{(layer, position): experts}`` routed as told.

``lib.serve.compare_logprobs`` looks for ``near_tie_alternatives`` and for
nothing else: a module without it is compared on its one routing-free
pass, as before.

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
