"""Speculative decoding: a small draft model proposes ``draft_k`` tokens,
the target model verifies them in ONE chunked forward, and the longest
target-greedy-consistent prefix (plus the target's bonus token) is accepted
— per round the target runs once for up to ``draft_k + 1`` emitted tokens
instead of once per token.

Role anchor: the speculative/draft-model decode path of the reference
platform's LLM serving stack (the same serving tier as
paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu; the
reference ecosystem ships it in its llm inference recipes). TPU-native
design: both the draft proposal loop (a ``lax.scan`` of greedy steps) and
the chunked verify are single jitted computations with donated KV buffers;
rollback after a rejected suffix is just resetting the cache's scalar
``pos`` — the dense serving cache (generation.cached_attention) masks
columns ``> pos``, so stale entries beyond the accepted prefix are inert
and get overwritten by later writes.

Greedy-exactness contract: the emitted sequence is IDENTICAL to
``target.generate(..., do_sample=False)`` — speculation changes latency,
never output (the test asserts token equality).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .autograd import tape as _tape
from .generation import (_get_prefill_step, _memoized_step, _split_caches,
                         _unwrap_caches)
from .nn.layer import functional_weights as _functional_weights
from .tensor_class import unwrap, wrap


def _spec_accept_hist(engine: str):
    """The shared acceptance histogram (serving_spec_accepted_tokens):
    every speculative path — solo, MTP self-draft, and the serving
    engine — publishes accepted-draft counts through the SAME registry
    family, so acceptance health reads off one /metrics series instead
    of caller-only stats dicts."""
    from .observability import catalog as _metrics

    return _metrics.SERVING_SPEC_ACCEPTED.labels(engine=engine)


def _ngram_next(hist: np.ndarray, max_ngram: int):
    """One prompt-lookup step: the token that followed the MOST RECENT
    earlier occurrence of ``hist``'s trailing n-gram (n = ``max_ngram``
    down to 1), or None when nothing repeats."""
    L = int(hist.size)
    if L < 2:
        return None
    for n in range(min(int(max_ngram), L - 1), 0, -1):
        pat = hist[L - n:]
        # windows starting before the trailing n-gram itself: a match at
        # start s < L - n guarantees a continuation token exists
        view = np.lib.stride_tricks.sliding_window_view(hist, n)
        hits = np.nonzero((view[: L - n] == pat).all(axis=1))[0]
        if hits.size:
            return int(hist[int(hits[-1]) + n])  # most recent wins
    return None


def ngram_propose(history, k: int, max_ngram: int = 3) -> np.ndarray:
    """Prompt-lookup draft proposal (n-gram drafter — no second model):
    ITERATED single-token lookups — each proposed token is appended to a
    working copy of the history before the next lookup, so the proposal
    is the drafter's own autoregressive continuation (a periodic stream
    extends past the raw history's end instead of truncating at it).
    ``c[0]`` predicts the NEXT position, ``c[j]`` the one j after it.
    Returns an int32 array of length <= k (empty when the history is too
    short or nothing repeats — the caller pads; padding can only be
    "accepted" when it coincidentally equals the target's greedy choice,
    so junk proposals never change output, only acceptance rate).

    Pure host work on the request's token history — the drafter runs
    between engine dispatches and never touches the device."""
    work = np.asarray(history).reshape(-1)
    out = []
    for _ in range(int(k)):
        nxt = _ngram_next(work, max_ngram)
        if nxt is None:
            break
        out.append(nxt)
        work = np.append(work, nxt)
    return np.asarray(out, np.int32)


class _ProposeStep:
    """Draft proposal: feed ``seed`` (1 or 2 catch-up tokens), then scan
    ``k-1`` greedy single-token steps — one jitted dispatch for all ``k``
    proposals, donated draft KV buffers."""

    def __init__(self, model, max_len, k, seed_len):
        self._model = model

        def spec_propose(state, seed, bufs, aux):
            caches = [{**b, **a} for b, a in zip(bufs, aux)]
            with _functional_weights(model, state), _tape.no_grad():
                hidden, caches = model.llama.forward_cached(
                    wrap(seed), caches, rope_len=max_len)
                h_last = unwrap(hidden)[:, -1:]
                first = jnp.argmax(
                    unwrap(model.lm_head_logits(wrap(h_last)))[:, -1, :],
                    axis=-1).astype(jnp.int32)

                def body(carry, _):
                    tok, caches = carry
                    hidden, caches = model.llama.forward_cached(
                        wrap(tok[:, None]), caches, rope_len=max_len)
                    nxt = jnp.argmax(
                        unwrap(model.lm_head_logits(hidden))[:, -1, :],
                        axis=-1).astype(jnp.int32)
                    return (nxt, caches), nxt

                if k > 1:
                    (_, caches), rest = jax.lax.scan(
                        body, (first, caches), None, length=k - 1)
                    toks = jnp.concatenate([first[None], rest], axis=0)
                else:
                    toks = first[None]
            nb, na = _split_caches(_unwrap_caches(caches))
            return toks.T, nb, na  # [B, k]

        self._jitted = jax.jit(spec_propose, donate_argnums=(2,))
        self._state = dict(model.functional_state())

    def __call__(self, seed, caches):
        bufs, aux = _split_caches(caches)
        toks, nb, na = self._jitted(self._state, seed, bufs, aux)
        return toks, [{**b, **a} for b, a in zip(nb, na)]


class _VerifyStep:
    """Target verify: one chunked forward over [last, d_1..d_k]; returns
    the target's greedy token at every chunk position."""

    def __init__(self, model, max_len, chunk_len):
        self._model = model

        def spec_verify_solo(state, chunk, bufs, aux):
            caches = [{**b, **a} for b, a in zip(bufs, aux)]
            with _functional_weights(model, state), _tape.no_grad():
                hidden, caches = model.llama.forward_cached(
                    wrap(chunk), caches, rope_len=max_len)
                logits = unwrap(model.lm_head_logits(hidden))
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            nb, na = _split_caches(_unwrap_caches(caches))
            return greedy, nb, na  # [B, chunk_len]

        self._jitted = jax.jit(spec_verify_solo, donate_argnums=(2,))
        self._state = dict(model.functional_state())

    def __call__(self, chunk, caches):
        bufs, aux = _split_caches(caches)
        greedy, nb, na = self._jitted(self._state, chunk, bufs, aux)
        return greedy, [{**b, **a} for b, a in zip(nb, na)]


def _set_pos(caches, pos):
    for c in caches:
        c["pos"] = jnp.asarray(pos, jnp.int32)
    return caches


def _prefill(model, ids, max_len):
    """Whole-prompt prefill (generation's one-shot jitted step); returns
    (greedy_next, caches)."""
    step = _get_prefill_step(model, max_len, ragged=False)
    lengths = jnp.full((ids.shape[0],), ids.shape[1], jnp.int32)
    last, caches = step(ids, lengths, None)
    return jnp.argmax(last, axis=-1).astype(jnp.int32), caches


def _normalize_request(input_ids):
    """Shared batch-1 request normalization: returns (ids [1,P] np,
    out_dtype); raises on batched input (the dense cache keeps one scalar
    write position)."""
    ids = np.asarray(unwrap(input_ids) if hasattr(input_ids, "shape")
                     else input_ids)
    out_dtype = ids.dtype
    if ids.ndim == 1:
        ids = ids[None]
    if ids.shape[0] != 1:
        raise ValueError(
            "speculative decoding is per-request (batch 1); run rows "
            "separately or use model.generate for batched decode")
    return ids, out_dtype


def _finish(emitted, max_new_tokens, eos_token_id, out_dtype):
    """Shared emit epilogue: truncate to the budget, cut at eos, wrap in
    the request dtype."""
    emitted = emitted[:max_new_tokens]
    if eos_token_id is not None and eos_token_id in emitted:
        emitted = emitted[: emitted.index(eos_token_id) + 1]
    return wrap(jnp.asarray(np.asarray(emitted, out_dtype)[None]))


def speculative_generate(target, draft, input_ids, max_new_tokens=20,
                         draft_k=4, eos_token_id=None, return_stats=False):
    """Greedy speculative decode of ``input_ids`` [1, P] → [1, P + new].

    Batch size 1 (per-request serving): the dense cache keeps ONE scalar
    write position, and rows accepting different prefix lengths would need
    per-row rollback. Output is exactly ``target.generate`` greedy.

    ``return_stats=True`` returns ``(out, stats)`` with the same contract
    as :func:`mtp_speculative_generate`: ``rounds`` (verify dispatches),
    ``hits`` (draft tokens the target accepted), ``acceptance`` (hits /
    (rounds * draft_k) — the fraction of proposed tokens that landed).
    Acceptance is ALSO published per round through the metrics registry
    (``serving_spec_accepted_tokens``, engine="solo") whether or not the
    caller asks for stats.
    """
    ids, out_dtype = _normalize_request(input_ids)
    B, P = ids.shape
    k = int(draft_k)
    if k < 1:
        raise ValueError(f"draft_k must be >= 1, got {draft_k}")
    max_len = P + max_new_tokens + k + 2
    for name, m in (("target", target), ("draft", draft)):
        limit = m.config.max_position_embeddings
        if max_len > limit:
            raise ValueError(
                f"speculative_generate: prompt+new(+{k + 2} speculation "
                f"slack) = {max_len} exceeds the {name} model's "
                f"max_position_embeddings {limit}")
    ids = jnp.asarray(ids, jnp.int32)

    t0, tgt_caches = _prefill(target, ids, max_len)
    _, dft_caches = _prefill(draft, ids, max_len)
    tgt_pos, dft_pos = P, P

    emitted = [int(t0[0])]  # pdlint: disable=host-sync -- the prefill's one deliberate first-token fetch
    last = emitted[0]
    catchup = []  # accepted tokens not yet written to the draft cache
    rounds = hits = 0       # draft-acceptance observability
    accept_hist = _spec_accept_hist("solo")

    def propose_step(seed_len):
        return _memoized_step(
            draft, "_spec_propose_steps", (max_len, k, seed_len),
            lambda: _ProposeStep(draft, max_len, k, seed_len), maxsize=8)

    verify_step = _memoized_step(
        target, "_spec_verify_steps", (max_len, k + 1),
        lambda: _VerifyStep(target, max_len, k + 1), maxsize=8)

    while len(emitted) < max_new_tokens and \
            (eos_token_id is None or emitted[-1] != eos_token_id):
        seed = jnp.asarray([catchup + [last]], jnp.int32)   # [1, 1|2]
        dft_caches = _set_pos(dft_caches, dft_pos)
        proposals, dft_caches = propose_step(seed.shape[1])(seed, dft_caches)
        props = [int(x) for x in np.asarray(proposals[0])]   # d_1..d_k  # pdlint: disable=host-sync -- the round's deliberate draft fetch (host builds the verify chunk from it)

        chunk = jnp.asarray([[last] + props], jnp.int32)     # [1, k+1]
        tgt_caches = _set_pos(tgt_caches, tgt_pos)
        greedy, tgt_caches = verify_step(chunk, tgt_caches)
        g = [int(x) for x in np.asarray(greedy[0])]          # g_0..g_k  # pdlint: disable=host-sync -- the round's deliberate verify fetch (acceptance is host control flow)

        m = 0
        while m < k and props[m] == g[m]:
            m += 1
        accepted = props[:m] + [g[m]]                        # ≤ k+1 tokens
        rounds += 1
        hits += m
        accept_hist.observe(m)

        # context now ends ...last, d_1..d_m, g_m; g_m is the new `last`
        ctx_len_old = tgt_pos + 1        # context length BEFORE this round
        tgt_pos = ctx_len_old + m        # target holds ctx + d_1..d_m
        if m == k:                       # draft never wrote d_k's entry
            dft_pos = ctx_len_old + (k - 1)
            catchup = [props[-1]]
        else:                            # d_1..d_m all in the draft cache
            dft_pos = ctx_len_old + m
            catchup = []
        last = accepted[-1]
        emitted.extend(accepted)
        if eos_token_id is not None and eos_token_id in accepted:
            break

    # same convention as model.generate: only the NEW tokens, input dtype
    out = _finish(emitted, max_new_tokens, eos_token_id, out_dtype)
    if return_stats:
        return out, {"rounds": rounds, "hits": hits,
                     "acceptance": (hits / (rounds * k)) if rounds else 0.0}
    return out


class _MTPRoundStep:
    """One MTP self-speculative round as ONE jitted dispatch (the
    mtp_speculative_generate docstring's promised follow-up off the eager
    host loop): extend the MTP latent stream with the previous round's
    completed (hidden, token) pairs and draft one token, then run the
    2-token cached verify [pending, draft] on the main model — draft,
    verify, and both cache updates in a single device program with the
    big cache buffers donated. Keyed on ``n_pairs`` (1 after a miss, 2
    after a hit — the only two carry shapes), memoized per model via
    _memoized_step exactly like the propose/verify steps above."""

    def __init__(self, model, max_len, n_pairs):
        self._model = model
        mtp = model.mtp_layers[0]

        def mtp_round(state, h_tail, toks, bufs, aux, mbufs, maux):
            caches = [{**b, **a} for b, a in zip(bufs, aux)]
            mtp_cache = {**mbufs[0], **maux[0]}
            with _functional_weights(model, state), _tape.no_grad():
                cos, sin = model.llama._rope(max_len)
                emb = model.llama.embed_tokens(wrap(toks)).astype(
                    model.config.dtype)
                x = mtp.fuse(wrap(h_tail), emb)
                h_m, mtp_cache = mtp.block(x, cos, sin, kv_cache=mtp_cache)
                draft = jnp.argmax(unwrap(model.lm_head_logits(
                    mtp.norm(h_m[:, -1:])))[0, 0]).astype(jnp.int32)
                verify = jnp.stack([toks[0, -1], draft])[None, :]  # [1, 2]
                normed2, pre2, caches = model.llama.forward_cached(
                    wrap(verify), caches, rope_len=max_len,
                    return_prenorm=True)
                logits2 = unwrap(model.lm_head_logits(normed2))
            g0 = jnp.argmax(logits2[0, 0]).astype(jnp.int32)
            g1 = jnp.argmax(logits2[0, 1]).astype(jnp.int32)
            nb, na = _split_caches(_unwrap_caches(caches))
            mb, ma = _split_caches(_unwrap_caches([mtp_cache]))
            return jnp.stack([g0, g1, draft]), unwrap(pre2), nb, na, mb, ma

        self._jitted = jax.jit(mtp_round, donate_argnums=(3, 5))
        self._state = dict(model.functional_state())

    def __call__(self, h_tail, toks, caches, mtp_caches):
        bufs, aux = _split_caches(_unwrap_caches(caches))
        mb, ma = _split_caches(_unwrap_caches(mtp_caches))
        g, pre2, nb, na, mb2, ma2 = self._jitted(
            self._state, h_tail, toks, bufs, aux, mb, ma)
        return (g, pre2, [{**b, **a} for b, a in zip(nb, na)],
                [{**b, **a} for b, a in zip(mb2, ma2)])


def mtp_speculative_generate(model, input_ids, max_new_tokens=20,
                             eos_token_id=None, return_stats=False):
    """Self-speculative greedy decode for DeepSeek models trained with
    multi-token prediction (``num_nextn_predict_layers >= 1``): the FIRST
    MTP depth drafts one token per round from the main model's PRE-norm
    hidden stream (the MTP block keeps its own latent cache over the
    shifted sequence, exactly the pairing it was trained on), and a
    2-token cached verify accepts or corrects (arXiv:2412.19437 §2.2
    inference usage — the "free" extra token per forward).

    Output is EXACTLY ``model.generate`` greedy — the draft only changes
    how many tokens each main-model forward retires. Batch 1 (the dense
    cache keeps one write position; see speculative_generate). Each round
    is ONE jitted dispatch (:class:`_MTPRoundStep`, memoized via
    _memoized_step and keyed on the 1- or 2-pair carry shape); rollback
    after a miss is a host-side cache ``pos`` reset, like
    speculative_generate's. Acceptance is published per round through the
    metrics registry (``serving_spec_accepted_tokens``, engine="mtp")."""
    from .generation import _empty_caches

    mtp_layers = getattr(model, "mtp_layers", None)
    if not mtp_layers:
        raise ValueError(
            "mtp_speculative_generate needs a model built with "
            "num_nextn_predict_layers >= 1 (the MTP draft module)")
    mtp = mtp_layers[0]
    ids, out_dtype = _normalize_request(input_ids)
    B, P = ids.shape
    max_len = P + max_new_tokens + 3
    if max_len > model.config.max_position_embeddings:
        raise ValueError(
            f"prompt+new(+3 speculation slack) = {max_len} exceeds "
            f"max_position_embeddings "
            f"{model.config.max_position_embeddings}")
    ids_j = jnp.asarray(ids, jnp.int32)
    dt = (jnp.dtype(model.config.dtype)
          if isinstance(model.config.dtype, str) else model.config.dtype)
    accept_hist = _spec_accept_hist("mtp")

    def emb(tokens_2d):
        # .astype: same compute dtype the MTP block trained on
        return model.llama.embed_tokens(
            wrap(jnp.asarray(tokens_2d, jnp.int32))).astype(
                model.config.dtype)

    with _tape.no_grad():
        cos, sin = model.llama._rope(max_len)
        # main prefill (pre-norm stream kept for the MTP pairing)
        caches = _empty_caches(model, 1, max_len)
        normed, pre, caches = model.llama.forward_cached(
            wrap(ids_j), caches, rope_len=max_len, return_prenorm=True)
        t1 = int(jnp.argmax(  # the prefill's one deliberate first-token fetch
            unwrap(model.lm_head_logits(normed[:, -1:]))[0, 0]))

        # MTP stream cache: seed with pairs (h_i, t_{i+1}) for the prompt
        mtp_cache = dict(model.llama.empty_cache_layer(1, max_len, dt),
                         pos=0, prefill=True)
        if P > 1:
            x = mtp.fuse(pre[:, : P - 1], emb(ids[:, 1:]))
            _, mtp_cache = mtp.block(x, cos, sin, kv_cache=mtp_cache)
        # rounds are jitted from here on: the static "prefill" marker
        # must not enter the traced aux (bool(tracer) raises), and
        # positions are tracked host-side and stamped before each call
        mtp_cache.pop("prefill", None)
        pos_main, pos_mtp = P, max(P - 1, 0)

        emitted = [t1]
        rounds = hits = 0          # draft-acceptance observability
        h_tail = unwrap(pre)[:, -1:]   # pre-norm hidden(s) pairing toks
        toks = [t1]                # tokens pairing h_tail rows
        while len(emitted) < max_new_tokens and (
                eos_token_id is None or emitted[-1] != eos_token_id):
            n = len(toks)
            step = _memoized_step(
                model, "_mtp_round_steps", (max_len, n),
                lambda: _MTPRoundStep(model, max_len, n), maxsize=8)
            caches = _set_pos(caches, pos_main)
            mtp_cache["pos"] = jnp.asarray(pos_mtp, jnp.int32)
            g_arr, pre2, caches, mcs = step(
                h_tail, jnp.asarray([toks], jnp.int32), caches,
                [mtp_cache])
            mtp_cache = mcs[0]
            g = np.asarray(g_arr)  # pdlint: disable=host-sync -- the round's ONE deliberate fetch: [g0, g1, draft] drive host acceptance control flow
            g0, g1, draft = int(g[0]), int(g[1]), int(g[2])
            rounds += 1
            pos_mtp += n           # the MTP stream grew by the n pairs
            if draft == g0:        # draft hit: two tokens from one forward
                hits += 1
                emitted.extend([draft, g1])
                pos_main += 2
                h_tail, toks = pre2, [draft, g1]
            else:                  # miss: the draft's cache entry is
                emitted.append(g0)  # stale — the host pos rewind parks it
                pos_main += 1
                h_tail, toks = pre2[:, :1], [g0]
            accept_hist.observe(1 if draft == g0 else 0)
            if eos_token_id is not None and eos_token_id in emitted[-2:]:
                break              # eos inside a hit pair stops the loop

    out = _finish(emitted, max_new_tokens, eos_token_id, out_dtype)
    if return_stats:
        # acceptance rate is THE speculative health metric: each hit
        # retired 2 tokens from one main forward
        return out, {"rounds": rounds, "hits": hits,
                     "acceptance": (hits / rounds) if rounds else 0.0}
    return out
