"""T5 encoder-decoder family (relative position bias, cross-attention).

Role parity: the encoder-decoder class of the reference ecosystem's model
zoo (PaddleNLP t5/bart modeling). Architecture per the T5 paper / HF
implementation: shared token embedding, T5LayerNorm (= RMSNorm), bucketed
relative position bias computed by the FIRST self-attention layer of each
stack and shared down the stack, cross-attention without position bias,
relu (v1.0) or gated-gelu (v1.1) FFN, tied lm head scaled by
d_model**-0.5 when tied.

TPU-native design: the encoder runs ONCE; decode carries (a) per-layer
self-attention KV buffers written in place at a scalar position — the
same static-shape cache discipline as the decoder-only families — and
(b) per-layer cross-attention K/V projected ONCE from the encoder output.
The whole decode step (embed → all blocks → logits) is one jitted
dispatch.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..nn.layer import Layer
from ..nn.initializer import Normal
from ..ops.registry import apply
from ..ops.pallas import fused_norm
from ..tensor_class import Tensor, unwrap, wrap

# sentinel: "caller did not pass eos_token_id" — maps to the config
# default; an explicit None DISABLES eos (matching the decoder-only
# families' semantics)
_UNSET = object()

@dataclasses.dataclass
class T5Config:
    vocab_size: int = 32128
    d_model: int = 512
    d_kv: int = 64
    d_ff: int = 2048
    num_layers: int = 6                  # encoder layers
    num_decoder_layers: Optional[int] = None
    num_heads: int = 8
    relative_attention_num_buckets: int = 32
    relative_attention_max_distance: int = 128
    layer_norm_epsilon: float = 1e-6
    feed_forward_proj: str = "relu"      # or "gated-gelu" (v1.1)
    tie_word_embeddings: bool = True
    initializer_factor: float = 1.0
    decoder_start_token_id: int = 0
    eos_token_id: int = 1
    pad_token_id: int = 0
    dtype: str = "float32"

    def __post_init__(self):
        if self.num_decoder_layers is None:
            self.num_decoder_layers = self.num_layers
        if self.feed_forward_proj not in ("relu", "gated-gelu"):
            raise ValueError(
                f"feed_forward_proj must be 'relu' or 'gated-gelu', got "
                f"{self.feed_forward_proj!r}")

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, d_model=64, d_kv=16, d_ff=128,
                    num_layers=2, num_heads=4, dtype="float32")
        base.update(kw)
        return T5Config(**base)


def _rel_position_bucket(rel, bidirectional, num_buckets, max_distance):
    """HF T5 bucketing: exact small distances, log-spaced large ones."""
    ret = jnp.zeros_like(rel)
    if bidirectional:
        num_buckets //= 2
        ret = ret + (rel > 0).astype(jnp.int32) * num_buckets
        n = jnp.abs(rel)
    else:
        n = jnp.maximum(-rel, 0)
    max_exact = num_buckets // 2
    is_small = n < max_exact
    large = max_exact + (
        jnp.log(n.astype(jnp.float32) / max_exact + 1e-6)
        / np.log(max_distance / max_exact) * (num_buckets - max_exact)
    ).astype(jnp.int32)
    large = jnp.minimum(large, num_buckets - 1)
    return ret + jnp.where(is_small, n, large)


class T5LayerNorm(Layer):
    """RMS norm, no bias, no mean subtraction (the T5 norm)."""

    def __init__(self, config: T5Config):
        super().__init__(dtype=config.dtype)
        from ..nn.initializer import Constant

        self.weight = self.create_parameter(
            [config.d_model], default_initializer=Constant(1.0),
            dtype=config.dtype)
        self._eps = config.layer_norm_epsilon

    def forward(self, x):
        eps = self._eps
        return apply("rms_norm", lambda a, w: fused_norm.rms_norm(a, w, eps),
                     x, self.weight)


class T5Attention(Layer):
    """Multi-head attention, no projection biases, NO 1/sqrt(d) scaling
    (T5 folds the scale into the init). Self- or cross-; the first
    self-attention of a stack owns the relative position bias table."""

    def __init__(self, config: T5Config, has_relative_bias=False,
                 bidirectional=True):
        super().__init__(dtype=config.dtype)
        from ..framework.dtype import dtype_guard

        self.config = config
        self.n_heads = config.num_heads
        self.d_kv = config.d_kv
        inner = config.num_heads * config.d_kv
        with dtype_guard(config.dtype):
            self.q = nn.Linear(config.d_model, inner, bias_attr=False)
            self.k = nn.Linear(config.d_model, inner, bias_attr=False)
            self.v = nn.Linear(config.d_model, inner, bias_attr=False)
            self.o = nn.Linear(inner, config.d_model, bias_attr=False)
        self.has_relative_bias = has_relative_bias
        self.bidirectional = bidirectional
        if has_relative_bias:
            with dtype_guard(config.dtype):
                self.relative_attention_bias = nn.Embedding(
                    config.relative_attention_num_buckets, config.num_heads)

    def compute_bias(self, q_len, kv_len, q_offset=0):
        """[1, heads, q_len, kv_len] additive bias."""
        ctx = jnp.arange(q_len)[:, None] + q_offset
        mem = jnp.arange(kv_len)[None, :]
        buckets = _rel_position_bucket(
            mem - ctx, self.bidirectional,
            self.config.relative_attention_num_buckets,
            self.config.relative_attention_max_distance)
        table = unwrap(self.relative_attention_bias.weight)
        bias = jnp.take(table, buckets, axis=0)       # [q, kv, heads]
        return jnp.moveaxis(bias, 2, 0)[None]         # [1, h, q, kv]

    def compute_bias_rows(self, lengths, kv_len):
        """PER-ROW bias for ragged single-token decode (the seq2seq
        serving engine): [B, heads, 1, kv_len] with row r's query at
        position lengths[r] — the same bucketing/table as compute_bias,
        kept on the layer that owns the table."""
        mem = jnp.arange(kv_len)[None, :]
        buckets = _rel_position_bucket(
            mem - lengths[:, None], self.bidirectional,
            self.config.relative_attention_num_buckets,
            self.config.relative_attention_max_distance)   # [B, kv]
        table = unwrap(self.relative_attention_bias.weight)
        bias = jnp.take(table, buckets, axis=0)            # [B, kv, h]
        return jnp.moveaxis(bias, 2, 1)[:, :, None, :]     # [B, h, 1, kv]

    def _split(self, t, b):
        return t.reshape([b, -1, self.n_heads, self.d_kv])

    def forward(self, hidden, kv_hidden=None, bias=None, mask=None,
                kv_cache=None):
        """bias: [1, h, q, kv] additive (position bias [+ causal/pad]);
        kv_hidden: encoder output for cross-attention; kv_cache: dict with
        'k'/'v' [B, max_len, h, d] + scalar 'pos' for cached self-attn, or
        precomputed {'k': K, 'v': V} (no 'pos': static) for cross-attn."""
        b = hidden.shape[0]
        q = self._split(self.q(hidden), b)

        def attend(qh, kh, vh, add_bias):
            scores = jnp.einsum("bqhd,bkhd->bhqk",
                                unwrap(qh).astype(jnp.float32),
                                unwrap(kh).astype(jnp.float32))
            if add_bias is not None:
                scores = scores + add_bias.astype(jnp.float32)
            probs = jax.nn.softmax(scores, axis=-1)
            out = jnp.einsum("bhqk,bkhd->bqhd", probs,
                             unwrap(vh).astype(jnp.float32))
            return out.astype(unwrap(qh).dtype)

        if (isinstance(kv_cache, dict) and "pos" not in kv_cache
                and "lengths" not in kv_cache):
            # cached cross-attention: K/V projected once from the encoder;
            # the encoder pad mask rides the cache (pad columns must stay
            # invisible at every decode step, not just inside the encoder)
            add = bias
            cmask = kv_cache.get("mask")
            if cmask is not None:
                m = jnp.where(cmask[:, None, None, :], 0.0, -jnp.inf)
                add = m if add is None else add + m
            out = attend(q, kv_cache["k"], kv_cache["v"], add)
            return self.o(wrap(out.reshape(b, -1, self.n_heads * self.d_kv))), kv_cache
        if isinstance(kv_cache, dict) and "lengths" in kv_cache:
            # RAGGED single-token decode (the seq2seq serving engine):
            # row r writes at ITS length and attends columns 0..lengths[r];
            # the caller supplies the PER-ROW relative bias [B, h, 1, T]
            s = hidden.shape[1]
            if s != 1:
                raise ValueError("ragged T5 decode is single-token")
            lengths = kv_cache["lengths"]
            k_new = self._split(self.k(hidden), b)
            v_new = self._split(self.v(hidden), b)
            rows = jnp.arange(b)
            k_buf = kv_cache["k"].at[rows, lengths].set(
                unwrap(k_new)[:, 0].astype(kv_cache["k"].dtype))
            v_buf = kv_cache["v"].at[rows, lengths].set(
                unwrap(v_new)[:, 0].astype(kv_cache["v"].dtype))
            t_idx = jnp.arange(k_buf.shape[1])
            valid = t_idx[None, :] <= lengths[:, None]
            add = jnp.where(valid[:, None, None, :], 0.0, -jnp.inf)
            if bias is not None:
                add = add + bias.astype(jnp.float32)
            out = attend(q, k_buf, v_buf, add)
            new = {"k": k_buf, "v": v_buf, "lengths": lengths + 1}
            return self.o(wrap(out.reshape(b, s, self.n_heads * self.d_kv))), new
        if isinstance(kv_cache, dict):
            # cached causal self-attention at scalar position pos
            s = hidden.shape[1]
            k_new = self._split(self.k(hidden), b)
            v_new = self._split(self.v(hidden), b)
            pos = kv_cache["pos"]
            k_buf = jax.lax.dynamic_update_slice(
                kv_cache["k"], unwrap(k_new).astype(kv_cache["k"].dtype),
                (0, pos, 0, 0))
            v_buf = jax.lax.dynamic_update_slice(
                kv_cache["v"], unwrap(v_new).astype(kv_cache["v"].dtype),
                (0, pos, 0, 0))
            t_idx = jnp.arange(k_buf.shape[1])
            s_idx = jnp.arange(s)
            valid = t_idx[None, :] <= (pos + s_idx)[:, None]
            add = jnp.where(valid[None, None], 0.0, -jnp.inf)
            if bias is not None:
                add = add + bias
            out = attend(q, k_buf, v_buf, add)
            new = {"k": k_buf, "v": v_buf, "pos": pos + s}
            return self.o(wrap(out.reshape(b, s, self.n_heads * self.d_kv))), new
        src = hidden if kv_hidden is None else kv_hidden
        k = self._split(self.k(src), b)
        v = self._split(self.v(src), b)
        add = bias
        if mask is not None:  # [B, kv] validity
            m = jnp.where(mask[:, None, None, :], 0.0, -jnp.inf)
            add = m if add is None else add + m
        out = attend(q, k, v, add)
        return self.o(wrap(out.reshape(b, -1, self.n_heads * self.d_kv)))


class T5FF(Layer):
    def __init__(self, config: T5Config):
        super().__init__(dtype=config.dtype)
        from ..framework.dtype import dtype_guard

        self.gated = config.feed_forward_proj == "gated-gelu"
        with dtype_guard(config.dtype):
            if self.gated:
                self.wi_0 = nn.Linear(config.d_model, config.d_ff, bias_attr=False)
                self.wi_1 = nn.Linear(config.d_model, config.d_ff, bias_attr=False)
            else:
                self.wi = nn.Linear(config.d_model, config.d_ff, bias_attr=False)
            self.wo = nn.Linear(config.d_ff, config.d_model, bias_attr=False)

    def forward(self, x):
        if self.gated:
            act = apply("gelu_tanh",
                        lambda a: jax.nn.gelu(a, approximate=True),
                        self.wi_0(x))
            return self.wo(act * self.wi_1(x))
        return self.wo(apply("relu", jax.nn.relu, self.wi(x)))


class T5Block(Layer):
    """Pre-norm residual block: self-attn [,cross-attn], FFN."""

    def __init__(self, config: T5Config, is_decoder, has_relative_bias):
        super().__init__(dtype=config.dtype)
        self.is_decoder = is_decoder
        self.ln_self = T5LayerNorm(config)
        self.self_attn = T5Attention(config, has_relative_bias,
                                     bidirectional=not is_decoder)
        if is_decoder:
            self.ln_cross = T5LayerNorm(config)
            self.cross_attn = T5Attention(config, False)
        self.ln_ff = T5LayerNorm(config)
        self.ff = T5FF(config)

    def forward(self, hidden, bias=None, enc_hidden=None, enc_mask=None,
                self_cache=None, cross_cache=None, mask=None):
        if self_cache is not None:
            a, self_cache = self.self_attn(self.ln_self(hidden), bias=bias,
                                           kv_cache=self_cache)
        else:
            a = self.self_attn(self.ln_self(hidden), bias=bias, mask=mask)
        hidden = hidden + a
        if self.is_decoder and (enc_hidden is not None
                                or cross_cache is not None):
            if cross_cache is not None:
                c, cross_cache = self.cross_attn(self.ln_cross(hidden),
                                                 bias=None,
                                                 kv_cache=cross_cache)
            else:
                c = self.cross_attn(self.ln_cross(hidden),
                                    kv_hidden=enc_hidden, mask=enc_mask)
            hidden = hidden + c
        hidden = hidden + self.ff(self.ln_ff(hidden))
        if self_cache is not None:
            return hidden, self_cache, cross_cache
        return hidden


class T5Stack(Layer):
    def __init__(self, config: T5Config, is_decoder, shared_embed):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.is_decoder = is_decoder
        self.embed = shared_embed
        n = config.num_decoder_layers if is_decoder else config.num_layers
        self.blocks = nn.LayerList(
            [T5Block(config, is_decoder, has_relative_bias=(i == 0))
             for i in range(n)])
        self.final_norm = T5LayerNorm(config)

    def _bias(self, q_len, kv_len, q_offset=0, causal=False):
        bias = self.blocks[0].self_attn.compute_bias(q_len, kv_len, q_offset)
        if causal:
            rows = jnp.arange(q_len)[:, None] + q_offset
            cols = jnp.arange(kv_len)[None, :]
            bias = bias + jnp.where(cols <= rows, 0.0, -jnp.inf)[None, None]
        return bias

    def forward(self, ids, enc_hidden=None, enc_mask=None, mask=None):
        s = ids.shape[1]
        hidden = self.embed(ids)
        bias = self._bias(s, s, causal=self.is_decoder)
        for block in self.blocks:
            hidden = block(hidden, bias=bias, enc_hidden=enc_hidden,
                           enc_mask=enc_mask, mask=mask)
        return self.final_norm(hidden)

    def forward_cached(self, ids, self_caches, cross_caches):
        """Decoder step(s) at the caches' scalar position — or at
        per-row positions when the caches carry "lengths" (the seq2seq
        serving engine's ragged rows)."""
        s = ids.shape[1]
        hidden = self.embed(ids)
        max_len = self_caches[0]["k"].shape[1]
        if "lengths" in self_caches[0]:
            bias = self.blocks[0].self_attn.compute_bias_rows(
                self_caches[0]["lengths"], max_len)
        else:
            pos = self_caches[0]["pos"]
            bias = self._bias(s, max_len, q_offset=pos)
        new_self, new_cross = [], []
        for block, sc, cc in zip(self.blocks, self_caches, cross_caches):
            hidden, sc, cc = block(hidden, bias=bias, self_cache=sc,
                                   cross_cache=cc)
            new_self.append(sc)
            new_cross.append(cc)
        return self.final_norm(hidden), new_self, new_cross


class T5ForConditionalGeneration(Layer):
    """T5 encoder-decoder LM (HF-compatible semantics incl. the
    d_model**-0.5 logit scaling under tied embeddings)."""

    def __init__(self, config: T5Config):
        super().__init__(dtype=config.dtype)
        from ..framework.dtype import dtype_guard

        self.config = config
        with dtype_guard(config.dtype):
            self.shared = nn.Embedding(config.vocab_size, config.d_model)
        self.shared.weight._array = (
            Normal(0.0, config.initializer_factor)(
                (config.vocab_size, config.d_model), jnp.float32)
            .astype(self.shared.weight.dtype))
        self.encoder = T5Stack(config, is_decoder=False,
                               shared_embed=self.shared)
        self.decoder = T5Stack(config, is_decoder=True,
                               shared_embed=self.shared)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            with dtype_guard(config.dtype):
                self.lm_head = nn.Linear(config.d_model, config.vocab_size,
                                         bias_attr=False)

    def lm_head_logits(self, hidden):
        if self.lm_head is None:
            from .llama import tied_lm_head_logits

            scaled = hidden * (self.config.d_model ** -0.5)
            return tied_lm_head_logits(scaled, self.shared.weight)
        return self.lm_head(hidden)

    def forward(self, input_ids, decoder_input_ids, attention_mask=None,
                labels=None):
        enc = self.encoder(input_ids, mask=attention_mask)
        dec = self.decoder(decoder_input_ids, enc_hidden=enc,
                           enc_mask=attention_mask)
        logits = self.lm_head_logits(dec)
        if labels is None:
            return logits
        from .llama import causal_lm_loss

        return causal_lm_loss(logits, labels), logits

    # ---- cached generation ---------------------------------------------------
    def _init_caches(self, enc, batch, max_len, enc_mask=None):
        cfg = self.config
        dt = jnp.dtype(cfg.dtype)
        self_caches, cross_caches = [], []
        for block in self.decoder.blocks:
            self_caches.append({
                "k": jnp.zeros((batch, max_len, cfg.num_heads, cfg.d_kv), dt),
                "v": jnp.zeros((batch, max_len, cfg.num_heads, cfg.d_kv), dt),
                "pos": jnp.asarray(0, jnp.int32)})
            ca = block.cross_attn
            k = ca._split(ca.k(enc), enc.shape[0])
            v = ca._split(ca.v(enc), enc.shape[0])
            # no "pos" key marks a STATIC (cross-attention) cache
            cc = {"k": unwrap(k), "v": unwrap(v)}
            if enc_mask is not None:
                cc["mask"] = enc_mask
            cross_caches.append(cc)
        return self_caches, cross_caches

    def generate(self, input_ids, max_new_tokens=20, do_sample=False,
                 temperature=1.0, top_k=0, top_p=1.0, eos_token_id=_UNSET,
                 attention_mask=None, num_beams=1, length_penalty=1.0,
                 early_stopping=False, **unsupported):
        """Encoder once, then jitted cached decoder steps from
        decoder_start_token_id; stops when every row emits eos.
        ``num_beams > 1`` runs the shared host-scored beam search over the
        cached decoder (HF num_beams semantics)."""
        from ..generation import reject_non_default_kwargs

        reject_non_default_kwargs("T5", unsupported)
        from ..generation import reject_sampled_beams

        reject_sampled_beams("T5", num_beams, do_sample)
        from ..autograd import tape as _tape
        from ..framework import random as _random
        from ..generation import _select, encdec_beam_generate

        cfg = self.config
        eos = cfg.eos_token_id if eos_token_id is _UNSET else eos_token_id
        ids = unwrap(input_ids) if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        B = ids.shape[0]
        am = attention_mask
        if am is not None:
            am = (unwrap(am) if isinstance(am, Tensor)
                  else jnp.asarray(am)).astype(bool)
        with _tape.no_grad():
            enc = self.encoder(wrap(ids), mask=am)
            self_c, cross_c = self._init_caches(enc, B, max_new_tokens,
                                                enc_mask=am)
            step = _get_t5_decode_step(self, max_new_tokens)
            token = jnp.full((B, 1), cfg.decoder_start_token_id, jnp.int32)
            if num_beams > 1:
                return encdec_beam_generate(
                    self,
                    lambda m, t, s, c: m.decoder.forward_cached(t, s, c),
                    step, token, self_c, cross_c, max_new_tokens,
                    num_beams, eos, length_penalty, early_stopping,
                    "_t5_beam_steps")
            finished = jnp.zeros((B,), bool)
            out = []
            for i in range(max_new_tokens):
                logits, self_c = step(token, self_c, cross_c)
                nxt = _select(logits[:, -1, :], _random.next_key(),
                              do_sample, float(temperature), int(top_k),
                              float(top_p))
                if eos is not None:
                    nxt = jnp.where(finished, eos, nxt)
                    finished = finished | (nxt == eos)
                token = nxt[:, None].astype(jnp.int32)
                out.append(token)
                if eos is not None and bool(finished.all()):
                    break
            return wrap(jnp.concatenate(out, axis=1))

    def num_parameters(self) -> int:
        return sum(p.size for p in self.parameters())


class _T5DecodeStep:
    """One jitted decoder step: embed → all blocks (cached self-attn +
    static cross-attn) → logits."""

    def __init__(self, model, max_len):
        from ..autograd import tape as _tape
        from ..nn.layer import functional_weights

        def t5_decode_step(state, token, self_caches, cross_caches):
            with functional_weights(model, state), _tape.no_grad():
                hidden, new_self, _ = model.decoder.forward_cached(
                    wrap(token), self_caches, cross_caches)
                logits = model.lm_head_logits(hidden)
            return unwrap(logits), [
                {k: (unwrap(v) if isinstance(v, Tensor) else v)
                 for k, v in c.items()} for c in new_self]

        self._jitted = jax.jit(t5_decode_step, donate_argnums=(2,))
        self._state = dict(model.functional_state())

    def __call__(self, token, self_caches, cross_caches):
        return self._jitted(self._state, token, self_caches, cross_caches)


def _get_t5_decode_step(model, max_len):
    from ..generation import _memoized_step

    return _memoized_step(model, "_t5_decode_steps", (max_len,),
                          lambda: _T5DecodeStep(model, max_len))


# ---------------------------------------------------------------------------
# HuggingFace checkpoint interop
# ---------------------------------------------------------------------------

def t5_from_hf(hf_model_or_state, hf_config=None, **config_overrides):
    """Build a T5ForConditionalGeneration from a transformers T5 model."""
    from .llama import _hf_to_np

    if hf_config is None:
        hf_config = hf_model_or_state.config
        state = hf_model_or_state.state_dict()
    else:
        state = hf_model_or_state
    from .llama import _hf_get

    get = _hf_get(hf_config)
    ff = get("feed_forward_proj", "relu")
    kw = dict(vocab_size=get("vocab_size"), d_model=get("d_model"),
              d_kv=get("d_kv"), d_ff=get("d_ff"),
              num_layers=get("num_layers"),
              num_decoder_layers=get("num_decoder_layers"),
              num_heads=get("num_heads"),
              relative_attention_num_buckets=get(
                  "relative_attention_num_buckets", 32),
              relative_attention_max_distance=get(
                  "relative_attention_max_distance", 128),
              layer_norm_epsilon=get("layer_norm_epsilon", 1e-6),
              feed_forward_proj=("gated-gelu" if "gated" in ff else "relu"),
              tie_word_embeddings=bool(get("tie_word_embeddings", True)),
              decoder_start_token_id=get("decoder_start_token_id", 0),
              eos_token_id=get("eos_token_id", 1),
              pad_token_id=get("pad_token_id", 0))
    kw.update(config_overrides)
    cfg = T5Config(**kw)
    model = T5ForConditionalGeneration(cfg)

    plan = {"shared.weight": ("shared.weight", False)}
    for side, stack, n in (("encoder", model.encoder, cfg.num_layers),
                           ("decoder", model.decoder,
                            cfg.num_decoder_layers)):
        plan[f"{side}.final_norm.weight"] = (
            f"{side}.final_layer_norm.weight", False)
        is_dec = side == "decoder"
        for i in range(n):
            hf = f"{side}.block.{i}.layer"
            ours = f"{side}.blocks.{i}"
            for proj in "qkvo":
                plan[f"{ours}.self_attn.{proj}.weight"] = (
                    f"{hf}.0.SelfAttention.{proj}.weight", True)
            plan[f"{ours}.ln_self.weight"] = (f"{hf}.0.layer_norm.weight",
                                              False)
            if i == 0:
                plan[f"{ours}.self_attn.relative_attention_bias.weight"] = (
                    f"{hf}.0.SelfAttention.relative_attention_bias.weight",
                    False)
            ff_idx = 1
            if is_dec:
                for proj in "qkvo":
                    plan[f"{ours}.cross_attn.{proj}.weight"] = (
                        f"{hf}.1.EncDecAttention.{proj}.weight", True)
                plan[f"{ours}.ln_cross.weight"] = (
                    f"{hf}.1.layer_norm.weight", False)
                ff_idx = 2
            if cfg.feed_forward_proj == "gated-gelu":
                plan[f"{ours}.ff.wi_0.weight"] = (
                    f"{hf}.{ff_idx}.DenseReluDense.wi_0.weight", True)
                plan[f"{ours}.ff.wi_1.weight"] = (
                    f"{hf}.{ff_idx}.DenseReluDense.wi_1.weight", True)
            else:
                plan[f"{ours}.ff.wi.weight"] = (
                    f"{hf}.{ff_idx}.DenseReluDense.wi.weight", True)
            plan[f"{ours}.ff.wo.weight"] = (
                f"{hf}.{ff_idx}.DenseReluDense.wo.weight", True)
            plan[f"{ours}.ln_ff.weight"] = (
                f"{hf}.{ff_idx}.layer_norm.weight", False)
    if not cfg.tie_word_embeddings:
        plan["lm_head.weight"] = ("lm_head.weight", True)

    mapped, consumed = {}, set()
    for name, (hf_key, transpose) in plan.items():
        if hf_key not in state:
            raise KeyError(f"t5_from_hf: checkpoint is missing {hf_key!r}")
        v = _hf_to_np(state[hf_key])
        mapped[name] = v.T if transpose else v
        consumed.add(hf_key)
    leftovers = [k for k in state
                 if k not in consumed and k != "lm_head.weight"
                 and "embed_tokens" not in k]   # stack aliases of shared
    if leftovers:
        raise ValueError(
            f"t5_from_hf: checkpoint tensors this model cannot represent: "
            f"{leftovers[:5]}{'...' if len(leftovers) > 5 else ''}")
    missing, unexpected = model.set_state_dict(mapped)
    assert not unexpected, unexpected
    if missing:
        raise KeyError(f"t5_from_hf: model keys not covered: {missing[:5]}")
    return model
