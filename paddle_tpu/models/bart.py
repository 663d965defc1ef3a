"""BART encoder-decoder family (post-LN, learned positions).

Role parity: the second seq2seq flagship of the reference ecosystem's
zoo (PaddleNLP bart/mbart modeling). Architecture per HF: learned
position embeddings with the +2 offset quirk, POST-layer-norm residual
blocks (LayerNorm after the residual add), scaled dot-product attention
with biases on every projection, gelu FFN with biases, tied lm head plus
a final_logits_bias row.

TPU-native design mirrors models/t5.py: the encoder runs once, cross
K/V are projected once, and each decoder step is one jitted dispatch
over in-place self-attention KV buffers; positions ride the caches'
scalar offset.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..nn.layer import Layer
from ..ops.registry import apply
from ..tensor_class import Tensor, Parameter, unwrap, wrap

# sentinel: "caller did not pass eos_token_id" — maps to the config
# default; an explicit None DISABLES eos (matching the decoder-only
# families' semantics)
_UNSET = object()

@dataclasses.dataclass
class BartConfig:
    vocab_size: int = 50265
    d_model: int = 768
    encoder_layers: int = 6
    decoder_layers: int = 6
    encoder_attention_heads: int = 12
    decoder_attention_heads: int = 12
    encoder_ffn_dim: int = 3072
    decoder_ffn_dim: int = 3072
    max_position_embeddings: int = 1024
    activation_function: str = "gelu"     # "gelu" | "gelu_new" | "relu"
    scale_embedding: bool = False
    decoder_start_token_id: int = 2
    eos_token_id: int = 2
    pad_token_id: int = 1
    dtype: str = "float32"

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, d_model=64, encoder_layers=2,
                    decoder_layers=2, encoder_attention_heads=4,
                    decoder_attention_heads=4, encoder_ffn_dim=128,
                    decoder_ffn_dim=128, max_position_embeddings=128,
                    dtype="float32")
        base.update(kw)
        return BartConfig(**base)

    def __post_init__(self):
        if self.activation_function not in ("gelu", "gelu_new", "relu"):
            raise NotImplementedError(
                f"BART activation_function {self.activation_function!r} "
                "(supported: gelu, gelu_new, relu)")


_POS_OFFSET = 2  # HF BartLearnedPositionalEmbedding reserves 2 rows


def _activation(config):
    if config.activation_function == "relu":
        return "relu", jax.nn.relu
    approx = config.activation_function == "gelu_new"
    return ("gelu_tanh" if approx else "gelu",
            lambda a: jax.nn.gelu(a, approximate=approx))


class BartAttention(Layer):
    """Scaled MHA with biases; self- (optionally cached) or cross-
    (static cached K/V) attention — the cache discipline of models/t5.py
    with BART's scaling and biases."""

    def __init__(self, config: BartConfig, n_heads: int):
        super().__init__(dtype=config.dtype)
        from ..framework.dtype import dtype_guard

        self.n_heads = n_heads
        self.head_dim = config.d_model // n_heads
        self.scale = 1.0 / math.sqrt(self.head_dim)
        with dtype_guard(config.dtype):
            self.q_proj = nn.Linear(config.d_model, config.d_model)
            self.k_proj = nn.Linear(config.d_model, config.d_model)
            self.v_proj = nn.Linear(config.d_model, config.d_model)
            self.out_proj = nn.Linear(config.d_model, config.d_model)

    def _split(self, t, b):
        return t.reshape([b, -1, self.n_heads, self.head_dim])

    def forward(self, hidden, kv_hidden=None, mask=None, causal=False,
                kv_cache=None):
        b = hidden.shape[0]
        q = self._split(self.q_proj(hidden), b)
        scale = self.scale

        def attend(qh, kh, vh, add):
            scores = jnp.einsum("bqhd,bkhd->bhqk",
                                unwrap(qh).astype(jnp.float32),
                                unwrap(kh).astype(jnp.float32)) * scale
            if add is not None:
                scores = scores + add
            probs = jax.nn.softmax(scores, axis=-1)
            out = jnp.einsum("bhqk,bkhd->bqhd", probs,
                             unwrap(vh).astype(jnp.float32))
            return out.astype(unwrap(qh).dtype)

        if (isinstance(kv_cache, dict) and "pos" not in kv_cache
                and "lengths" not in kv_cache):
            add = None
            cmask = kv_cache.get("mask")
            if cmask is not None:
                add = jnp.where(cmask[:, None, None, :], 0.0, -jnp.inf)
            out = attend(q, kv_cache["k"], kv_cache["v"], add)
            return self.out_proj(
                wrap(out.reshape(b, -1, self.n_heads * self.head_dim))), kv_cache
        if isinstance(kv_cache, dict) and "lengths" in kv_cache:
            # RAGGED single-token decode (the seq2seq serving engine):
            # row r's new token writes at ITS length and attends columns
            # 0..lengths[r] — slots of different ages share one step
            s = hidden.shape[1]
            if s != 1:
                raise ValueError("ragged enc-dec decode is single-token")
            lengths = kv_cache["lengths"]
            k_new = self._split(self.k_proj(hidden), b)
            v_new = self._split(self.v_proj(hidden), b)
            rows = jnp.arange(b)
            k_buf = kv_cache["k"].at[rows, lengths].set(
                unwrap(k_new)[:, 0].astype(kv_cache["k"].dtype))
            v_buf = kv_cache["v"].at[rows, lengths].set(
                unwrap(v_new)[:, 0].astype(kv_cache["v"].dtype))
            t_idx = jnp.arange(k_buf.shape[1])
            valid = t_idx[None, :] <= lengths[:, None]          # [B, T]
            add = jnp.where(valid[:, None, None, :], 0.0, -jnp.inf)
            out = attend(q, k_buf, v_buf, add)
            new = {"k": k_buf, "v": v_buf, "lengths": lengths + 1}
            return self.out_proj(
                wrap(out.reshape(b, s, self.n_heads * self.head_dim))), new
        if isinstance(kv_cache, dict):
            s = hidden.shape[1]
            k_new = self._split(self.k_proj(hidden), b)
            v_new = self._split(self.v_proj(hidden), b)
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
            out = attend(q, k_buf, v_buf, add)
            new = {"k": k_buf, "v": v_buf, "pos": pos + s}
            return self.out_proj(
                wrap(out.reshape(b, s, self.n_heads * self.head_dim))), new
        src = hidden if kv_hidden is None else kv_hidden
        k = self._split(self.k_proj(src), b)
        v = self._split(self.v_proj(src), b)
        add = None
        if causal:
            sq, sk = hidden.shape[1], src.shape[1]
            cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
            add = jnp.where(cm, 0.0, -jnp.inf)[None, None]
        if mask is not None:
            m = jnp.where(mask[:, None, None, :], 0.0, -jnp.inf)
            add = m if add is None else add + m
        out = attend(q, k, v, add)
        return self.out_proj(
            wrap(out.reshape(b, -1, self.n_heads * self.head_dim)))


class BartEncoderLayer(Layer):
    """POST-LN: x = LN(x + attn(x)); x = LN(x + ffn(x))."""

    def __init__(self, config: BartConfig):
        super().__init__(dtype=config.dtype)
        from ..framework.dtype import dtype_guard

        self.self_attn = BartAttention(config, config.encoder_attention_heads)
        with dtype_guard(config.dtype):
            self.self_attn_layer_norm = nn.LayerNorm(config.d_model)
            self.fc1 = nn.Linear(config.d_model, config.encoder_ffn_dim)
            self.fc2 = nn.Linear(config.encoder_ffn_dim, config.d_model)
            self.final_layer_norm = nn.LayerNorm(config.d_model)
        self._act = _activation(config)

    def forward(self, hidden, mask=None):
        hidden = self.self_attn_layer_norm(
            hidden + self.self_attn(hidden, mask=mask))
        act = apply(self._act[0], self._act[1], self.fc1(hidden))
        return self.final_layer_norm(hidden + self.fc2(act))


class BartDecoderLayer(Layer):
    def __init__(self, config: BartConfig):
        super().__init__(dtype=config.dtype)
        from ..framework.dtype import dtype_guard

        self.self_attn = BartAttention(config, config.decoder_attention_heads)
        self.encoder_attn = BartAttention(config,
                                          config.decoder_attention_heads)
        with dtype_guard(config.dtype):
            self.self_attn_layer_norm = nn.LayerNorm(config.d_model)
            self.encoder_attn_layer_norm = nn.LayerNorm(config.d_model)
            self.fc1 = nn.Linear(config.d_model, config.decoder_ffn_dim)
            self.fc2 = nn.Linear(config.decoder_ffn_dim, config.d_model)
            self.final_layer_norm = nn.LayerNorm(config.d_model)
        self._act = _activation(config)

    def forward(self, hidden, enc_hidden=None, enc_mask=None,
                self_cache=None, cross_cache=None):
        if self_cache is not None:
            a, self_cache = self.self_attn(hidden, kv_cache=self_cache)
        else:
            a = self.self_attn(hidden, causal=True)
        hidden = self.self_attn_layer_norm(hidden + a)
        if cross_cache is not None:
            c, cross_cache = self.encoder_attn(hidden, kv_cache=cross_cache)
        else:
            c = self.encoder_attn(hidden, kv_hidden=enc_hidden,
                                  mask=enc_mask)
        hidden = self.encoder_attn_layer_norm(hidden + c)
        act = apply(self._act[0], self._act[1], self.fc1(hidden))
        hidden = self.final_layer_norm(hidden + self.fc2(act))
        if self_cache is not None:
            return hidden, self_cache, cross_cache
        return hidden


class BartModel(Layer):
    def __init__(self, config: BartConfig):
        super().__init__(dtype=config.dtype)
        from ..framework.dtype import dtype_guard

        self.config = config
        with dtype_guard(config.dtype):
            self.shared = nn.Embedding(config.vocab_size, config.d_model)
            self.encoder_pos = nn.Embedding(
                config.max_position_embeddings + _POS_OFFSET, config.d_model)
            self.decoder_pos = nn.Embedding(
                config.max_position_embeddings + _POS_OFFSET, config.d_model)
            self.encoder_ln_emb = nn.LayerNorm(config.d_model)
            self.decoder_ln_emb = nn.LayerNorm(config.d_model)
        self.encoder_layers_list = nn.LayerList(
            [BartEncoderLayer(config) for _ in range(config.encoder_layers)])
        self.decoder_layers_list = nn.LayerList(
            [BartDecoderLayer(config) for _ in range(config.decoder_layers)])
        self._scale = (math.sqrt(config.d_model)
                       if config.scale_embedding else 1.0)

    def _embed(self, ids, pos_table, positions):
        tok = unwrap(self.shared(ids)) * self._scale
        pe = jnp.take(unwrap(pos_table.weight),
                      jnp.asarray(positions) + _POS_OFFSET, axis=0)
        if pe.ndim == 2:
            pe = pe[None]
        return wrap((tok + pe).astype(jnp.dtype(self.config.dtype)))

    def _check_len(self, s):
        if s > self.config.max_position_embeddings:
            # learned tables are fixed size; clamped take would silently
            # reuse the last row for every overflow position
            raise ValueError(
                f"BART: sequence length {s} exceeds max_position_embeddings "
                f"{self.config.max_position_embeddings}")

    def encode(self, input_ids, mask=None):
        s = input_ids.shape[1]
        self._check_len(s)
        hidden = self.encoder_ln_emb(
            self._embed(input_ids, self.encoder_pos, jnp.arange(s)))
        for layer in self.encoder_layers_list:
            hidden = layer(hidden, mask=mask)
        return hidden

    def decode(self, ids, enc_hidden, enc_mask=None):
        s = ids.shape[1]
        self._check_len(s)
        hidden = self.decoder_ln_emb(
            self._embed(ids, self.decoder_pos, jnp.arange(s)))
        for layer in self.decoder_layers_list:
            hidden = layer(hidden, enc_hidden=enc_hidden, enc_mask=enc_mask)
        return hidden

    def decode_cached(self, ids, self_caches, cross_caches):
        s = ids.shape[1]
        if "lengths" in self_caches[0]:     # ragged serving rows
            positions = (self_caches[0]["lengths"][:, None]
                         + jnp.arange(s)[None, :])
        else:
            positions = self_caches[0]["pos"] + jnp.arange(s)
        hidden = self.decoder_ln_emb(
            self._embed(ids, self.decoder_pos, positions))
        new_self, new_cross = [], []
        for layer, sc, cc in zip(self.decoder_layers_list, self_caches,
                                 cross_caches):
            hidden, sc, cc = layer(hidden, self_cache=sc, cross_cache=cc)
            new_self.append(sc)
            new_cross.append(cc)
        return hidden, new_self, new_cross


class BartForConditionalGeneration(Layer):
    """BART seq2seq LM: tied lm head + final_logits_bias."""

    def __init__(self, config: BartConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.model = BartModel(config)
        self.final_logits_bias = Parameter(
            jnp.zeros((config.vocab_size,), jnp.float32), trainable=False)

    def lm_head_logits(self, hidden):
        from .llama import tied_lm_head_logits

        logits = tied_lm_head_logits(hidden, self.model.shared.weight)
        return logits + wrap(unwrap(self.final_logits_bias).astype(
            unwrap(logits).dtype))

    def forward(self, input_ids, decoder_input_ids, attention_mask=None,
                labels=None):
        enc = self.model.encode(input_ids, mask=attention_mask)
        dec = self.model.decode(decoder_input_ids, enc,
                                enc_mask=attention_mask)
        logits = self.lm_head_logits(dec)
        if labels is None:
            return logits
        from .llama import causal_lm_loss

        return causal_lm_loss(logits, labels), logits

    def _init_caches(self, enc, batch, max_len, enc_mask=None):
        cfg = self.config
        dt = jnp.dtype(cfg.dtype)
        h = cfg.decoder_attention_heads
        d = cfg.d_model // h
        self_caches, cross_caches = [], []
        for layer in self.model.decoder_layers_list:
            self_caches.append({
                "k": jnp.zeros((batch, max_len, h, d), dt),
                "v": jnp.zeros((batch, max_len, h, d), dt),
                "pos": jnp.asarray(0, jnp.int32)})
            ca = layer.encoder_attn
            cc = {"k": unwrap(ca._split(ca.k_proj(enc), enc.shape[0])),
                  "v": unwrap(ca._split(ca.v_proj(enc), enc.shape[0]))}
            if enc_mask is not None:
                cc["mask"] = enc_mask
            cross_caches.append(cc)
        return self_caches, cross_caches

    def generate(self, input_ids, max_new_tokens=20, do_sample=False,
                 temperature=1.0, top_k=0, top_p=1.0, eos_token_id=_UNSET,
                 attention_mask=None, num_beams=1, length_penalty=1.0,
                 early_stopping=False, **unsupported):
        from ..generation import reject_non_default_kwargs

        reject_non_default_kwargs("BART", unsupported)
        from ..generation import reject_sampled_beams

        reject_sampled_beams("BART", num_beams, do_sample)
        from ..autograd import tape as _tape
        from ..framework import random as _random
        from ..generation import _select, encdec_beam_generate

        cfg = self.config
        eos = cfg.eos_token_id if eos_token_id is _UNSET else eos_token_id
        ids = unwrap(input_ids) if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        B = ids.shape[0]
        self.model._check_len(int(max_new_tokens))
        am = attention_mask
        if am is not None:
            am = (unwrap(am) if isinstance(am, Tensor)
                  else jnp.asarray(am)).astype(bool)
        with _tape.no_grad():
            enc = self.model.encode(wrap(ids), mask=am)
            self_c, cross_c = self._init_caches(enc, B, max_new_tokens,
                                                enc_mask=am)
            step = _get_bart_decode_step(self, max_new_tokens)
            token = jnp.full((B, 1), cfg.decoder_start_token_id, jnp.int32)
            if num_beams > 1:
                return encdec_beam_generate(
                    self,
                    lambda m, t, s, c: m.model.decode_cached(t, s, c),
                    step, token, self_c, cross_c, max_new_tokens,
                    num_beams, eos, length_penalty, early_stopping,
                    "_bart_beam_steps")
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


class _BartDecodeStep:
    def __init__(self, model, max_len):
        from ..autograd import tape as _tape
        from ..nn.layer import functional_weights

        def bart_decode_step(state, token, self_caches, cross_caches):
            with functional_weights(model, state), _tape.no_grad():
                hidden, new_self, _ = model.model.decode_cached(
                    wrap(token), self_caches, cross_caches)
                logits = model.lm_head_logits(hidden)
            return unwrap(logits), [
                {k: (unwrap(v) if isinstance(v, Tensor) else v)
                 for k, v in c.items()} for c in new_self]

        self._jitted = jax.jit(bart_decode_step, donate_argnums=(2,))
        self._state = dict(model.functional_state())

    def __call__(self, token, self_caches, cross_caches):
        return self._jitted(self._state, token, self_caches, cross_caches)


def _get_bart_decode_step(model, max_len):
    from ..generation import _memoized_step

    return _memoized_step(model, "_bart_decode_steps", (max_len,),
                          lambda: _BartDecodeStep(model, max_len))


# ---------------------------------------------------------------------------
# HuggingFace checkpoint interop
# ---------------------------------------------------------------------------

def bart_from_hf(hf_model_or_state, hf_config=None, **config_overrides):
    """Build a BartForConditionalGeneration from a transformers BART."""
    from .llama import _hf_to_np

    if hf_config is None:
        hf_config = hf_model_or_state.config
        state = hf_model_or_state.state_dict()
    else:
        state = hf_model_or_state
    from .llama import _hf_get

    get = _hf_get(hf_config)
    kw = dict(vocab_size=get("vocab_size"), d_model=get("d_model"),
              encoder_layers=get("encoder_layers"),
              decoder_layers=get("decoder_layers"),
              encoder_attention_heads=get("encoder_attention_heads"),
              decoder_attention_heads=get("decoder_attention_heads"),
              encoder_ffn_dim=get("encoder_ffn_dim"),
              decoder_ffn_dim=get("decoder_ffn_dim"),
              max_position_embeddings=get("max_position_embeddings"),
              activation_function=get("activation_function", "gelu"),
              scale_embedding=bool(get("scale_embedding", False)),
              decoder_start_token_id=get("decoder_start_token_id", 2),
              eos_token_id=get("eos_token_id", 2),
              pad_token_id=get("pad_token_id", 1))
    kw.update(config_overrides)
    cfg = BartConfig(**kw)
    model = BartForConditionalGeneration(cfg)

    plan = {"model.shared.weight": ("model.shared.weight", False),
            "model.encoder_pos.weight": ("model.encoder.embed_positions.weight", False),
            "model.decoder_pos.weight": ("model.decoder.embed_positions.weight", False),
            "model.encoder_ln_emb.weight": ("model.encoder.layernorm_embedding.weight", False),
            "model.encoder_ln_emb.bias": ("model.encoder.layernorm_embedding.bias", False),
            "model.decoder_ln_emb.weight": ("model.decoder.layernorm_embedding.weight", False),
            "model.decoder_ln_emb.bias": ("model.decoder.layernorm_embedding.bias", False),
            "final_logits_bias": ("final_logits_bias", False)}
    attn_mods = ("q_proj", "k_proj", "v_proj", "out_proj")
    for side, n, ours_list in (("encoder", cfg.encoder_layers,
                                "encoder_layers_list"),
                               ("decoder", cfg.decoder_layers,
                                "decoder_layers_list")):
        for i in range(n):
            hf = f"model.{side}.layers.{i}"
            ours = f"model.{ours_list}.{i}"
            attns = [("self_attn", "self_attn")]
            if side == "decoder":
                attns.append(("encoder_attn", "encoder_attn"))
            for ours_attn, hf_attn in attns:
                for proj in attn_mods:
                    plan[f"{ours}.{ours_attn}.{proj}.weight"] = (
                        f"{hf}.{hf_attn}.{proj}.weight", True)
                    plan[f"{ours}.{ours_attn}.{proj}.bias"] = (
                        f"{hf}.{hf_attn}.{proj}.bias", False)
                plan[f"{ours}.{ours_attn}_layer_norm.weight"] = (
                    f"{hf}.{hf_attn}_layer_norm.weight", False)
                plan[f"{ours}.{ours_attn}_layer_norm.bias"] = (
                    f"{hf}.{hf_attn}_layer_norm.bias", False)
            for fc in ("fc1", "fc2"):
                plan[f"{ours}.{fc}.weight"] = (f"{hf}.{fc}.weight", True)
                plan[f"{ours}.{fc}.bias"] = (f"{hf}.{fc}.bias", False)
            plan[f"{ours}.final_layer_norm.weight"] = (
                f"{hf}.final_layer_norm.weight", False)
            plan[f"{ours}.final_layer_norm.bias"] = (
                f"{hf}.final_layer_norm.bias", False)

    mapped, consumed = {}, set()
    for name, (hf_key, transpose) in plan.items():
        if hf_key not in state:
            raise KeyError(f"bart_from_hf: checkpoint is missing {hf_key!r}")
        v = _hf_to_np(state[hf_key])
        if name == "final_logits_bias":
            v = v.reshape(-1)          # HF stores [1, vocab]
        mapped[name] = v.T if transpose else v
        consumed.add(hf_key)
    leftovers = [k for k in state
                 if k not in consumed and k != "lm_head.weight"
                 and "embed_tokens" not in k]   # encoder/decoder aliases
    if leftovers:
        raise ValueError(
            f"bart_from_hf: checkpoint tensors this model cannot represent: "
            f"{leftovers[:5]}{'...' if len(leftovers) > 5 else ''}")
    missing, unexpected = model.set_state_dict(mapped)
    assert not unexpected, unexpected
    if missing:
        raise KeyError(f"bart_from_hf: model keys not covered: {missing[:5]}")
    return model
