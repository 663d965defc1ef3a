"""Whisper speech-recognition family (tiny → large-v3).

The audio member of the zoo — encoder-decoder ASR on the BART cache
machinery with Whisper's deviations:

- mel-spectrogram frontend: two Conv1Ds (the second stride-2) with gelu,
  then FIXED sinusoidal encoder positions (stored as a weight, matching
  the checkpoint layout);
- PRE-LN transformer blocks (BART is post-LN) and a final LayerNorm on
  both stacks;
- attention k_proj carries NO bias (q/v/out do);
- learned decoder positions indexed by absolute position (no BART +2
  offset), tied lm head (proj_out == embed weight).

The cached decode discipline (dense self-cache + precomputed cross K/V)
is models/bart.py's — WhisperAttention subclasses BartAttention for it.

``whisper_from_hf`` converts a transformers ``WhisperForConditionalGeneration``.
Parity is tested against manual HF greedy (transformers' whisper.generate
injects task/language forcing that belongs to the tokenizer layer, not
the model)."""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..nn.layer import Layer
from ..ops.registry import apply
from ..tensor_class import Tensor, unwrap, wrap
from .bart import BartAttention

# sentinel: "caller did not pass eos_token_id" — maps to the config
# default; an explicit None DISABLES eos (matching the decoder-only
# families' semantics)
_UNSET = object()

@dataclasses.dataclass
class WhisperConfig:
    # whisper-tiny shape
    vocab_size: int = 51865
    d_model: int = 384
    encoder_layers: int = 4
    decoder_layers: int = 4
    encoder_attention_heads: int = 6
    decoder_attention_heads: int = 6
    encoder_ffn_dim: int = 1536
    decoder_ffn_dim: int = 1536
    num_mel_bins: int = 80
    max_source_positions: int = 1500   # frames after the stride-2 conv
    max_target_positions: int = 448
    activation_function: str = "gelu"
    scale_embedding: bool = False
    decoder_start_token_id: int = 50257
    eos_token_id: int = 50256
    pad_token_id: int = 50256
    dtype: str = "float32"

    def __post_init__(self):
        if self.activation_function != "gelu":
            raise NotImplementedError(
                f"Whisper activation_function "
                f"{self.activation_function!r} is not supported (gelu "
                "only — every released Whisper checkpoint uses gelu)")

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, d_model=64, encoder_layers=2,
                    decoder_layers=2, encoder_attention_heads=4,
                    decoder_attention_heads=4, encoder_ffn_dim=128,
                    decoder_ffn_dim=128, num_mel_bins=8,
                    max_source_positions=16, max_target_positions=64,
                    decoder_start_token_id=1, eos_token_id=2,
                    pad_token_id=2)
        base.update(kw)
        return WhisperConfig(**base)


def sinusoids(length: int, channels: int) -> np.ndarray:
    """Whisper's fixed encoder position table (modeling_whisper
    sinusoids): interleaved-free [sin | cos] halves over log-spaced
    timescales."""
    if channels % 2:
        raise ValueError("sinusoid channels must be even")
    log_inc = math.log(10000) / (channels // 2 - 1)
    inv = np.exp(-log_inc * np.arange(channels // 2, dtype=np.float64))
    t = np.arange(length, dtype=np.float64)[:, None] * inv[None, :]
    return np.concatenate([np.sin(t), np.cos(t)],
                          axis=1).astype(np.float32)


class WhisperAttention(BartAttention):
    """BART's cache-disciplined MHA with Whisper's bias layout: k_proj
    has no bias."""

    def __init__(self, config, n_heads: int):
        Layer.__init__(self, dtype=config.dtype)
        from ..framework.dtype import dtype_guard

        d = config.d_model
        self.n_heads = n_heads
        self.head_dim = d // n_heads
        self.scale = 1.0 / math.sqrt(self.head_dim)
        with dtype_guard(config.dtype):
            self.q_proj = nn.Linear(d, d)
            self.k_proj = nn.Linear(d, d, bias_attr=False)
            self.v_proj = nn.Linear(d, d)
            self.out_proj = nn.Linear(d, d)


def _gelu(x):
    return jax.nn.gelu(x, approximate=False)


class WhisperEncoderLayer(Layer):
    """PRE-LN: x = x + attn(LN1(x)); x = x + ffn(LN2(x))."""

    def __init__(self, config: WhisperConfig):
        super().__init__(dtype=config.dtype)
        from ..framework.dtype import dtype_guard

        self.self_attn = WhisperAttention(config,
                                          config.encoder_attention_heads)
        with dtype_guard(config.dtype):
            self.self_attn_layer_norm = nn.LayerNorm(config.d_model)
            self.fc1 = nn.Linear(config.d_model, config.encoder_ffn_dim)
            self.fc2 = nn.Linear(config.encoder_ffn_dim, config.d_model)
            self.final_layer_norm = nn.LayerNorm(config.d_model)

    def forward(self, hidden):
        hidden = hidden + self.self_attn(self.self_attn_layer_norm(hidden))
        act = apply("gelu", _gelu, self.fc1(self.final_layer_norm(hidden)))
        return hidden + self.fc2(act)


class WhisperDecoderLayer(Layer):
    def __init__(self, config: WhisperConfig):
        super().__init__(dtype=config.dtype)
        from ..framework.dtype import dtype_guard

        self.self_attn = WhisperAttention(config,
                                          config.decoder_attention_heads)
        self.encoder_attn = WhisperAttention(config,
                                             config.decoder_attention_heads)
        with dtype_guard(config.dtype):
            self.self_attn_layer_norm = nn.LayerNorm(config.d_model)
            self.encoder_attn_layer_norm = nn.LayerNorm(config.d_model)
            self.fc1 = nn.Linear(config.d_model, config.decoder_ffn_dim)
            self.fc2 = nn.Linear(config.decoder_ffn_dim, config.d_model)
            self.final_layer_norm = nn.LayerNorm(config.d_model)

    def forward(self, hidden, enc_hidden=None, self_cache=None,
                cross_cache=None):
        h = self.self_attn_layer_norm(hidden)
        if self_cache is not None:
            a, self_cache = self.self_attn(h, kv_cache=self_cache)
        else:
            a = self.self_attn(h, causal=True)
        hidden = hidden + a
        h = self.encoder_attn_layer_norm(hidden)
        if cross_cache is not None:
            c, cross_cache = self.encoder_attn(h, kv_cache=cross_cache)
        else:
            c = self.encoder_attn(h, kv_hidden=enc_hidden)
        hidden = hidden + c
        act = apply("gelu", _gelu, self.fc1(self.final_layer_norm(hidden)))
        hidden = hidden + self.fc2(act)
        if self_cache is not None:
            return hidden, self_cache, cross_cache
        return hidden


class WhisperModel(Layer):
    def __init__(self, config: WhisperConfig):
        super().__init__(dtype=config.dtype)
        from ..framework.dtype import dtype_guard

        self.config = config
        d = config.d_model
        with dtype_guard(config.dtype):
            self.conv1 = nn.Conv1D(config.num_mel_bins, d, 3, padding=1)
            self.conv2 = nn.Conv1D(d, d, 3, stride=2, padding=1)
            self.embed_tokens = nn.Embedding(config.vocab_size, d)
            self.decoder_pos = nn.Embedding(config.max_target_positions, d)
            self.encoder_ln = nn.LayerNorm(d)
            self.decoder_ln = nn.LayerNorm(d)
            # fixed sinusoidal encoder positions, stored as a
            # (non-trainable) weight to match the checkpoint layout; the
            # table follows the model dtype — an f32 island here would
            # upcast every encoder activation at the stem
            self.encoder_pos = nn.Embedding(config.max_source_positions, d)
        self.encoder_pos.weight.set_value(
            sinusoids(config.max_source_positions, d).astype(config.dtype))
        self.encoder_pos.weight.stop_gradient = True
        self.encoder_layers_list = nn.LayerList(
            [WhisperEncoderLayer(config)
             for _ in range(config.encoder_layers)])
        self.decoder_layers_list = nn.LayerList(
            [WhisperDecoderLayer(config)
             for _ in range(config.decoder_layers)])
        self._scale = (math.sqrt(d) if config.scale_embedding else 1.0)

    def encode(self, input_features):
        """[B, num_mel_bins, T] mel frames -> [B, T//2, d_model]."""
        x = apply("gelu", _gelu, self.conv1(input_features))
        x = apply("gelu", _gelu, self.conv2(x))
        x = x.transpose([0, 2, 1])
        t = x.shape[1]
        if t > self.config.max_source_positions:
            raise ValueError(
                f"Whisper: {t} encoder frames exceed max_source_positions "
                f"{self.config.max_source_positions}")
        pe = jnp.take(unwrap(self.encoder_pos.weight), jnp.arange(t),
                      axis=0)
        hidden = wrap((unwrap(x) + pe).astype(jnp.dtype(self.config.dtype)))
        for layer in self.encoder_layers_list:
            hidden = layer(hidden)
        return self.encoder_ln(hidden)

    def _embed(self, ids, positions):
        tok = unwrap(self.embed_tokens(ids)) * self._scale
        pe = jnp.take(unwrap(self.decoder_pos.weight),
                      jnp.asarray(positions), axis=0)
        if pe.ndim == 2:
            pe = pe[None]
        return wrap((tok + pe).astype(jnp.dtype(self.config.dtype)))

    def decode(self, ids, enc_hidden):
        s = ids.shape[1]
        if s > self.config.max_target_positions:
            raise ValueError(
                f"Whisper: {s} decoder positions exceed "
                f"max_target_positions {self.config.max_target_positions}")
        hidden = self._embed(ids, jnp.arange(s))
        for layer in self.decoder_layers_list:
            hidden = layer(hidden, enc_hidden=enc_hidden)
        return self.decoder_ln(hidden)

    def decode_cached(self, ids, self_caches, cross_caches):
        s = ids.shape[1]
        if "lengths" in self_caches[0]:     # ragged serving rows
            positions = (self_caches[0]["lengths"][:, None]
                         + jnp.arange(s)[None, :])
        else:
            positions = self_caches[0]["pos"] + jnp.arange(s)
        hidden = self._embed(ids, positions)
        new_self, new_cross = [], []
        for layer, sc, cc in zip(self.decoder_layers_list, self_caches,
                                 cross_caches):
            hidden, sc, cc = layer(hidden, self_cache=sc, cross_cache=cc)
            new_self.append(sc)
            new_cross.append(cc)
        return self.decoder_ln(hidden), new_self, new_cross


class WhisperForConditionalGeneration(Layer):
    """Whisper ASR seq2seq LM — tied lm head (proj_out)."""

    def __init__(self, config: WhisperConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.model = WhisperModel(config)

    def lm_head_logits(self, hidden):
        from .llama import tied_lm_head_logits

        return tied_lm_head_logits(hidden, self.model.embed_tokens.weight)

    def forward(self, input_features, decoder_input_ids, labels=None):
        enc = self.model.encode(input_features)
        dec = self.model.decode(decoder_input_ids, enc)
        logits = self.lm_head_logits(dec)
        if labels is None:
            return logits
        from .llama import causal_lm_loss

        return causal_lm_loss(logits, labels), logits

    def _init_caches(self, enc, batch, max_len):
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
            cross_caches.append(
                {"k": unwrap(ca._split(ca.k_proj(enc), enc.shape[0])),
                 "v": unwrap(ca._split(ca.v_proj(enc), enc.shape[0]))})
        return self_caches, cross_caches

    def generate(self, input_features, decoder_input_ids=None,
                 max_new_tokens=20, do_sample=False, temperature=1.0,
                 top_k=0, top_p=1.0, eos_token_id=_UNSET, num_beams=1,
                 length_penalty=1.0, early_stopping=False, **unsupported):
        """Cached autoregressive transcription. ``decoder_input_ids``
        seeds the decoder (task/language prompt tokens); defaults to
        ``decoder_start_token_id``. Token suppression/forcing beyond the
        seed belongs to the tokenizer pipeline, not the model.
        ``num_beams>1``: HF-semantics beam search (greedy scoring)."""
        from ..generation import reject_non_default_kwargs

        reject_non_default_kwargs("Whisper", unsupported)
        from ..generation import reject_sampled_beams

        reject_sampled_beams("Whisper", num_beams, do_sample)
        from ..autograd import tape as _tape
        from ..framework import random as _random
        from ..generation import _select, encdec_beam_generate

        cfg = self.config
        eos = cfg.eos_token_id if eos_token_id is _UNSET else eos_token_id
        feats = (input_features if isinstance(input_features, Tensor)
                 else wrap(jnp.asarray(np.asarray(input_features))))
        B = feats.shape[0]
        if decoder_input_ids is None:
            seed = jnp.full((B, 1), cfg.decoder_start_token_id, jnp.int32)
        else:
            seed = jnp.asarray(
                unwrap(decoder_input_ids)
                if isinstance(decoder_input_ids, Tensor)
                else np.asarray(decoder_input_ids)).astype(jnp.int32)
        max_len = seed.shape[1] + max_new_tokens
        if max_len > cfg.max_target_positions:
            raise ValueError(
                f"seed ({seed.shape[1]}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_target_positions "
                f"{cfg.max_target_positions}")
        with _tape.no_grad():
            enc = self.model.encode(feats)
            self_c, cross_c = self._init_caches(enc, B, max_len)
            step = _get_whisper_decode_step(self, max_len)
            if num_beams > 1:
                return encdec_beam_generate(
                    self,
                    lambda m, t, s, c: m.model.decode_cached(t, s, c),
                    step, seed, self_c, cross_c, max_new_tokens,
                    num_beams, eos, length_penalty, early_stopping,
                    "_whisper_beam_steps")
            token = seed
            finished = jnp.zeros((B,), bool)
            out = []
            for _ in range(max_new_tokens):
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


class _WhisperDecodeStep:
    def __init__(self, model, max_len):
        from ..autograd import tape as _tape
        from ..nn.layer import functional_weights

        def whisper_decode_step(state, token, self_caches, cross_caches):
            with functional_weights(model, state), _tape.no_grad():
                hidden, new_self, _ = model.model.decode_cached(
                    wrap(token), self_caches, cross_caches)
                logits = model.lm_head_logits(hidden)
            return unwrap(logits), [
                {k: (unwrap(v) if isinstance(v, Tensor) else v)
                 for k, v in c.items()} for c in new_self]

        self._jitted = jax.jit(whisper_decode_step, donate_argnums=(2,))
        self._state = dict(model.functional_state())

    def __call__(self, token, self_caches, cross_caches):
        return self._jitted(self._state, token, self_caches, cross_caches)


def _get_whisper_decode_step(model, max_len):
    from ..generation import _memoized_step

    return _memoized_step(model, "_whisper_decode_steps", (max_len,),
                          lambda: _WhisperDecodeStep(model, max_len))


# ---------------------------------------------------------------------------
# HuggingFace checkpoint interop
# ---------------------------------------------------------------------------

def whisper_from_hf(hf_model_or_state, hf_config=None, **config_overrides):
    """Build a WhisperForConditionalGeneration from a transformers
    Whisper model (or a raw state dict + config)."""
    from .llama import _hf_get, _hf_to_np

    if hf_config is None:
        hf_config = hf_model_or_state.config
        state = hf_model_or_state.state_dict()
    else:
        state = hf_model_or_state
    get = _hf_get(hf_config)
    kw = dict(vocab_size=get("vocab_size"), d_model=get("d_model"),
              encoder_layers=get("encoder_layers"),
              decoder_layers=get("decoder_layers"),
              encoder_attention_heads=get("encoder_attention_heads"),
              decoder_attention_heads=get("decoder_attention_heads"),
              encoder_ffn_dim=get("encoder_ffn_dim"),
              decoder_ffn_dim=get("decoder_ffn_dim"),
              num_mel_bins=get("num_mel_bins", 80),
              max_source_positions=get("max_source_positions", 1500),
              max_target_positions=get("max_target_positions", 448),
              activation_function=get("activation_function", "gelu"),
              scale_embedding=bool(get("scale_embedding", False)),
              decoder_start_token_id=get("decoder_start_token_id"),
              eos_token_id=get("eos_token_id"),
              pad_token_id=get("pad_token_id"))
    if kw["activation_function"] != "gelu":
        raise NotImplementedError(
            f"whisper_from_hf: activation_function "
            f"{kw['activation_function']!r} not supported (gelu only)")
    kw.update(config_overrides)
    cfg = WhisperConfig(**kw)
    model = WhisperForConditionalGeneration(cfg)

    plan = {
        "model.conv1.weight": ("model.encoder.conv1.weight", False),
        "model.conv1.bias": ("model.encoder.conv1.bias", False),
        "model.conv2.weight": ("model.encoder.conv2.weight", False),
        "model.conv2.bias": ("model.encoder.conv2.bias", False),
        "model.encoder_pos.weight": (
            "model.encoder.embed_positions.weight", False),
        "model.embed_tokens.weight": (
            "model.decoder.embed_tokens.weight", False),
        "model.decoder_pos.weight": (
            "model.decoder.embed_positions.weight", False),
        "model.encoder_ln.weight": ("model.encoder.layer_norm.weight",
                                    False),
        "model.encoder_ln.bias": ("model.encoder.layer_norm.bias", False),
        "model.decoder_ln.weight": ("model.decoder.layer_norm.weight",
                                    False),
        "model.decoder_ln.bias": ("model.decoder.layer_norm.bias", False),
    }
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
                for proj in ("q_proj", "k_proj", "v_proj", "out_proj"):
                    plan[f"{ours}.{ours_attn}.{proj}.weight"] = (
                        f"{hf}.{hf_attn}.{proj}.weight", True)
                    if proj != "k_proj":    # whisper: no k bias
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
            raise KeyError(f"whisper_from_hf: checkpoint missing {hf_key!r}")
        v = _hf_to_np(state[hf_key])
        mapped[name] = v.T if transpose else v
        consumed.add(hf_key)
    leftovers = [k for k in state if k not in consumed
                 and k != "proj_out.weight"]   # tied-head alias
    if leftovers:
        raise ValueError(
            f"whisper_from_hf: checkpoint tensors this model cannot "
            f"represent: {leftovers[:5]}"
            f"{'...' if len(leftovers) > 5 else ''}")
    missing, unexpected = model.set_state_dict(mapped)
    assert not unexpected, unexpected
    if missing:
        raise KeyError(f"whisper_from_hf: model keys not covered: "
                       f"{missing[:5]}")
    return model
