"""The plain reference: one float32 decoder forward in straightforward
``jax.numpy`` that covers both families of the benchmark by what the
configuration file states, and the arithmetic of operations and bytes
that ``train_mfu``, ``serve_mfu`` and the attention rooflines divide by
(``reference/__init__.py`` has the contract of such a module).

No kernels, no cache, no batching. It follows the published descriptions:

- Mistral (Jiang et al. 2023; ``modeling_mistral.py``): pre-norm blocks
  ``h += attn(norm(h)); h += mlp(norm(h))``, grouped-query attention,
  rotary embedding over the whole head in the half-split convention,
  SwiGLU, final RMSNorm, untied head.
- OLMo 2 (OLMo et al. 2024; ``modeling_olmo2.py``): POST-norm blocks
  ``h += norm(attn(h)); h += norm(mlp(h))`` and one RMSNorm over the
  whole projected q and k width before the head split.

Departures: none in the mathematics. Weights arrive in the type the
system holds them in (bf16) and are widened to float32 here, so both
sides start from the same numbers; every contraction asks for
``Precision.HIGHEST``, without which a TPU multiplies float32 in bf16
passes. The forward runs layer by layer from the model's own arrays so
that one widened layer at a time is alive beside the served model.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from benchmarks.reference._costs import roofline_seconds  # noqa: F401

HI = jax.lax.Precision.HIGHEST


class Spec(NamedTuple):
    """What the reference needs of a configuration file."""
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rms_norm_eps: float
    rope_theta: float
    tie_word_embeddings: bool
    block: str                  # "pre_norm" | "post_norm"
    qk_norm: Optional[str]      # None | "full"

    @classmethod
    def from_config(cls, cfg: dict) -> "Spec":
        ref = cfg["reference"]
        if ref["block"] not in ("pre_norm", "post_norm") or \
                ref.get("qk_norm") not in (None, "full"):
            raise ValueError(f"reference decoder: unknown block {ref}")
        heads = int(cfg["num_attention_heads"])
        return cls(
            vocab_size=int(cfg["vocab_size"]),
            hidden_size=int(cfg["hidden_size"]),
            intermediate_size=int(cfg["intermediate_size"]),
            num_hidden_layers=int(cfg["num_hidden_layers"]),
            num_attention_heads=heads,
            num_key_value_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg.get("head_dim")
                         or int(cfg["hidden_size"]) // heads),
            rms_norm_eps=float(cfg["rms_norm_eps"]),
            rope_theta=float(cfg["rope_theta"]),
            tie_word_embeddings=bool(cfg["tie_word_embeddings"]),
            block=ref["block"], qk_norm=ref.get("qk_norm"))


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, theta):
    """x: [S, heads, D]; positions 0..S-1; half-split (rotate_half)."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.outer(jnp.arange(s, dtype=jnp.float32), inv)        # [S, D/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(spec: Spec, x, w):
    """Causal softmax attention over one sequence. x: [S, hidden]."""
    s = x.shape[0]
    h, hk, d = (spec.num_attention_heads, spec.num_key_value_heads,
                spec.head_dim)
    q = jnp.dot(x, w["q_proj"], precision=HI)
    k = jnp.dot(x, w["k_proj"], precision=HI)
    v = jnp.dot(x, w["v_proj"], precision=HI)
    if spec.qk_norm == "full":
        q = rms_norm(q, w["q_norm"], spec.rms_norm_eps)
        k = rms_norm(k, w["k_norm"], spec.rms_norm_eps)
    q = rope(q.reshape(s, h, d), spec.rope_theta)
    k = rope(k.reshape(s, hk, d), spec.rope_theta)
    v = v.reshape(s, hk, d)
    group = h // hk
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / jnp.sqrt(
        jnp.float32(d))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v, precision=HI)
    return jnp.dot(out.reshape(s, h * d), w["o_proj"], precision=HI)


def mlp(x, w):
    gate = jnp.dot(x, w["gate_proj"], precision=HI)
    up = jnp.dot(x, w["up_proj"], precision=HI)
    return jnp.dot(jax.nn.silu(gate) * up, w["down_proj"], precision=HI)


@functools.partial(jax.jit, static_argnums=0)
def block(spec: Spec, x, w):
    """One decoder block on [S, hidden] float32; ``w`` in any float type."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    eps = spec.rms_norm_eps
    if spec.block == "pre_norm":
        x = x + attention(spec, rms_norm(x, w["input_layernorm"], eps), w)
        return x + mlp(rms_norm(x, w["post_attention_layernorm"], eps), w)
    x = x + rms_norm(attention(spec, x, w), w["post_attention_layernorm"],
                     eps)
    return x + rms_norm(mlp(x, w), w["post_feedforward_layernorm"], eps)


@functools.partial(jax.jit, static_argnums=0)
def head_logprobs(spec: Spec, x, norm_w, head_w):
    """Final norm, head and log-softmax of the rows of ``x``."""
    x = rms_norm(x, norm_w.astype(jnp.float32), spec.rms_norm_eps)
    logits = jnp.dot(x, head_w.astype(jnp.float32), precision=HI)
    return jax.nn.log_softmax(logits, axis=-1)


def layer_weights(state: dict, i: int) -> dict:
    """The arrays of layer ``i`` from a state dict in the trunk's naming
    (``llama.layers.<i>.self_attn.q_proj.weight`` ...), [in, out] layout."""
    pre = f"llama.layers.{i}."
    out = {}
    for key, arr in state.items():
        if key.startswith(pre) and key.endswith(".weight"):
            out[key[len(pre):-len(".weight")].split(".")[-1]] = arr
    return out


def forward_logprobs(spec: Spec, state: dict, ids, last: int):
    """log-softmax over the vocabulary at the last ``last`` positions of
    one sequence ``ids`` ([S] ints), from a dict of plain arrays."""
    ids = jnp.asarray(ids, jnp.int32)
    embed = state["llama.embed_tokens.weight"]
    x = embed[ids].astype(jnp.float32)
    for i in range(spec.num_hidden_layers):
        x = block(spec, x, layer_weights(state, i))
    head = (embed.T if spec.tie_word_embeddings else state["lm_head.weight"])
    return head_logprobs(spec, x[-last:], state["llama.norm.weight"], head)


def next_token_loss(spec: Spec, state: dict, ids) -> float:
    """Mean cross entropy of predicting ``ids[1:]`` from ``ids[:-1]``."""
    ids = jnp.asarray(ids, jnp.int32)
    lp = forward_logprobs(spec, state, ids[:-1], last=ids.shape[0] - 1)
    return float(-jnp.mean(jnp.take_along_axis(lp, ids[1:, None], axis=-1)))


# ---- operations and bytes, from shapes --------------------------------------

def matmul_params(spec: Spec) -> int:
    """Parameters that sit in a matrix multiplication of one token's
    forward pass: the projections, the MLP and the head (an embedding
    lookup multiplies nothing)."""
    h, d = spec.hidden_size, spec.head_dim
    attn = h * d * (2 * spec.num_attention_heads
                    + 2 * spec.num_key_value_heads)
    return (spec.num_hidden_layers * (attn + 3 * h * spec.intermediate_size)
            + h * spec.vocab_size)


def attn_flops_per_token(spec: Spec, seq: int, passes: int = 3) -> float:
    """QK^T and PV of causal attention per token at length ``seq``: a
    token attends to seq/2 others on average; 2 flops a multiply-add,
    two products; ``passes`` 1 forward, 3 forward and backward (the
    backward's recomputation of the scores is not counted)."""
    return (passes * 2 * 2 * spec.num_hidden_layers
            * spec.num_attention_heads * spec.head_dim * (seq / 2))


def train_flops_per_token(spec: Spec, seq: int) -> float:
    """What forward and backward REQUIRE per trained token: 6 x the
    matmul parameters plus causal attention; nothing recomputed counts."""
    return 6.0 * matmul_params(spec) + attn_flops_per_token(spec, seq, 3)


def serve_flops_per_token(spec: Spec, context: float,
                          sampled_share: float = 1.0) -> float:
    """What serving REQUIRES per token that enters the model, prompt or
    generated: one forward pass through the layers' matrices, attention
    over ``context`` keys (the mean over those tokens of the keys each
    attends to), and the head for the ``sampled_share`` of them whose
    logits a token is drawn from (a prompt needs its last position's
    only). Logits a program computes and drops do not count."""
    head = spec.hidden_size * spec.vocab_size
    return (2.0 * (matmul_params(spec) - head * (1.0 - sampled_share))
            + attn_flops_per_token(spec, 2.0 * context, passes=1))


def flash_train_cost(spec: Spec, batch: int, seq: int) -> dict:
    """One train step's attention kernels (forward and backward, all
    layers): required flops, and the bytes that must cross HBM at least
    once (q, k, v, o forward; q, k, v, o, do in and dq, dk, dv out
    backward), bf16."""
    flops = attn_flops_per_token(spec, seq, 3) * batch * seq
    qo = batch * seq * spec.num_attention_heads * spec.head_dim * 2
    kv = batch * seq * spec.num_key_value_heads * spec.head_dim * 2
    fwd = 2 * qo + 2 * kv
    bwd = (3 * qo + 2 * kv) + (qo + 2 * kv)
    return {"flops": float(flops),
            "bytes": float(spec.num_hidden_layers * (fwd + bwd))}


def paged_decode_cost(spec: Spec, context_tokens: float,
                      rows: float) -> dict:
    """ONE layer's paged decode attention call over ``rows`` sequences
    holding ``context_tokens`` cached tokens in all: every cached K and V
    row is read once (bf16), q and the output once."""
    kv_row = 2 * spec.num_key_value_heads * spec.head_dim * 2
    qo = 2 * rows * spec.num_attention_heads * spec.head_dim * 2
    return {"flops": 4.0 * context_tokens * spec.num_attention_heads
            * spec.head_dim,
            "bytes": float(context_tokens * kv_row + qo)}
