"""What ``lib.serve.compare_logprobs`` has to FAIL, for a ``cohere2_moe``
reference: the contract's control (the reference itself, computed from
weights of the nearest precision below the configuration's bf16, put in
the program's place) and the planted faults. ``tools/planted_faults.py``
reads them at the cell's own size on the chip,
``tests/test_reference_cohere2_moe.py`` at the rehearsal's widths.

A fault is planted on the REFERENCE's side (the reference then stands for
a correct model and the program for one with that fault), so the
near-ties on offer are the faulty reference's, not those a faulty program
would meet beside a sound reference. The control is the other way round:
the sound reference judges, and the near-ties are its own.
"""
from __future__ import annotations

import numpy as np

FAULTS = ("window_one_short", "one_expert_fewer_a_token",
          "shared_expert_dropped", "routed_held_expert_zeroed")
#: the kinds ``in_lower_precision`` knows; the first is the control
LOWER_PRECISIONS = ("fp8_e4m3_weights", "int8_weights")


def plant(fault: str, spec, state: dict) -> tuple:
    """(spec, state) of a ``cohere2_moe`` reference with ``fault``."""
    if fault == "window_one_short":
        return spec._replace(sliding_window=spec.sliding_window - 1), state
    if fault == "one_expert_fewer_a_token":
        return spec._replace(top_k=spec.top_k - 1), state
    f = spec.intermediate_size
    state = dict(state)
    for layer in range(spec.num_hidden_layers):
        if fault == "shared_expert_dropped":      # the second of them
            key = f"llama.layers.{layer}.mlp.shared_expert.down_proj.weight"
            state[key] = state[key].at[f:2 * f].set(0.0)
        elif fault == "routed_held_expert_zeroed":
            key = next(k for k in state
                       if k.startswith(f"llama.layers.{layer}.")
                       and k.split(".")[-1] == "w2")
            state[key] = state[key].at[1].set(0.0)
        else:
            raise ValueError(f"no fault {fault!r}")
    return spec, state


def fp8_e4m3(w):
    """``w`` rounded to float8_e4m3fn and back, under one power-of-two
    scale a tensor that takes its largest entry to under 448. By
    arithmetic, not by a convert: the TPU compiler drops a convert to a
    narrower float and back (``xla_allow_excess_precision``), and the
    control then read 0.0 (my chip run, PR 37). Three mantissa bits, the
    smallest normal 2^-6, below it one step of 2^-9; round half to even."""
    import jax.numpy as jnp

    wide = w.astype(jnp.float32)
    _, e = jnp.frexp(jnp.max(jnp.abs(wide)) / 448.0)    # amax/448 < 2^e
    scaled = jnp.ldexp(wide, -e)        # ldexp: exp2 is not exact everywhere
    _, e_x = jnp.frexp(scaled)                  # |x| in [2^(e_x-1), 2^e_x)
    step = jnp.ldexp(jnp.float32(1.0), jnp.maximum(e_x - 1, -6) - 3)
    return jnp.ldexp(jnp.round(scaled / step) * step, e).astype(w.dtype)


def int8_columns(w):
    """``w`` rounded to symmetric int8 and back, one scale an output
    column: what the program's own ``nn.quant`` weight-only path does to a
    ``Linear``."""
    import jax.numpy as jnp

    wide = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(wide), axis=-2, keepdims=True) / 127.0
    return (jnp.clip(jnp.round(wide / scale), -127, 127)
            * scale).astype(w.dtype)


def in_lower_precision(weights: dict, kind: str) -> dict:
    """``weights`` (a state dict, or one layer's arrays by their last
    name) as a weight-only 8-bit serving path would hold them: every
    matrix rounded (``fp8_e4m3_weights``: ``fp8_e4m3``; ``int8_weights``:
    ``int8_columns``) and widened back to its own type; norms, biases and
    the ROUTER stay as they are (the program's router runs in float32, and
    a rounded router would fail by its routing alone: this is the smaller
    reading)."""
    import jax

    rounder = jax.jit({"fp8_e4m3_weights": fp8_e4m3,
                       "int8_weights": int8_columns}[kind])
    out = {}
    for key, w in weights.items():
        name = key.split(".")
        last = name[-2] if name[-1] == "weight" else name[-1]
        matrix = w.ndim >= 2 and last != "gate_weight"
        out[key] = rounder(w) if matrix else w
    return out


def control_answers(reference, spec, state: dict, kind: str, prompts: list,
                    answers: list) -> list:
    """The contract's control: ``answers`` as a program would give them
    that computed what the ``cohere2_moe`` ``reference`` computes, from
    weights ``in_lower_precision``: the same tokens (no decoding: the
    comparison teacher-forces them anyway) with that pass's logprobs of
    them. A layer's weights are rounded as the pass reaches it, so that
    no second copy of the model has to fit beside the first."""
    import jax.numpy as jnp

    head = in_lower_precision(
        {k: state[k] for k in ("llama.embed_tokens.weight",
                               "llama.norm.weight")}, kind)
    out = []
    for prompt, ans in zip(prompts, answers):
        toks = ans["token_ids"]
        ids = jnp.asarray(prompt + toks[:-1], jnp.int32)
        x = head["llama.embed_tokens.weight"][ids].astype(jnp.float32)
        for i in range(spec.num_hidden_layers):
            x, _, _ = reference.block(
                spec, spec.layer_types[i] == "sliding_attention", x,
                in_lower_precision(reference.layer_weights(state, i), kind),
                np.zeros(0, np.int32))
        lp = np.asarray(reference.head_logprobs(
            spec, x[-len(toks):], head["llama.norm.weight"],
            head["llama.embed_tokens.weight"]))
        out.append({"token_ids": toks, "logprobs": [
            float(v) for v in lp[np.arange(len(toks)), np.asarray(toks)]]})
    return out
