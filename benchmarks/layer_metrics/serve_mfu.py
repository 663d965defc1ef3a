"""The whole serving step's share of the chip's peak, as ``train_mfu`` is
the train step's: the tokens, prompt and generated, that the requests
COMPLETED inside the window (``tokens_per_s``'s own requests) put through
the model x the flops a forward pass REQUIRES per token (the
``serve_flops_per_token`` of the cell's own reference module: the layers'
matrices, attention over the keys each token attends to, the head only
where a token is drawn) over the chip's bf16 peak from ``peaks.json``. A
kernel's roofline falls silent when a later PR takes the kernel off the
path; this share still bounds what that PR can claim on
``tokens_per_s``."""
LAYER = "generation.py prefill / decode programs"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "host_clock"


def attended_keys(n_prompt: int, n_tokens: int) -> float:
    """Keys attended to by all tokens of one request: prompt position i
    sees i + 1 of them; the first token is drawn from the prompt's last
    position, and each later one enters the model behind all before it."""
    decoded = max(n_tokens - 1, 0)
    return (n_prompt * (n_prompt + 1) / 2.0
            + decoded * n_prompt + decoded * (decoded + 1) / 2.0)


def read(ctx):
    from benchmarks.lib.common import note, reference_function

    if ctx.get("kind") != "closed_loop" or not ctx.get("peaks"):
        return None
    done = [o for o in ctx.get("window_outcomes", []) if o["ok"]]
    # a request's last token is drawn and never enters the model
    entered = sum(o["n_prompt"] + max(o["n_tokens"] - 1, 0) for o in done)
    if not entered:
        return None
    per_token = reference_function(ctx, "serve_mfu", "serve_flops_per_token")
    if per_token is None:
        return None
    context = sum(attended_keys(o["n_prompt"], o["n_tokens"])
                  for o in done) / entered
    sampled = sum(o["n_tokens"] for o in done) / entered
    flops = per_token(ctx["spec"], context, sampled)
    note("serve_mfu", tokens_entered=entered, mean_attended_keys=context,
         sampled_share=sampled, flops_per_token=flops)
    return (100.0 * entered / ctx["seconds"] * flops
            / (ctx["chips"] * ctx["peaks"]["flops_bf16_per_s"]))
