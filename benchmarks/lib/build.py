"""A configuration file -> the program's own model, through its normal
constructor (``module:Class`` named by the file)."""
from __future__ import annotations

from .common import import_object


def model_config(cfg: dict):
    """The program's config object from the file's published keys plus
    the constructor arguments the file adds."""
    b = cfg["builder"]
    kwargs = {k: cfg[k] for k in b["config_keys"] if k in cfg}
    kwargs.update(b.get("config_args", {}))
    return import_object(b["config"])(**kwargs)


def build_model(cfg: dict, seed: int):
    """Seed the program's generator and construct the model: the weights
    are drawn on the device, in the type they are served in, by the
    constructor's own initializers (leaf by leaf: only a change to the
    program can make that one call; PERF.md, Open questions)."""
    import paddle_tpu as paddle

    paddle.seed(int(seed))
    return import_object(cfg["builder"]["model"])(model_config(cfg))


def plain_state(model) -> dict:
    """The model's arrays by name, as plain ``jax`` arrays (the only thing
    the reference is given)."""
    return {k: v._array for k, v in model.state_dict().items()}
