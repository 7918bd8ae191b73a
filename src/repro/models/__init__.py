from .lm import (
    ModelOptions,
    abstract_params,
    decode_step,
    forward,
    forward_with_cache,
    init_cache,
    init_params,
    init_serving_params,
    loss_fn,
    stack_plan,
)

__all__ = [
    "ModelOptions",
    "abstract_params",
    "decode_step",
    "forward",
    "init_cache",
    "init_params",
    "init_serving_params",
    "loss_fn",
    "stack_plan",
]
