"""RG-LRU linear recurrence — time-blocked Pallas TPU kernel.

h_t = exp(log_a_t) * h_{t-1} + b_t, elementwise over channels.

TPU mapping: channels are the lane dimension (128-aligned blocks), batch is
the sublane dimension; time is the innermost *sequential* grid axis with the
carry h held in VMEM scratch across time blocks.  Within a block the scan is
a short unrolled loop of VPU multiply-adds over (block_b, block_c) tiles —
no MXU needed; the kernel exists to keep the recurrence resident in VMEM
instead of bouncing h through HBM per step (the XLA associative-scan path
materializes log-depth intermediates).

Layouts: log_a, b (B, S, C) f32.  Grid (B/bb, C/bc, S/bt), time innermost.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(la_ref, b_ref, o_ref, h_ref, *, block_t: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    h = h_ref[...]
    for j in range(block_t):
        a = jnp.exp(la_ref[:, j, :])
        h = a * h + b_ref[:, j, :]
        o_ref[:, j, :] = h
    h_ref[...] = h


@functools.partial(jax.jit, static_argnames=("block_b", "block_c", "block_t",
                                             "interpret"))
def rglru_scan(log_a, b, *, block_b: int = 8, block_c: int = 128,
               block_t: int = 16, interpret: bool = False):
    """log_a, b (B,S,C) f32 -> h (B,S,C) f32."""
    B, S, C = log_a.shape
    block_b = min(block_b, B)
    block_c = min(block_c, C)
    block_t = min(block_t, S)
    assert B % block_b == 0 and C % block_c == 0 and S % block_t == 0
    kernel = functools.partial(_rglru_kernel, block_t=block_t)
    return pl.pallas_call(
        kernel,
        grid=(B // block_b, C // block_c, S // block_t),
        in_specs=[
            pl.BlockSpec((block_b, block_t, block_c),
                         lambda ib, ic, it: (ib, it, ic)),
            pl.BlockSpec((block_b, block_t, block_c),
                         lambda ib, ic, it: (ib, it, ic)),
        ],
        out_specs=pl.BlockSpec((block_b, block_t, block_c),
                               lambda ib, ic, it: (ib, it, ic)),
        out_shape=jax.ShapeDtypeStruct((B, S, C), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_b, block_c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(log_a, b)
