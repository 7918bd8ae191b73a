"""Pallas TPU kernels for the compute hot spots.

Each kernel: ``<name>.py`` (pl.pallas_call + explicit BlockSpec VMEM
tiling), jitted wrappers in ``ops.py``, pure-jnp oracles in ``ref.py``.
Validated with interpret=True on CPU against the oracles, and compiled for
a described TPU v5e in ``tests/test_tpu_compile.py``.  On the serving path
``PagedServeEngine(attn_impl="kernel")`` calls ``paged_decode_attention``,
compiled on a TPU; the other kernels are reached only from tests.
"""

from . import ops, ref
from .decode_attention import decode_attention, paged_decode_attention
from .flash_attention import flash_attention
from .mlstm_chunk import mlstm_chunk
from .rglru_scan import rglru_scan
from .rmsnorm import rmsnorm

__all__ = [
    "decode_attention",
    "flash_attention",
    "mlstm_chunk",
    "ops",
    "paged_decode_attention",
    "ref",
    "rglru_scan",
    "rmsnorm",
]
