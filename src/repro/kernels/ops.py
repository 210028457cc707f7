"""Jit'd public wrappers for the Pallas kernels.

On the CPU backend the kernels run in interpret mode; on any other backend
they lower through Mosaic.  Only the field codec is checked on the TPU:
``tests/test_tpu_compile.py`` compiles it for a described v5e chip and
``chip_smoke.py`` runs it on one.  ``ref.py`` holds the pure-jnp oracles
used by the per-kernel allclose sweeps in tests/test_kernels.py.

Importing this module mirrors the program's ``repro.obs`` spans into the
JAX profiler (``jax.profiler.TraceAnnotation``, name only), so a profiler
trace shows them on its host planes, on the clock of the device's
operations.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.obs.trace import set_span_mirror

from . import ref
from .field_codec import field_decode as _field_decode
from .field_codec import field_encode as _field_encode
from .flash_attention import flash_attention as _flash_attention
from .rmsnorm import fused_rmsnorm as _fused_rmsnorm

set_span_mirror(jax.profiler.TraceAnnotation)


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_k: int = 128):
    """q,k,v: (B, H, S, D)."""
    return _flash_attention(q, k, v, causal=causal, block_q=block_q,
                            block_k=block_k, interpret=_interpret())


def field_encode(x, block: int = 256, bits: int = 8):
    return _field_encode(x, block=block, bits=bits, interpret=_interpret())


def field_decode(q, scale, mins, block: int = 256, bits: int = 8,
                 out_dtype=jnp.float32):
    return _field_decode(q, scale, mins, block=block, bits=bits,
                         out_dtype=out_dtype, interpret=_interpret())


def fused_rmsnorm(x, scale, eps: float = 1e-5, block_rows: int = 256):
    return _fused_rmsnorm(x, scale, eps=eps, block_rows=block_rows,
                          interpret=_interpret())


__all__ = ["flash_attention", "field_encode", "field_decode",
           "fused_rmsnorm", "ref"]
