"""Pallas TPU field codec — GRIB "simple packing" adapted to TPU (DESIGN §3).

The ECMWF I/O servers' compute hot spot is field packing: v = round((x -
min) / scale) at reduced bit width.  A mechanical port would be serial
bit-twiddling; the TPU-native rethink is *block-local byte-granular
quantisation*: each grid step owns a (block, 128·k) VMEM tile, computes the
tile min/max with VPU reductions, scales to int8 (or int16), and stores the
lane-aligned quantised tile + the tile's (scale, min) pair.  Sub-byte
packing does not vectorise on TPU lanes and is intentionally dropped
(documented as non-transferring).

Used by the ``field8``/``field16`` chunk codecs of
:mod:`repro.tensorstore.codec` (chunked fields and compressed checkpoints)
and by the legacy checkpoint shard blobs.

encode:  x (N, C) → q int8 (N, C), scale (N/block,), mins (N/block,)
decode:  inverse.

Both entry points also accept a leading *batch* dimension — x (B, N, C) —
encoding B same-shape fields in ONE kernel launch over the grid (B,
N/block).  Each field's row count is a multiple of the block size, so no
quantisation block ever straddles a field boundary, and the per-block
(scale, min) pairs — and therefore the quantised bytes — are bit-identical
to B separate 2-D calls.  This is what lets the tensorstore write path
encode a whole write plan's chunks per launch instead of a Python loop of
per-chunk launches.

Tiling.  Mosaic accepts a block whose row count is a multiple of
:data:`SUBLANES` — for the f32 field and its int8/int16 codes alike — or
equal to the field's whole row count (:func:`legal_block`); compiled for
TPU v5e, blocks of 1, 2 and 4 rows are refused.  It also refuses (1, 1)
blocks and scalar stores to VMEM, so the per-block (scale, min) leave the
kernel lane-dense: each grid step writes its scalar broadcast over one
(1, C) row and the wrapper keeps lane 0; decode takes them back the same
way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: row multiple Mosaic requires of a block that is not the whole field
SUBLANES = 8


def legal_block(rows: int, block: int) -> bool:
    """Whether ``block`` tiles a ``rows``-row field in a way Mosaic lowers."""
    return rows % block == 0 and (block % SUBLANES == 0 or block == rows)


def _check_block(rows: int, block: int) -> None:
    if not legal_block(rows, block):
        raise ValueError(f"block {block} does not tile {rows} rows on the "
                         f"chip: it must divide them and be a multiple of "
                         f"{SUBLANES} or all of them")


def _encode_kernel(x_ref, q_ref, scale_ref, min_ref, *, bits: int):
    x = x_ref[...].astype(jnp.float32)
    mn = jnp.min(x)
    mx = jnp.max(x)
    levels = float(2 ** bits - 1)
    shift = float(2 ** (bits - 1))
    scale = (mx - mn) / levels
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.round((x - mn) / safe) - shift
    q = jnp.clip(q, -shift, shift - 1)
    q_ref[...] = q.astype(q_ref.dtype)
    scale_ref[...] = jnp.full(scale_ref.shape, scale, jnp.float32)
    min_ref[...] = jnp.full(min_ref.shape, mn, jnp.float32)


def _decode_kernel(q_ref, scale_ref, min_ref, x_ref, *, bits: int):
    shift = float(2 ** (bits - 1))
    q = q_ref[...].astype(jnp.float32)
    x = (q + shift) * scale_ref[...] + min_ref[...]
    x_ref[...] = x.astype(x_ref.dtype)


def _specs(block: int, cdim: int):
    """(row-tile spec, lane-dense per-block stat spec) over grid (B, nb)."""
    return (pl.BlockSpec((None, block, cdim), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, None, 1, cdim), lambda b, i: (b, i, 0, 0)))


@functools.partial(jax.jit,
                   static_argnames=("block", "bits", "interpret"))
def field_encode(x: jax.Array, block: int = 256, bits: int = 8,
                 interpret: bool = False):
    """x: (N, C) or (B, N, C), C % 128 == 0 (lane alignment); ``block``
    (clipped to N) must tile N legally (:func:`legal_block`).

    With a batch dimension the outputs are q (B, N, C), scale (B, N/block),
    mins (B, N/block) from a single launch over the grid (B, N/block).
    """
    if x.ndim == 2:
        q, scale, mins = field_encode(x[None], block=block, bits=bits,
                                      interpret=interpret)
        return q[0], scale[0], mins[0]
    B, N, Cdim = x.shape
    block = min(block, N)
    _check_block(N, block)
    nb = N // block
    dtype = jnp.int8 if bits == 8 else jnp.int16
    row, stat = _specs(block, Cdim)
    stat_shape = jax.ShapeDtypeStruct((B, nb, 1, Cdim), jnp.float32)
    q, scale, mins = pl.pallas_call(
        functools.partial(_encode_kernel, bits=bits),
        grid=(B, nb),
        in_specs=[row],
        out_specs=[row, stat, stat],
        out_shape=[jax.ShapeDtypeStruct((B, N, Cdim), dtype),
                   stat_shape, stat_shape],
        interpret=interpret,
        name="field_encode",
    )(x)
    return q, scale[:, :, 0, 0], mins[:, :, 0, 0]


@functools.partial(jax.jit,
                   static_argnames=("block", "bits", "out_dtype", "interpret"))
def field_decode(q: jax.Array, scale: jax.Array, mins: jax.Array,
                 block: int = 256, bits: int = 8, out_dtype=jnp.float32,
                 interpret: bool = False) -> jax.Array:
    """Inverse of :func:`field_encode`; q (N, C) or batched (B, N, C) with
    scale/mins (B, N/block) — the batched form decodes in one launch."""
    if q.ndim == 2:
        return field_decode(q[None], scale[None], mins[None], block=block,
                            bits=bits, out_dtype=out_dtype,
                            interpret=interpret)[0]
    B, N, Cdim = q.shape
    block = min(block, N)
    _check_block(N, block)
    nb = N // block
    row, stat = _specs(block, Cdim)

    def lanes(v):
        return jnp.broadcast_to(v.astype(jnp.float32)[:, :, None, None],
                                (B, nb, 1, Cdim))

    return pl.pallas_call(
        functools.partial(_decode_kernel, bits=bits),
        grid=(B, nb),
        in_specs=[row, stat, stat],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((B, N, Cdim), out_dtype),
        interpret=interpret,
        name="field_decode",
    )(q, lanes(scale), lanes(mins))
