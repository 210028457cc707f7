"""Per-chunk codecs: raw passthrough + the Pallas field codec.

``field8``/``field16`` reuse the TPU field-packing kernels
(:mod:`repro.kernels.field_codec`): the chunk is flattened, its head of
128-element rows is block-quantised to int8/int16 with per-block (scale,
min) pairs, and the rest rides along as float32 — the sub-lane tail, plus,
in chunks longer than one block, the rows past the last multiple of the
chip's row tile (see :meth:`FieldQuantCodec._layout`).
Chunks that cannot profit (non-float dtypes, tiny chunks) fall back to raw
bytes — the one-byte container header makes every chunk self-describing, so
edge chunks of any shape roundtrip exactly through either path.

The batch entry points (:meth:`Codec.encode_batch` /
:meth:`Codec.decode_batch`) are the write/read plans' hook into kernel
vectorisation: equal-shape chunks are stacked onto the kernels' leading
batch dimension and encoded (decoded) in ONE Pallas launch — grid over
chunks × blocks — while ragged edge chunks fall back to the per-chunk path.
Batched output is byte-identical to per-chunk encodes (blocks never
straddle chunks), so the two paths interoperate freely.  A decode group
launches in slices of at most :func:`launch_cap` chunks (32, fewer where
32 would not fit :data:`MAX_LAUNCH_BYTES`), each at the next power of two
of its size (zero rows padded, dropped after), so a geometry uses at most
log2(32) + 1 launch shapes, which the codec runs once, on zeros, before the
geometry's first launch.

Container layout (little-endian):
  [0]   marker: 0 = raw ndarray bytes, 1 = quantised
  quantised payload:
  [1:9] rows:u32, block:u32
  [9:]  q (rows*128 int8|int16) | scale (rows/block f32) | mins (f32) | tail f32

The header makes every container self-describing: containers written
before blocks were held to the chip's tiling (blocks of 1, 2 or 4 rows)
still decode, through the jnp reference decoder on the same device.
"""
from __future__ import annotations

import struct
import threading
from typing import Dict, List, Sequence, Tuple

import numpy as np

from repro.obs.trace import count as obs_count
from repro.obs.trace import span as obs_span

_LANES = 128
_RAW, _QUANT = 0, 1
_BLOCK_CANDIDATES = (256, 128, 64, 32, 16, 8)
#: most chunks one decode launch takes: the default executor window
#: (8 workers x 4), so a read stage of one geometry is one launch
MAX_DECODE_BATCH = 32
#: most float32 bytes one decode launch restores, so that eight workers'
#: launches hold at most 512 MiB of device and host memory at once
MAX_LAUNCH_BYTES = 64 << 20


def launch_batch(n: int) -> int:
    """Leading batch dimension a launch of ``n`` chunks runs at: the next
    power of two (one chunk stays ``B = 1``)."""
    return 1 << (n - 1).bit_length()


def launch_cap(rows: int) -> int:
    """Most chunks of ``rows`` quantised rows one decode launch takes: the
    largest power of two within :data:`MAX_DECODE_BATCH` and
    :data:`MAX_LAUNCH_BYTES` (at least one)."""
    fit = min(MAX_DECODE_BATCH, MAX_LAUNCH_BYTES // (rows * _LANES * 4))
    return 1 << (max(1, fit).bit_length() - 1)


class Codec:
    name: str = "?"

    def encode(self, arr: np.ndarray) -> bytes:
        raise NotImplementedError

    def decode(self, data: bytes, shape: Tuple[int, ...],
               dtype: np.dtype) -> np.ndarray:
        raise NotImplementedError

    # -- batch entry points (kernel vectorisation hook) ---------------------
    def encode_batch(self, arrs: Sequence[np.ndarray]) -> List[bytes]:
        """Encode several chunks, byte-identical to per-chunk :meth:`encode`
        and in input order.  Codecs backed by kernels override this to
        launch once per equal-shape group instead of once per chunk."""
        return [self.encode(a) for a in arrs]

    def decode_batch(self, datas: Sequence[bytes],
                     shapes: Sequence[Tuple[int, ...]],
                     dtype: np.dtype) -> List[np.ndarray]:
        """Decode several chunk payloads (inverse of :meth:`encode_batch`)."""
        return [self.decode(d, s, dtype) for d, s in zip(datas, shapes)]


class RawCodec(Codec):
    name = "raw"

    def encode(self, arr: np.ndarray) -> bytes:
        return np.ascontiguousarray(arr).tobytes()

    def decode(self, data: bytes, shape: Tuple[int, ...],
               dtype: np.dtype) -> np.ndarray:
        return np.frombuffer(data, dtype=dtype).reshape(shape).copy()


class FieldQuantCodec(Codec):
    """Lossy block quantisation via the Pallas field codec kernels."""

    def __init__(self, bits: int = 8):
        assert bits in (8, 16)
        self.bits = bits
        self.name = f"field{bits}"
        self._qdtype = np.int8 if bits == 8 else np.int16
        #: (rows, block) of the geometries whose launch shapes have run
        self._warm: set = set()
        self._warm_lock = threading.Lock()

    def _eligible(self, arr: np.ndarray) -> bool:
        return (arr.dtype in (np.float32, np.float16, np.float64)
                and arr.size >= 2 * _LANES)

    @staticmethod
    def _layout(size: int) -> Tuple[int, int, int]:
        """(quantised head length, quantised rows, block) for a chunk of
        ``size`` elements — shared by the loop and batched encode paths
        (and the legacy checkpoint blobs) so all pick identical geometry.

        The block is the largest candidate dividing the rows, so it is a
        multiple of the chip's row tile (``field_codec.SUBLANES``); a chunk
        of at most 256 rows that none divides is one block.  A longer chunk
        quantises its rows down to a multiple of the tile, and the rows
        past it join the float32 tail."""
        from repro.kernels.field_codec import SUBLANES
        rows = size // _LANES
        if rows > _BLOCK_CANDIDATES[0]:
            rows -= rows % SUBLANES
        block = next((b for b in _BLOCK_CANDIDATES if rows % b == 0), rows)
        return rows * _LANES, rows, block

    def _container(self, rows: int, block: int, q, scale, mins,
                   tail: np.ndarray) -> bytes:
        return b"".join([
            bytes([_QUANT]), struct.pack("<II", rows, block),
            np.asarray(q, self._qdtype).tobytes(),
            np.asarray(scale, np.float32).tobytes(),
            np.asarray(mins, np.float32).tobytes(),
            tail.tobytes(),
        ])

    def encode(self, arr: np.ndarray) -> bytes:
        arr = np.ascontiguousarray(arr)
        if not self._eligible(arr):
            return bytes([_RAW]) + arr.tobytes()
        from repro.kernels import ops
        flat = arr.reshape(-1).astype(np.float32)
        n, rows, block = self._layout(flat.size)
        q, scale, mins = ops.field_encode(flat[:n].reshape(rows, _LANES),
                                          block=block, bits=self.bits)
        return self._container(rows, block, q, scale, mins, flat[n:])

    def encode_batch(self, arrs: Sequence[np.ndarray]) -> List[bytes]:
        """Stack equal-shape eligible chunks onto the kernel's batch
        dimension: one Pallas launch per distinct chunk shape (interior
        chunks of a write plan all share one), instead of one per chunk.
        Ineligible chunks take the raw fallback; output is byte-identical
        to calling :meth:`encode` per chunk.

        Each group runs in the stages ``codec.encode.stack`` (host copies
        into one float32 batch), ``.launch`` (the batch's copy to the
        device and the kernel's launch, which returns before the kernel
        ends), ``.d2h`` (the wait for the kernel and the copy of its
        results back) and ``.pack`` (containers): the same calls, and the
        one wait, whether tracing is on or off."""
        out: List[bytes] = [b""] * len(arrs)
        by_shape: Dict[Tuple[int, ...], List[int]] = {}
        for i, a in enumerate(arrs):
            if self._eligible(a):
                by_shape.setdefault(a.shape, []).append(i)
            else:
                out[i] = bytes([_RAW]) + np.ascontiguousarray(a).tobytes()
        if by_shape:
            from repro.kernels import ops
        for idxs in by_shape.values():
            with obs_span("codec.encode.stack", chunks=len(idxs)):
                flats = [np.ascontiguousarray(arrs[i]).reshape(-1)
                         .astype(np.float32) for i in idxs]
                n, rows, block = self._layout(flats[0].size)
                stacked = np.stack([f[:n].reshape(rows, _LANES)
                                    for f in flats])
            with obs_span("codec.encode.launch"):
                q, scale, mins = ops.field_encode(stacked, block=block,
                                                  bits=self.bits)
            with obs_span("codec.encode.d2h"):
                q, scale, mins = (np.asarray(q, self._qdtype),
                                  np.asarray(scale, np.float32),
                                  np.asarray(mins, np.float32))
            with obs_span("codec.encode.pack"):
                for k, i in enumerate(idxs):
                    out[i] = self._container(rows, block, q[k], scale[k],
                                             mins[k], flats[k][n:])
        return out

    def _decode_head(self, q: np.ndarray, scale: np.ndarray,
                     mins: np.ndarray, block: int):
        """Decode quantised rows q (rows, 128) or (B, rows, 128) on the
        device: through the kernel, or — for a container whose block the
        chip's tiling refuses — through the jnp reference decoder."""
        import jax.numpy as jnp

        from repro.kernels import ops, ref
        from repro.kernels.field_codec import legal_block
        if legal_block(q.shape[-2], block):
            return ops.field_decode(q, scale, mins, block=block,
                                    bits=self.bits)
        return ref.field_decode_ref(
            jnp.asarray(q.reshape(-1, _LANES)), jnp.asarray(scale.reshape(-1)),
            jnp.asarray(mins.reshape(-1)), block=block,
            bits=self.bits).reshape(q.shape)

    def describes(self, data: bytes, size: int) -> bool:
        """Whether ``data`` is a quantised container of a ``size``-element
        chunk: a header whose (rows, block) agree with its length."""
        if len(data) < 9 or data[0] != _QUANT:
            return False
        rows, block = struct.unpack_from("<II", data, 1)
        n = rows * _LANES
        return (block > 0 and rows % block == 0 and n <= size
                and len(data) == 9 + n * np.dtype(self._qdtype).itemsize
                + 8 * (rows // block) + 4 * (size - n))

    def _parse(self, data: bytes):
        """Split a quantised container into its typed views (zero-copy)."""
        rows, block = struct.unpack_from("<II", data, 1)
        nb = rows // block
        off = 9
        q = np.frombuffer(data, self._qdtype, rows * _LANES, off
                          ).reshape(rows, _LANES)
        off += rows * _LANES * np.dtype(self._qdtype).itemsize
        scale = np.frombuffer(data, np.float32, nb, off)
        off += 4 * nb
        mins = np.frombuffer(data, np.float32, nb, off)
        off += 4 * nb
        tail = np.frombuffer(data, np.float32, offset=off)
        return rows, block, q, scale, mins, tail

    def decode(self, data: bytes, shape: Tuple[int, ...],
               dtype: np.dtype) -> np.ndarray:
        if data[0] == _RAW:
            return np.frombuffer(data, dtype=dtype, offset=1
                                 ).reshape(shape).copy()
        _rows, block, q, scale, mins, tail = self._parse(data)
        head = np.asarray(self._decode_head(q, scale, mins, block))
        return np.concatenate([head.reshape(-1), tail]).astype(
            dtype, copy=False).reshape(shape)

    def _warm_launches(self, rows: int, block: int) -> int:
        """Run every launch shape of this geometry (each power of two up to
        :func:`launch_cap`) on zeros at its first decode, so that no later
        decode compiles; returns the cap.  Warm geometries take no lock."""
        cap = launch_cap(rows)
        if (rows, block) in self._warm:
            return cap
        with self._warm_lock:
            batch = 1
            while (rows, block) not in self._warm and batch <= cap:
                stats = np.zeros((batch, rows // block), np.float32)
                self._decode_head(
                    np.zeros((batch, rows, _LANES), self._qdtype), stats,
                    stats, block).block_until_ready()
                batch *= 2
            self._warm.add((rows, block))
        return cap

    def decode_batch(self, datas: Sequence[bytes],
                     shapes: Sequence[Tuple[int, ...]],
                     dtype: np.dtype) -> List[np.ndarray]:
        """Batched inverse: equal-geometry quantised payloads (all interior
        chunks of one array) decode through one kernel launch per
        :func:`launch_cap` of them, each at :func:`launch_batch` of its
        size (the rows past the chunks are zeros, decoded and dropped), in
        the stages ``codec.decode.stack`` (parse, stack),
        ``.launch``, ``.d2h`` and ``.unpack`` (tail, dtype, shape), as in
        :meth:`encode_batch`.  Each launch is counted in the ambient
        counter ``codec.decode_launches``."""
        out: List[np.ndarray] = [None] * len(datas)  # type: ignore[list-item]
        groups: Dict[Tuple, List[int]] = {}
        for i, (d, s) in enumerate(zip(datas, shapes)):
            if d[0] == _RAW:
                out[i] = np.frombuffer(d, dtype=dtype, offset=1
                                       ).reshape(s).copy()
            else:
                rows, block = struct.unpack_from("<II", d, 1)
                groups.setdefault((tuple(s), rows, block), []).append(i)
        for (shape, rows, block), group in groups.items():
            cap = self._warm_launches(rows, block)
            for lo in range(0, len(group), cap):
                idxs = group[lo:lo + cap]
                obs_count("codec.decode_launches")
                with obs_span("codec.decode.stack", chunks=len(idxs)):
                    parsed = [self._parse(datas[i]) for i in idxs]
                    q, scale, mins = (_stack_padded([p[j] for p in parsed])
                                      for j in (2, 3, 4))
                with obs_span("codec.decode.launch"):
                    heads = self._decode_head(q, scale, mins, block)
                with obs_span("codec.decode.d2h"):
                    heads = np.asarray(heads)
                with obs_span("codec.decode.unpack"):
                    for k, i in enumerate(idxs):
                        out[i] = np.concatenate(
                            [heads[k].reshape(-1), parsed[k][5]]).astype(
                                dtype, copy=False).reshape(shape)
        return out


def _stack_padded(parts: List[np.ndarray]) -> np.ndarray:
    """``np.stack(parts)`` with zero rows appended up to
    :func:`launch_batch` of their number."""
    batch = launch_batch(len(parts))
    if batch == len(parts):
        return np.stack(parts)
    out = np.zeros((batch,) + parts[0].shape, parts[0].dtype)
    np.stack(parts, out=out[:len(parts)])
    return out


CODECS: Dict[str, Codec] = {
    c.name: c for c in (RawCodec(), FieldQuantCodec(8), FieldQuantCodec(16))
}


def get_codec(name: str) -> Codec:
    try:
        return CODECS[name]
    except KeyError:
        raise ValueError(f"unknown tensorstore codec {name!r}; "
                         f"known: {sorted(CODECS)}") from None
