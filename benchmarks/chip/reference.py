"""The plain reference: a numpy block quantiser, and the selection of a
stored array it would return.

It imports nothing of the program and takes nothing the program made: it
quantises the original values itself, chunk by chunk, on the chunk grid
of the configuration file, with the block geometry the store's codec
documents (128-value rows; blocks of 256, 128, 64, 32, 16 or 8 rows, the
largest that divides the rows, or all of them; chunks over 256 rows
quantise their rows down to a multiple of 8 and keep the rest as float32).

A stored array's answer is held to it by :func:`err_ratio`: the worst
error of the answer against the original values, over the worst error of
the reference's own answer to the same selection.  The block geometry
sets both errors alike, so a store that keeps the declared bits reads
about 1 whatever tiling it uses, and one that keeps fewer bits reads the
ratio of their level steps: about 17 for 4 bits where 8 are declared,
about 257 for 8 where 16 are.
"""
from __future__ import annotations

import itertools
from typing import Dict, Sequence, Tuple

import numpy as np

LANES = 128
_BLOCKS = (256, 128, 64, 32, 16, 8)
_ROW_TILE = 8


def layout(size: int) -> Tuple[int, int, int]:
    """(quantised values, quantised rows, block rows) of a chunk of
    ``size`` values."""
    rows = size // LANES
    if rows > _BLOCKS[0]:
        rows -= rows % _ROW_TILE
    block = next((b for b in _BLOCKS if rows and rows % b == 0), rows)
    return rows * LANES, rows, block


def quantise(values: np.ndarray, bits: int) -> np.ndarray:
    """The values a ``bits``-bit block quantiser returns for one chunk:
    per block, codes ``round((x - min) / scale)`` with ``scale = (max -
    min) / (2**bits - 1)``, restored as ``code * scale + min``; values past
    the quantised rows, and chunks under two rows, stay exact."""
    x = np.ascontiguousarray(values, np.float32).reshape(-1)
    if x.size < 2 * LANES:
        return x.reshape(values.shape).copy()
    n, rows, block = layout(x.size)
    head = x[:n].reshape(rows // block, block * LANES)
    mn, mx = head.min(axis=1), head.max(axis=1)
    scale = (mx - mn) / np.float32(2 ** bits - 1)
    safe = np.where(scale > 0, scale, np.float32(1))
    shift = np.float32(2 ** (bits - 1))
    codes = np.clip(np.round((head - mn[:, None]) / safe[:, None]) - shift,
                    -shift, shift - 1)
    out = np.empty_like(x)
    out[:n] = ((codes + shift) * scale[:, None] + mn[:, None]).reshape(-1)
    out[n:] = x[n:]
    return out.reshape(values.shape)


class RefArray:
    """One stored array as the reference sees it: the original values, the
    chunk grid and the declared bits.  Chunks are quantised when first
    needed and kept."""

    def __init__(self, values: np.ndarray, chunks: Sequence[int], bits: int):
        self.values = values
        self.chunks = tuple(int(c) for c in chunks)
        self.bits = bits
        self._done: Dict[Tuple[int, ...], np.ndarray] = {}

    def _chunk(self, idx: Tuple[int, ...]) -> np.ndarray:
        out = self._done.get(idx)
        if out is None:
            sl = tuple(slice(i * c, (i + 1) * c)
                       for i, c in zip(idx, self.chunks))
            out = self._done[idx] = quantise(self.values[sl], self.bits)
        return out

    def select(self, sel: Sequence) -> Tuple[np.ndarray, np.ndarray]:
        """(original values, reference answer) of a selection of ints and
        ascending slices, shaped as numpy would shape ``values[sel]``."""
        sel = tuple(sel) + (slice(None),) * (self.values.ndim - len(sel))
        # per axis: (chunk id, slice of the answer, slice of the chunk)
        segments, squeeze = [], []
        for a, (s, n, c) in enumerate(zip(sel, self.values.shape,
                                          self.chunks)):
            if isinstance(s, (int, np.integer)):
                s = slice(int(s) % n, int(s) % n + 1)
                squeeze.append(a)
            start, stop, step = s.indices(n)
            if step <= 0:
                raise ValueError(f"selection {sel} does not ascend")
            ix = np.arange(start, stop, step)
            owner = ix // c
            cuts = np.flatnonzero(np.diff(owner)) + 1
            axis = []
            for p in np.split(np.arange(ix.size), cuts):
                cid = int(owner[p[0]])
                lo = int(ix[p[0]]) - cid * c
                axis.append((cid, slice(int(p[0]), int(p[-1]) + 1),
                             slice(lo, lo + step * (p.size - 1) + 1, step)))
            segments.append(axis)
        full = tuple(slice(*s.indices(n)) if isinstance(s, slice) else
                     slice(int(s) % n, int(s) % n + 1)
                     for s, n in zip(sel, self.values.shape))
        truth = np.ascontiguousarray(self.values[full])
        ref = np.empty_like(truth)
        for combo in itertools.product(*segments):
            ref[tuple(x[1] for x in combo)] = self._chunk(
                tuple(x[0] for x in combo))[tuple(x[2] for x in combo)]
        if squeeze:
            truth, ref = truth.squeeze(tuple(squeeze)), ref.squeeze(
                tuple(squeeze))
        return truth, ref


#: what :func:`err_ratio` reads for an answer that cannot be right
WRONG = 1e30


def err_ratio(answer, truth: np.ndarray, ref: np.ndarray) -> float:
    """Worst error of ``answer`` over the reference's worst error on the
    same values; :data:`WRONG` for an answer of another shape, with a
    value that is not finite, or with an error where the reference is
    exact; 0 where both are exact."""
    answer = np.asarray(answer)
    if answer.shape != truth.shape:
        return WRONG
    worst = np.max(np.abs(answer.astype(np.float64) - truth), initial=0.0)
    if not np.isfinite(worst):
        return WRONG
    base = np.max(np.abs(ref.astype(np.float64) - truth), initial=0.0)
    if base == 0:
        return 0.0 if worst == 0 else WRONG
    return min(float(worst / base), WRONG)
