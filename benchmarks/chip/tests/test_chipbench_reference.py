"""The benchmark's numpy reference against the store's codec on the CPU:
it agrees with what the codec keeps, refuses a codec that keeps fewer
bits than declared, and selects exactly what numpy would."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[3]))

from benchmarks.chip.reference import (WRONG, RefArray, err_ratio,  # noqa
                                       layout, quantise)


def _field(shape, seed=0):
    rng = np.random.default_rng(seed)
    return (250 + 30 * np.sin(np.linspace(0, 9, int(np.prod(shape))))
            .reshape(shape) + rng.standard_normal(shape)).astype(np.float32)


@pytest.mark.parametrize("size", [256, 300, 1000, 32768, 46080, 167570,
                                  2 * 16384 + 10800])
def test_layout_is_the_codecs(size):
    from repro.tensorstore.codec import FieldQuantCodec
    assert layout(size) == FieldQuantCodec._layout(size)


@pytest.mark.parametrize("codec,bits", [("field16", 16), ("field8", 8)])
def test_reference_agrees_with_the_codec(codec, bits):
    from repro.tensorstore.codec import get_codec
    x = _field((3, 8000))
    c = get_codec(codec)
    y = c.decode(c.encode(x), x.shape, x.dtype)
    ref = quantise(x, bits)
    assert err_ratio(y, x, ref) <= 1.0 + 1e-6
    # the same codes: values differ by float32 rounding at most
    assert np.mean(np.isclose(y, ref, rtol=1e-6, atol=0)) > 0.999


def test_codec_at_8_bits_where_16_are_declared_is_refused():
    from repro.tensorstore.codec import get_codec
    x = _field((3, 8000))
    c = get_codec("field8")
    y = c.decode(c.encode(x), x.shape, x.dtype)
    truth, ref = RefArray(x, (3, 8000), 16).select(())
    assert err_ratio(y, truth, ref) > 100


@pytest.mark.parametrize("sel", [
    (), (1,), (slice(0, 2), slice(100, 7000)), (2, slice(None)),
    (slice(None, None, 2), slice(5, None, 97)), (0, 12345)])
def test_select_is_numpys(sel):
    x = _field((3, 20000), seed=1)
    arr = RefArray(x, (1, 4096), 16)
    truth, ref = arr.select(sel)
    assert np.array_equal(truth, x[sel])
    whole = np.concatenate([quantise(x[i:i + 1, j:j + 4096], 16)
                            for i in range(3) for j in range(0, 20000, 4096)
                            ], axis=1)
    whole = np.concatenate(np.split(whole, 3, axis=1), axis=0)
    assert np.array_equal(ref, whole[sel])


def test_descending_selection_is_refused():
    with pytest.raises(ValueError):
        RefArray(_field((2, 600)), (1, 300), 8).select((slice(None, None, -1),))


def test_err_ratio_reads_wrong_answers_as_wrong():
    x = _field((2, 1000))
    ref = quantise(x, 8)
    assert err_ratio(ref, x, ref) == 1.0
    assert err_ratio(ref[:1], x, ref) == WRONG
    bad = ref.copy()
    bad[0, 0] = np.nan
    assert err_ratio(bad, x, ref) == WRONG
    assert err_ratio(x, x, x) == 0.0
    assert err_ratio(ref, x, x) == WRONG
