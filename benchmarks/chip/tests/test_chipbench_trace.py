"""The reduction from a profiler trace to device time, the roofline's byte
counts and the table of peaks."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[3]))

from benchmarks.chip import trace_reduce  # noqa: E402
from benchmarks.chip.context import Context  # noqa: E402
from benchmarks.chip.peaks import PEAKS, codec_hbm_bytes, peaks  # noqa: E402
from benchmarks.chip.trace_reduce import Device, Trace  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data" / "tiny.xplane.pb"


def _trace():
    # window 0..100 ns; ops at 10-30 (encode), 20-40 (encode), 60-70
    dev = Device(ops=[("q", 10, 30, "jit_field_encode"),
                      ("q", 20, 40, "jit_field_encode"),
                      ("d", 60, 70, "jit_field_decode"),
                      ("x", 95, 120, "jit_other")],
                 modules=[])
    host = [("bench.window", 0, 100), ("bench.commit", 38, 58),
            ("PjitFunction", 40, 60), ("bench.read_window", 70, 100)]
    return Trace((0, 100), [dev], host)


def test_busy_union_and_module_time():
    t = _trace()
    assert t.window_s == pytest.approx(100e-9)
    assert t.busy_s == pytest.approx((30 + 10 + 5) * 1e-9)
    assert t.module_seconds("jit_field_encode") == pytest.approx(40e-9)
    assert t.top_ops(2) == [["q", pytest.approx(40e-9)],
                            ["d", pytest.approx(10e-9)]]


def test_idle_gaps_are_named_by_the_host_span_open_in_them():
    gaps = _trace().idle_gaps()
    assert [round(s * 1e9) for _n, s in gaps] == [25, 20, 10]
    names = dict((round(s * 1e9), n) for n, s in gaps)
    assert names[25] == "bench.read_window"     # 70-95
    assert names[20] == "bench.commit"          # 40-60: own span first
    assert names[10] == "none"                  # 0-10: only the window


def test_roofline_counts_the_fields_bytes():
    assert codec_hbm_bytes(1000, 8) == 5000
    assert codec_hbm_bytes(1000, 16) == 6000
    ctx = Context(trace=_trace(), spans=[], counters={},
                  codec={"encode_hbm_bytes": 819}, work=[], window_s=1.0,
                  peaks={"hbm_bytes_per_s": 819e9})
    # 819 bytes in 40 ns at 819 GB/s: 1 ns of 40
    assert ctx.roofline("jit_field_encode", "encode_hbm_bytes") == \
        pytest.approx(2.5)
    assert ctx.roofline("jit_field_decode", "decode_hbm_bytes") is None
    assert ctx.idle_share() == pytest.approx(55.0)


def test_unknown_device_kind_raises():
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        peaks("cpu")
    assert set(PEAKS) == {"TPU v5 lite"}


def test_recorded_chip_trace():
    t = trace_reduce.reduce(str(RECORDED))
    assert len(t.devices) == 1
    assert 0 < t.busy_s < t.window_s
    # a module's span holds its operations and the gaps between them
    enc = t.module_seconds("jit_field_encode")
    assert t.busy_s <= enc < t.window_s
    assert t.top_ops(10) and all(s > 0 for _n, s in t.top_ops(10))
    gaps = t.idle_gaps(10)
    assert len(gaps) == 10 and all(n != "bench.window" for n, _s in gaps)
    assert sum(s for _n, s in gaps) <= t.window_s - t.busy_s + 1e-9
