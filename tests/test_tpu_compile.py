"""The field codec at the store's real chunk geometries.

Two halves share one table of cases — every geometry the O1280 store, the
chunked example and the batched write path produce:

* compiled for a described TPU v5e chip (no chip needed): Mosaic must
  accept encode and decode, and the compiled program must hold the kernel
  (``tpu_custom_call``), not an XLA fallback;
* run on the CPU (interpret mode): the round trip stays within half a
  level step of an independent numpy block quantiser, codes agree with it
  to within 1, and batched output is byte-identical to per-chunk output.

The compile check and the numpy quantiser are ``chip_smoke.py``'s own
(``compile_codec``, ``check_container``), so the chip run and these tests
hold the codec to one reference.  The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import hashlib
import struct

import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import field_codec, ref
from repro.tensorstore.codec import FieldQuantCodec

#: (case, chunk shapes encoded together, bits).  O1280 levels hold
#: 6,599,680 points: ``t`` is stored field16 in (1, 262144) chunks (edge
#: chunk 46,080 points), ``z`` field8 under auto_chunks, (13, 12890).
CASES = [
    ("o1280-interior", [(1, 262144)], 16),
    ("o1280-edge", [(1, 46080)], 16),
    ("o1280-auto-chunks", [(13, 12890)], 8),
    ("example-chunk", [(60, 90, 2)], 8),
    ("batched", [(2048, 128)] * 4, 8),
]
IDS = [c[0] for c in CASES]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("case,shapes,bits", CASES, ids=IDS)
def test_codec_compiles_for_v5e(smoke, one_chip, case, shapes, bits):
    smoke.compile_codec(int(np.prod(shapes[0])), bits, batch=len(shapes),
                        sharding=one_chip)


def _field(shape, seed):
    """A smooth field with noise, in a temperature-like range."""
    n = int(np.prod(shape))
    rng = np.random.default_rng(seed)
    x = 250 + 30 * np.sin(np.arange(n) * 2e-4) + rng.normal(0, 2, n)
    return x.astype(np.float32).reshape(shape)


@pytest.mark.parametrize("case,shapes,bits", CASES, ids=IDS)
def test_codec_roundtrip_at_store_geometry(smoke, case, shapes, bits):
    codec = FieldQuantCodec(bits)
    xs = [_field(s, seed) for seed, s in enumerate(shapes)]
    batched = codec.encode_batch(xs)
    assert batched == [codec.encode(x) for x in xs]
    decoded = codec.decode_batch(batched, shapes, np.dtype(np.float32))
    for data, x, y in zip(batched, xs, decoded):
        np.testing.assert_array_equal(
            y, codec.decode(data, x.shape, np.dtype(np.float32)))
        assert codec.describes(data, x.size)
        assert not codec.describes(data[:-1], x.size)
        assert field_codec.legal_block(*struct.unpack_from("<II", data, 1))
        smoke.check_container(data, x, y, bits)


@pytest.mark.parametrize("bits,digest", [
    (8, "373542dde6baa4886e6ff6ae002440d10ee28d9a432d21f21954a17b791b73dd"),
    (16, "e8957e0b487d4be947fa71325b3c2453cb5337b67091f72e94d775c032d8263c"),
])
def test_legal_geometry_container_bytes_pinned(bits, digest):
    """2048 rows at block 256 was already chip-legal, so its containers
    keep the bytes the codec wrote before blocks were held to the tile."""
    n = 2048 * 128
    x = (((np.arange(n) * 7919) % 10007).astype(np.float32)
         * np.float32(0.03) + np.float32(200.0)).reshape(2048, 128)
    data = FieldQuantCodec(bits).encode(x)
    assert struct.unpack_from("<II", data, 1) == (2048, 256)
    assert hashlib.sha256(data).hexdigest() == digest


@pytest.mark.parametrize("bits", [8, 16])
def test_sub_tile_block_container_still_decodes(bits):
    """Containers written with the old block rule carry blocks of 1, 2 or
    4 rows, which the chip's tiling refuses; they decode through the jnp
    reference decoder, singly and batched."""
    codec = FieldQuantCodec(bits)
    x = _field((60, 90, 2), 7)
    flat = x.reshape(-1)
    rows, block = 84, 4                      # the old rule's geometry
    q, s, m = ref.field_encode_ref(jnp.asarray(flat[:rows * 128]
                                               ).reshape(rows, 128),
                                   block=block, bits=bits)
    data = codec._container(rows, block, q, s, m, flat[rows * 128:])
    expect = np.concatenate([np.asarray(ref.field_decode_ref(
        q, s, m, block=block, bits=bits)).reshape(-1), flat[rows * 128:]])
    y = codec.decode(data, x.shape, np.dtype(np.float32))
    np.testing.assert_allclose(y.reshape(-1), expect, rtol=1e-6)
    [yb] = codec.decode_batch([data], [x.shape], np.dtype(np.float32))
    np.testing.assert_array_equal(yb, y)
    bound = np.asarray(ref.codec_error_bound(
        jnp.asarray(flat[:rows * 128]).reshape(rows, 128), block, bits))
    err = np.abs(y.reshape(-1) - flat)[:rows * 128].reshape(rows // block, -1)
    assert (err.max(axis=1) <= bound + np.abs(flat).max() * 1e-6).all()
