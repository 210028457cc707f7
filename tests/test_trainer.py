"""Trainer integration: checkpoint/restart, async archival, stragglers,
elastic re-planning."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import FDBConfig
from repro.data import FDBDataPipeline, SyntheticTokens
from repro.train.checkpoint import FDBCheckpointer
from repro.train.optimizer import AdamWConfig, adamw_init, adamw_update
from repro.train.trainer import (StragglerMonitor, Trainer, WorkerFailure,
                                 reassign_shard, run_with_restarts)


@pytest.fixture
def tiny_setup():
    cfg = get_smoke_config("tinyllama-1.1b")
    data = SyntheticTokens(cfg.vocab_size, 16, seed=3)
    return cfg, data


def test_adamw_descends_quadratic():
    params = {"w": jnp.asarray([3.0, -2.0])}
    opt = adamw_init(params)
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0)
    for _ in range(100):
        grads = {"w": 2 * params["w"]}
        params, opt, _ = adamw_update(grads, opt, params, cfg)
    assert float(jnp.abs(params["w"]).max()) < 0.2


def test_checkpoint_roundtrip_async(tiny_setup):
    cfg, data = tiny_setup
    from repro.models import lm
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    ck = FDBCheckpointer("async-run", FDBConfig(backend="rados"),
                         asynchronous=True)
    ck.save(7, params)
    ck.wait()
    restored = ck.restore(7, params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ck.close()


@pytest.mark.parametrize("chunked", [True, False])
def test_checkpoint_compressed_roundtrip(tiny_setup, chunked):
    cfg, _ = tiny_setup
    from repro.models import lm
    params = lm.init_params(cfg, jax.random.PRNGKey(1))
    ck = FDBCheckpointer("comp-run", FDBConfig(backend="daos"),
                         compress=True, chunked=chunked)
    ck.save(1, params)
    restored = ck.restore(1, params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        a, b = np.asarray(a), np.asarray(b)
        if a.size >= 1024 and a.ndim >= 2:
            rng = a.max() - a.min()
            assert np.abs(a - b).max() <= rng / 255 * 0.51 + 1e-6
        else:
            np.testing.assert_array_equal(a, b)
    ck.close()


def _headerless_blob(arr: np.ndarray, block: int) -> np.ndarray:
    """A compressed shard blob as older runs wrote it: int8 codes, scales,
    mins and the float32 tail, with no header."""
    from repro.kernels import ref
    flat = arr.reshape(-1)
    n = flat.size // 128 * 128
    q, s, m = ref.field_encode_ref(jnp.asarray(flat[:n]).reshape(-1, 128),
                                   block=block)
    return np.concatenate([
        np.asarray(q, np.int8).reshape(-1).view(np.uint8),
        np.asarray(s, np.float32).view(np.uint8),
        np.asarray(m, np.float32).view(np.uint8),
        flat[n:].astype(np.float32).view(np.uint8)])


@pytest.mark.parametrize("rows,block", [(84, 4), (383, 1), (766, 2),
                                        (1532, 4), (2048, 256)])
def test_headerless_compressed_blob_restores(monkeypatch, rows, block):
    """Blobs of older runs restore by the block rule they were written
    with, including sizes where a container of today's layout would have
    the same number of bytes without its header (383 x 128: 383 rows of
    block 1, or 376 rows of block 8 plus an 896-value tail)."""
    x = np.random.default_rng(rows).normal(250, 20, (rows, 128)).astype(
        np.float32)
    params = {"w": x}
    ck = FDBCheckpointer("legacy-run", FDBConfig(backend="daos"),
                         compress=True, chunked=False)
    monkeypatch.setattr(ck, "_compress", lambda a: _headerless_blob(a, block))
    ck.save(1, params)
    got = np.asarray(ck.restore(1, params)["w"])
    ck.close()
    from repro.kernels import ref
    q, s, m = ref.field_encode_ref(jnp.asarray(x), block=block)
    expect = np.asarray(ref.field_decode_ref(q, s, m, block=block))
    np.testing.assert_allclose(got, expect, rtol=1e-6)
    bound = np.asarray(ref.codec_error_bound(jnp.asarray(x), block))
    err = np.abs(got - x).reshape(rows // block, -1).max(axis=1)
    assert (err <= bound + np.abs(x).max() * 1e-6).all()


def test_compressed_blob_of_unknown_size_is_refused():
    """A blob that is neither a field8 container nor a headerless blob of
    the tensor's size raises instead of decoding to wrong values."""
    ck = FDBCheckpointer("legacy-run", FDBConfig(backend="daos"),
                         chunked=False)
    ref = np.zeros((383, 128), np.float32)
    blob = ck._compress(ref + np.arange(128, dtype=np.float32))
    assert blob[0] == 1 and ck._decompress(blob, ref).shape == (ref.size,)
    with pytest.raises(ValueError, match="neither"):
        ck._decompress(blob[:-4], ref)
    with pytest.raises(ValueError, match="neither"):
        ck._decompress(_headerless_blob(ref, 1)[:-8], ref)
    ck.close()


def test_restart_resumes_from_checkpoint(tiny_setup):
    cfg, data = tiny_setup
    ck = FDBCheckpointer("restart-run", FDBConfig(backend="daos"))
    fail = {8}

    def fault(step):
        if step in fail:
            fail.discard(step)
            raise WorkerFailure("chaos")

    def make():
        return Trainer(cfg, None, AdamWConfig(lr=1e-3), checkpointer=ck,
                       ckpt_every=4, batch_fn=lambda s: data.batch(s, 2),
                       fault_hook=fault)

    tr = run_with_restarts(make, n_steps=12, max_restarts=1)
    assert tr.step == 12
    assert all(math.isfinite(m["loss"]) for m in tr.metrics)
    assert 12 in ck.available_steps()


def test_straggler_monitor_flags_outliers():
    mon = StragglerMonitor(threshold=2.0)
    for _ in range(10):
        assert not mon.observe(0.1)
    assert mon.observe(0.5)
    assert mon.flagged == 1


def test_reassign_shard_deterministic_and_total():
    n = 16
    for epoch in range(3):
        targets = {reassign_shard(h, n, epoch) for h in range(n)}
        assert targets == set(range(n))     # a permutation — no data loss


def test_elastic_replan():
    import os
    if "pod" in str(jax.devices()):
        pass
    from repro.launch.elastic import reassign_data_shards
    out = reassign_data_shards(10, [0, 2, 5])
    assert sorted(s for lst in out.values() for s in lst) == list(range(10))
    assert max(len(v) for v in out.values()) \
        - min(len(v) for v in out.values()) <= 1


def test_pipeline_contended_producer_consumer(tiny_setup):
    cfg, data = tiny_setup
    import threading
    pipe = FDBDataPipeline("corpus", fdb_config=FDBConfig(backend="daos"))
    n = 8
    got = []

    def producer():
        for i in range(n):
            pipe.put_batch(0, i, data.batch(i, 2))
            pipe.commit()

    t = threading.Thread(target=producer)
    t.start()
    # poll concurrently with the producer: only ever see complete batches
    import time
    deadline = time.time() + 30
    while len(got) < n and time.time() < deadline:
        b = pipe.get_batch(0, len(got))
        if b is not None:
            got.append(b)
    t.join()
    assert len(got) == n
    for i, b in enumerate(got):
        np.testing.assert_array_equal(b["tokens"], data.batch(i, 2)["tokens"])
