"""Drive the store's main path once on one TPU chip and check what it returns.

    python chip_smoke.py [--seed N]

Everything runs in this one process, which holds the chip:

* **device check** — JAX's first device must be a TPU, or the script exits
  non-zero before any phase; the field codec's encode and decode, compiled
  at the store's geometries, must hold the Mosaic kernel
  (``tpu_custom_call``), not the interpreter;
* **store** — one operational output step on the ECMWF IFS O1280
  octahedral grid (6,599,680 points per level) at the 13 pressure levels of
  WeatherBench 2: ``t`` (field16, chunks (1, 262144)) and ``z`` (field8,
  ``auto_chunks``) archive through :class:`ChunkedFieldStore` on the daos
  backend, commit, and a :class:`FieldServeEngine` with no chunk cache
  answers 8 product requests, so every read decodes on the chip;
* **ckpt** — tinyllama-1.1b at its published widths (f32, about 4.4 GB)
  saves through ``FDBCheckpointer(compress=True)`` (field8 on the chip),
  restores onto the device — the only copy of the weights there — and a
  :class:`ServeEngine` on the restored weights answers 4 requests of 16 new
  tokens.

Every chunk container is held to an independent numpy block quantiser:
codes within 1 of it, every value within half a level step of its block.
Fields and weights are made from ``--seed``; nothing outside the checkout is
read.  Each phase prints one JSON line (wall time, compile time, bytes,
checks); the last line is the device record ``{"ok": true, "device": ...}``.
No time here is a benchmark: it is one run, compilation included.
"""
from __future__ import annotations

import argparse
import json
import struct
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
#: grid points per level of the IFS O1280 octahedral reduced Gaussian grid
O1280_POINTS = 6_599_680
#: WeatherBench 2's 13 pressure levels (hPa)
WB2_LEVELS_HPA = (50, 100, 150, 200, 250, 300, 400, 500, 600, 700, 850, 925,
                  1000)
T_CHUNKS = 262_144
LANES = 128


def require(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {what}")


class CompileClock:
    """Sums XLA backend compile time, read around each phase."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


# -- reference --------------------------------------------------------------
def check_container(data: bytes, original: np.ndarray, decoded: np.ndarray,
                    bits: int, what: str = "chunk"):
    """Hold one quantised container and its decode to a numpy block
    quantiser run on the container's own (rows, block) header: codes
    within 1, decoded values within half a level step of their block, the
    float32 tail exact.  Returns (codes off by one, worst error over its
    bound)."""
    x = np.ascontiguousarray(original, np.float32).reshape(-1)
    y = np.ascontiguousarray(decoded, np.float32).reshape(-1)
    rows, block = struct.unpack_from("<II", data, 1)
    n = rows * LANES
    shift = 2 ** (bits - 1)
    head = x[:n].reshape(rows // block, block * LANES)
    mn, mx = head.min(axis=1), head.max(axis=1)
    scale = (mx - mn) / np.float32(2 ** bits - 1)
    safe = np.where(scale > 0, scale, np.float32(1))
    codes = np.clip(np.round((head - mn[:, None]) / safe[:, None])
                    - shift, -shift, shift - 1).astype(np.int32)
    q = np.frombuffer(data, np.int8 if bits == 8 else np.int16, n, 9
                      ).reshape(codes.shape)
    diff = np.abs(q.astype(np.int32) - codes)
    require(int(diff.max()) <= 1, f"codes of {what} within 1")
    err = np.abs(y[:n] - x[:n]).reshape(codes.shape).max(axis=1)
    # half a level step, plus a few float32 ulps of the block's values
    bound = (scale * np.float32(0.5 * (1 + 1e-5))
             + np.maximum(np.abs(mn), np.abs(mx)) * np.float32(1e-6))
    require(bool((err <= bound).all()), f"{what} within half a level step")
    require(np.array_equal(y[n:], x[n:]), f"tail of {what} exact")
    return (int(np.count_nonzero(diff)),
            float((err / np.where(bound > 0, bound, 1)).max()))


def check_array(arr, original: np.ndarray, decoded: np.ndarray) -> dict:
    """Hold every stored chunk of ``arr`` to :func:`check_container`; raw
    chunks must decode exactly."""
    fdb = arr.store.fdb
    bits = {"field8": 8, "field16": 16}.get(arr.meta.codec)
    stored = off_by_one = 0
    worst = 0.0
    for idx in arr.grid.all_indices():
        sl = arr.grid.chunk_slices(idx)
        data = fdb.retrieve(arr.chunk_ident(idx)).read()
        stored += len(data)
        if bits is None or data[0] == 0:
            require(np.array_equal(original[sl], decoded[sl]),
                    f"raw chunk {idx} round-trips")
            continue
        off, w = check_container(data, original[sl], decoded[sl], bits,
                                 f"chunk {idx}")
        off_by_one += off
        worst = max(worst, w)
    return {"stored_bytes": stored, "codes_off_by_one": off_by_one,
            "worst_err_over_bound": worst}


# -- phases -----------------------------------------------------------------
def make_fields(seed: int, levels: int, points: int):
    """Temperature (K) and geopotential (m^2 s^-2) on ``levels`` WB2
    pressure levels: a standard-atmosphere profile, a pole-to-equator
    contrast along the north-to-south point order, a zonal wave and noise."""
    rng = np.random.default_rng(seed)
    p = np.asarray(WB2_LEVELS_HPA[:levels], np.float32)
    sigma = (p / np.float32(1013.25)) ** np.float32(0.1903)
    t_level = np.maximum(np.float32(288.15) * sigma, np.float32(216.65))
    z_level = np.float32(9.80665 * 44330.8) * (1 - sigma)
    u = np.linspace(-1, 1, points, dtype=np.float32)
    wave = np.sin(np.float32(np.pi * 1280) * u)
    t = (t_level[:, None] + (25 * np.cos(np.float32(np.pi) * u) - 15
                             + 3 * wave)[None]
         + rng.standard_normal((levels, points), np.float32) * 0.5)
    z = (z_level[:, None] + (-3000 * u * u + 800 * wave)[None]
         + rng.standard_normal((levels, points), np.float32) * 5)
    return t.astype(np.float32), z.astype(np.float32)


def product_requests(levels: int, points: int):
    """8 product requests: regional windows, one full level and one
    strided subsample."""
    P = points
    return [
        ("t", (slice(None), slice(0, P // 20))),
        ("t", (slice(2, min(9, levels)), slice(P // 3, P // 3 + P // 25))),
        ("t", (slice(None), slice(P - P // 50, P))),
        ("z", (slice(None), slice(P // 10, P // 10 + P // 40))),
        ("z", (slice(0, min(4, levels)), slice(P // 2, P // 2 + P // 100))),
        ("z", (levels // 2, slice(None))),
        ("t", (levels - 1, slice(P // 4, P // 4 + P // 64))),
        ("t", (slice(None, None, 3), slice(None, None, 97))),
    ]


def phase_store(seed: int, levels: int = len(WB2_LEVELS_HPA),
                points: int = O1280_POINTS,
                t_chunks: int = T_CHUNKS) -> dict:
    from repro.core import FDBConfig
    from repro.data.pipeline import ChunkedFieldStore
    from repro.serve.fields import FieldRequest, FieldServeEngine

    t0 = time.perf_counter()
    t, z = make_fields(seed, levels, points)
    t_make = time.perf_counter() - t0
    store = ChunkedFieldStore(store="o1280", writer="io0",
                              fdb_config=FDBConfig(backend="daos"),
                              cache_bytes=0)
    try:
        t0 = time.perf_counter()
        store.put_field("t", t, chunks=(1, t_chunks), codec="field16")
        store.put_field("z", z, codec="field8")
        store.commit()
        t_archive = time.perf_counter() - t0

        engine = FieldServeEngine(store, wave_slots=8)
        requests = product_requests(levels, points)
        for rid, (name, sel) in enumerate(requests):
            engine.submit(FieldRequest(rid, name, sel, fill_missing=False))
        t0 = time.perf_counter()
        done = engine.run()
        t_serve = time.perf_counter() - t0
        require(engine.stats["errors"] == 0,
                f"no request failed: {[r.error for r in done if r.error]}")
        require(len(done) == len(requests) and all(r.done for r in done),
                "every request answered")

        t0 = time.perf_counter()
        arrays = {"t": store.open_field("t"), "z": store.open_field("z")}
        full = {k: a.read(fill_missing=False) for k, a in arrays.items()}
        t_read = time.perf_counter() - t0
        for req in done:
            require(np.array_equal(req.result, full[req.field][req.selection]),
                    f"request {req.rid} equals its slice of a full read")
        t0 = time.perf_counter()
        checks = {k: check_array(a, {"t": t, "z": z}[k], full[k])
                  for k, a in arrays.items()}
        t_check = time.perf_counter() - t0
        chunks = {k: list(a.chunks) for k, a in arrays.items()}
    finally:
        store.fdb.close()
    return {"phase": "store", "shape": [levels, points], "chunks": chunks,
            "raw_bytes": t.nbytes + z.nbytes,
            "stored_bytes": sum(c["stored_bytes"] for c in checks.values()),
            "requests": len(done),
            "served_bytes": sum(r.result.nbytes for r in done),
            "serve_errors": engine.stats["errors"], "checks": checks,
            "make_s": t_make, "archive_s": t_archive, "serve_s": t_serve,
            "full_read_s": t_read, "check_s": t_check}


def phase_ckpt(seed: int, cfg, new_tokens: int = 16,
               n_requests: int = 4) -> dict:
    from repro.core import FDBConfig
    from repro.models import lm
    from repro.serve import Request, ServeEngine
    from repro.train.checkpoint import FDBCheckpointer, _tensor_name

    t0 = time.perf_counter()
    params = lm.init_params(cfg, jax.random.PRNGKey(seed), jnp.float32)
    host = jax.tree.map(np.asarray, params)
    # the host copy is what is saved: free the device copy, so the
    # restored weights are the only ones on the chip
    jax.tree.map(lambda a: a.delete(), params)
    del params
    t_init = time.perf_counter() - t0
    raw_bytes = sum(a.nbytes for a in jax.tree.leaves(host))

    ck = FDBCheckpointer("chip-smoke", FDBConfig(backend="daos"),
                         compress=True, host="h0")
    try:
        t0 = time.perf_counter()
        ck.save(0, host)
        t_save = time.perf_counter() - t0
        t0 = time.perf_counter()
        restored = ck.restore(0, host)
        jax.block_until_ready(restored)
        t_restore = time.perf_counter() - t0

        t0 = time.perf_counter()
        stored = off_by_one = compressed = 0
        worst = 0.0
        for (path, orig), dev in zip(
                jax.tree_util.tree_flatten_with_path(host)[0],
                jax.tree.leaves(restored)):
            arr = ck.open_tensor(0, _tensor_name(path))
            c = check_array(arr, orig, np.asarray(dev))
            stored += c["stored_bytes"]
            off_by_one += c["codes_off_by_one"]
            worst = max(worst, c["worst_err_over_bound"])
            compressed += arr.meta.codec == "field8"
        t_check = time.perf_counter() - t0
    finally:
        ck.close()
    del host

    rng = np.random.default_rng(seed)
    engine = ServeEngine(cfg, restored, batch_slots=n_requests, max_len=64)
    for rid in range(n_requests):
        engine.submit(Request(rid, rng.integers(0, cfg.vocab_size, 8,
                                                dtype=np.int32),
                              max_new_tokens=new_tokens))
    t0 = time.perf_counter()
    done = engine.run()
    t_serve = time.perf_counter() - t0
    require(len(done) == n_requests, "every request answered")
    for r in done:
        ids = np.asarray(r.out_tokens)
        require(ids.shape == (new_tokens,)
                and bool(((ids >= 0) & (ids < cfg.vocab_size)).all()),
                f"request {r.rid} returns {new_tokens} ids in "
                f"[0, {cfg.vocab_size})")
    return {"phase": "ckpt", "model": cfg.name,
            "tensors": len(jax.tree.leaves(restored)),
            "field8_tensors": compressed, "raw_bytes": raw_bytes,
            "stored_bytes": stored,
            "checks": {"codes_off_by_one": off_by_one,
                       "worst_err_over_bound": worst},
            "requests": len(done),
            "new_tokens": sum(len(r.out_tokens) for r in done),
            "init_s": t_init, "save_s": t_save, "restore_s": t_restore,
            "check_s": t_check, "serve_s": t_serve}


# -- entry point ------------------------------------------------------------
def compile_codec(size: int, bits: int, batch: int = 1,
                  sharding=None) -> str:
    """Compile encode and decode for ``batch`` chunks of ``size`` elements
    at the codec's own geometry (``FieldQuantCodec._layout``), for
    ``sharding``'s device or the default one, and require the Mosaic
    kernel (``tpu_custom_call``) in both programs."""
    from repro.kernels import field_codec
    from repro.tensorstore.codec import FieldQuantCodec

    _n, rows, block = FieldQuantCodec._layout(size)
    lead = (batch,) if batch > 1 else ()
    nb = rows // block

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(lead + shape, dtype, sharding=sharding)

    encode = field_codec.field_encode.lower(
        spec((rows, LANES), jnp.float32), block=block, bits=bits,
        interpret=False).compile()
    decode = field_codec.field_decode.lower(
        spec((rows, LANES), jnp.int8 if bits == 8 else jnp.int16),
        spec((nb,), jnp.float32), spec((nb,), jnp.float32), block=block,
        bits=bits, interpret=False).compile()
    key = f"{batch}x{rows}x{LANES}/block{block}/bits{bits}"
    for name, c in (("encode", encode), ("decode", decode)):
        require("tpu_custom_call" in c.as_text(),
                f"{name} of {key} runs the Mosaic kernel")
    return key


def kernel_check() -> dict:
    """Compile the codec at the store's geometries for this device."""
    return {compile_codec(size, bits): "tpu_custom_call"
            for size, bits in ((T_CHUNKS, 16), (O1280_POINTS % T_CHUNKS, 16),
                               (13 * 12890, 8))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (first device: "
              f"{dev.platform}); nothing was run", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs import get_config
    from repro.core import reset_engines
    from repro.launch.cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    clock = CompileClock()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(json.dumps({"phase": "device", **device,
                      "compile_cache": cache_dir,
                      "kernels": kernel_check()}), flush=True)
    for run in (lambda: phase_store(args.seed),
                lambda: phase_ckpt(args.seed,
                                   get_config("tinyllama-1.1b"))):
        c0, t0 = clock.seconds, time.perf_counter()
        result = run()
        result["wall_s"] = time.perf_counter() - t0
        result["compile_s"] = clock.seconds - c0
        result["peak_device_bytes"] = (dev.memory_stats() or {}).get(
            "peak_bytes_in_use")
        print(json.dumps(result), flush=True)
        reset_engines()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
