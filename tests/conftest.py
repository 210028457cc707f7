import importlib.util
import os
from pathlib import Path

import pytest

from repro.core import FDB, FDBConfig, reset_engines
from repro.core.engine.meter import GLOBAL_METER
from repro.obs.trace import GLOBAL_TRACER
from repro.tensorstore import TensorStore

#: the four simulated deployments every cross-backend suite sweeps —
#: hoisted here so test modules share one parametrization (the `backend`
#: fixture) instead of each carrying its own copy
BACKENDS = ("daos", "rados", "posix", "s3")

#: one knob reproduces any chaos failure: the seed below feeds
#: FaultInjector coin flips and RetryPolicy jitter in the fault/workflow
#: suites, and is printed in the pytest header — rerun with
#: REPRO_TEST_SEED=<printed value> to replay the exact schedule
TEST_SEED = int(os.environ.get("REPRO_TEST_SEED", "0"))


def pytest_report_header(config):
    return (f"REPRO_TEST_SEED={TEST_SEED} "
            f"(chaos jitter seed; set the env var to reproduce)")


@pytest.fixture(params=BACKENDS)
def backend(request):
    """Sweep all four simulated backends.  A test needing a subset
    overrides with ``@pytest.mark.parametrize("backend", [...])``."""
    return request.param


@pytest.fixture
def test_seed():
    """The suite-wide chaos seed (``REPRO_TEST_SEED``, default 0)."""
    return TEST_SEED


@pytest.fixture
def make_fdb(tmp_path):
    """Factory for FDB clients on this test's private deployment root.
    Config kwargs (``io_parallelism=...``) flow to :class:`FDBConfig`;
    ``faults``/``retry``/``tracer`` flow to the client."""
    def _make(backend, schema="tensor", *, faults=None, retry=None,
              tracer=None, **cfg_kw):
        cfg_kw.setdefault("root", str(tmp_path / "fdb"))
        return FDB(FDBConfig(backend=backend, schema=schema, **cfg_kw),
                   faults=faults, retry=retry, tracer=tracer)
    return _make


@pytest.fixture
def make_store(make_fdb):
    """Factory for ``(fdb, TensorStore)`` pairs on the shared test
    deployment — the tensorstore suite's idiom."""
    def _make(backend, array="a", writer="w0", **kw):
        fdb = make_fdb(backend, **kw)
        return fdb, TensorStore(fdb, {"store": "s", "array": array,
                                      "writer": writer})
    return _make


@pytest.fixture(autouse=True)
def fresh_engines():
    """Each test gets pristine in-process storage engines + meter, and a
    disabled, empty global tracer."""
    reset_engines()
    GLOBAL_METER.reset()
    GLOBAL_TRACER.disable()
    GLOBAL_TRACER.clear()
    yield
    reset_engines()
    GLOBAL_METER.reset()
    GLOBAL_TRACER.disable()
    GLOBAL_TRACER.clear()


#: modules whose tests run under the dynamic protocol sanitizer
#: (repro.analysis.protocol).  The tracer is force-enabled only for the
#: lease suite — test_obs asserts the disabled-by-default contract, so
#: there the guard still records lock order but sees no spans.
_PROTOCOL_GUARDED = {"test_leases", "test_obs"}
_TRACED = {"test_leases"}


@pytest.fixture(autouse=True)
def protocol_check(request, fresh_engines):
    """Replay every guarded test's trace window through the concurrency
    protocol checker and fail on any contract violation (archive without
    a live lease, release-before-flush, stale RMW, lock-order cycles,
    executor over window)."""
    module = request.module.__name__.rpartition(".")[2]
    if module not in _PROTOCOL_GUARDED:
        yield
        return
    from repro.analysis.protocol import protocol_guard
    if module in _TRACED:
        GLOBAL_TRACER.enable()
    with protocol_guard(GLOBAL_TRACER):
        yield


@pytest.fixture(scope="session")
def smoke():
    """The root ``chip_smoke.py`` as a module: its phases, and the numpy
    block quantiser and codec compile check the tests share with it."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", Path(__file__).resolve().parents[1] / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def nwp_identifier():
    return {
        "class": "od", "expver": "0001", "stream": "oper",
        "date": "20231201", "time": "1200", "type": "ef", "levtype": "sfc",
        "step": "1", "number": "13", "levelist": "1", "param": "v",
    }
