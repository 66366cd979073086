"""Test configuration.

All tests run on CPU with 8 virtual XLA devices so multi-chip sharding logic
is exercised without TPU hardware (mirrors the reference's strategy of
CPU/gloo multiprocess tests, SURVEY.md §4).  Env must be set before jax
import — conftest runs first, and worker subprocesses inherit it.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import gc  # noqa: E402
import sys  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _unfreeze_after_test():
    """A wrapper's restart freezes the heap that survived it
    (``inprocess/wrap.py``); without this the stores, sockets and threads one
    test left in cycles would stay permanent for the rest of its worker's run."""
    yield
    gc.unfreeze()


@pytest.fixture(autouse=True)
def _land_deferred_dumps_after_test():
    """A trip's and a ladder's black boxes are captured where they are asked
    for and written up to two seconds later (``telemetry/flight.py``); without
    this the writer thread would record one test's ``flight.dump.write`` in the
    ring the next test reads."""
    yield
    flight = sys.modules.get("tpu_resiliency.telemetry.flight")
    if flight is not None:
        flight.flush()


@pytest.fixture
def store_server():
    from tpu_resiliency.store import StoreServer

    server = StoreServer(host="127.0.0.1", port=0).start_in_thread()
    yield server
    server.stop()


@pytest.fixture
def native_store_server():
    from tpu_resiliency.store.native import NativeStoreServer

    server = NativeStoreServer(host="127.0.0.1", port=0).start()
    yield server
    server.stop()


@pytest.fixture
def store(store_server):
    from tpu_resiliency.store import StoreClient

    client = StoreClient("127.0.0.1", store_server.port, timeout=10.0)
    yield client
    client.close()
