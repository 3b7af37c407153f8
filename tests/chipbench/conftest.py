"""Every test of the benchmark's files starts with an empty event ring.

The entries difference ``len(telemetry.events("compile"))`` across their
window, and the ring holds 4,096 events of EVERY kind: in a worker process
that has run other serving tests first the ring is full, compile events are
pushed out while the window runs, and ``compiles_in_window`` reads negative
(PERF.md section 7).  A chip run is a fresh process; a test gets the same by
dropping the ring.
"""
import pytest


@pytest.fixture(autouse=True)
def _fresh_event_ring():
    from mxnet_tpu import telemetry
    telemetry.clear_events()
    yield
