"""Test config: force an 8-device virtual CPU platform so collective /
sharding tests run without TPU hardware (mirrors the reference's
multi-process-on-localhost nightly pattern, SURVEY.md §7 test strategy).
Must set XLA flags before jax initializes."""
import os
import sys

# make `import mxnet_tpu` work no matter where pytest is invoked from
# (pytest.ini pins rootdir, this pins the import path)
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as onp
import pytest


def _load_slow_ids():
    path = os.path.join(os.path.dirname(__file__), "slow_tests.txt")
    try:
        with open(path) as fh:
            return {ln.strip() for ln in fh
                    if ln.strip() and not ln.startswith("#")}
    except OSError:
        return set()


def pytest_collection_modifyitems(config, items):
    """Apply the measured fast/slow split (VERDICT r4 item 9): every
    collected test gets exactly one of the two markers; membership comes
    from tests/slow_tests.txt (regenerate with tools/gen_slow_marks.py
    after perf-relevant suite changes).  Unlisted tests default to fast —
    new tests enter the gate until a regeneration measures them."""
    slow_ids = _load_slow_ids()
    seen = set()
    # the op-conformance sweep is ~1900 nodes; the gate keeps a 1/8
    # rotation (structural, so newly registered ops join automatically)
    # while measured-slow nodes stay out of the gate regardless
    conf_idx = 0
    for item in items:
        seen.add(item.nodeid)
        slow = item.nodeid in slow_ids
        if "test_op_conformance" in item.nodeid and \
                "::test_" in item.nodeid and "[" in item.nodeid:
            slow = slow or (conf_idx % 8 != 0)
            conf_idx += 1
        if slow:
            item.add_marker(pytest.mark.slow)
        else:
            item.add_marker(pytest.mark.fast)
    # staleness guard: ids that no longer collect mean the list rotted
    # (only meaningful when the whole suite was collected — single-file
    # runs legitimately miss most listed ids)
    n_files = len({i.nodeid.split("::")[0] for i in items})
    if n_files >= 30:
        dead = slow_ids - seen
        if dead:
            import warnings
            warnings.warn(
                f"tests/slow_tests.txt lists {len(dead)} node ids that no "
                f"longer exist (e.g. {sorted(dead)[:3]}); regenerate with "
                "tools/gen_slow_marks.py")


@pytest.fixture(autouse=True)
def _seed():
    import mxnet_tpu as mx
    onp.random.seed(0)
    mx.random.seed(0)
    yield
