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


def build_xspace(planes):
    """A serialized xspace (the bytes of an ``.xplane.pb``) from
    ``[{"name": plane, "lines": {line: [(event name, start_ps, dur_ps,
    {metadata stat: value}), ...]}}]`` — built by JAX's own serializer, so
    what reads it is checked against the real wire format.  As in a v5e
    trace, an operation's provenance (``tf_op``), category, FLOPs and
    bytes are stats of its event METADATA, keyed by the event's name."""
    from jax.profiler import ProfileData

    def quoted(s):
        return '"' + str(s).replace("\\", "\\\\").replace('"', '\\"') + '"'

    out = []
    for pid, plane in enumerate(planes, 1):
        stat_ids, meta_ids, metas, lines = {}, {}, [], []
        for lid, (line, events) in enumerate(plane["lines"].items(), 1):
            rows = []
            for name, start, dur, *rest in events:
                if name not in meta_ids:
                    meta_ids[name] = len(meta_ids) + 1
                    stats = []
                    for k, v in (rest[0] if rest else {}).items():
                        sid = stat_ids.setdefault(k, len(stat_ids) + 1)
                        kind = "str_value: " + quoted(v) \
                            if isinstance(v, str) else f"int64_value: {v}"
                        stats.append(f"stats {{ metadata_id: {sid} {kind} }}")
                    short = name.split(" = ", 1)[0].lstrip("%")
                    metas.append(
                        f"event_metadata {{ key: {meta_ids[name]} value {{ "
                        f"id: {meta_ids[name]} name: {quoted(name)} "
                        f"display_name: {quoted(short)} "
                        f"{' '.join(stats)} }} }}")
                rows.append(f"events {{ metadata_id: {meta_ids[name]} "
                            f"offset_ps: {start} duration_ps: {dur} }}")
            lines.append(f"lines {{ id: {lid} name: {quoted(line)} "
                         f"{' '.join(rows)} }}")
        stat_meta = [f"stat_metadata {{ key: {i} value {{ id: {i} name: "
                     f"{quoted(k)} }} }}" for k, i in stat_ids.items()]
        out.append(f"planes {{ id: {pid} name: {quoted(plane['name'])} "
                   f"{' '.join(lines + metas + stat_meta)} }}")
    return ProfileData.text_proto_to_serialized_xspace("\n".join(out))


@pytest.fixture
def make_xspace():
    return build_xspace
