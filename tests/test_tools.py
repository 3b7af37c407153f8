"""Tools + opperf tests (reference tools/ and benchmark/opperf coverage;
SURVEY.md L10, §6)."""
import os
import subprocess
import sys

import numpy as onp
import pytest

_ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _run(args, **kw):
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, cwd="/root/repo", env=_ENV, **kw)


@pytest.fixture
def image_tree(tmp_path):
    from PIL import Image
    root = tmp_path / "imgs"
    for cls in ("cat", "dog"):
        d = root / cls
        d.mkdir(parents=True)
        for i in range(3):
            arr = (onp.random.rand(20, 20, 3) * 255).astype(onp.uint8)
            Image.fromarray(arr).save(str(d / f"{i}.jpg"))
    return str(root)


class TestIm2Rec:
    def test_list_mode(self, image_tree, tmp_path):
        prefix = str(tmp_path / "d")
        r = _run(["tools/im2rec.py", prefix, image_tree, "--recursive",
                  "--list"])
        assert r.returncode == 0, r.stderr
        lines = open(prefix + ".lst").read().strip().splitlines()
        assert len(lines) == 6
        labels = {l.split("\t")[1] for l in lines}
        assert labels == {"0", "1"}

    def test_pack_and_read_back(self, image_tree, tmp_path):
        prefix = str(tmp_path / "d")
        r = _run(["tools/im2rec.py", prefix, image_tree, "--recursive",
                  "--resize", "16"])
        assert r.returncode == 0, r.stderr
        from mxnet_tpu.gluon.data.vision import ImageRecordDataset
        ds = ImageRecordDataset(prefix + ".rec")
        assert len(ds) == 6
        img, label = ds[0]
        assert min(img.shape[:2]) == 16
        assert label in (0.0, 1.0)


class TestParseLog:
    def test_parses_metrics(self, tmp_path):
        log = tmp_path / "t.log"
        log.write_text(
            "INFO:root:Epoch[0] Train-accuracy=0.5\n"
            "INFO:root:Epoch[0] Time cost=10.1\n"
            "INFO:root:Epoch[1] Train-accuracy=0.8\n"
            "INFO:root:Epoch[1] Validation-accuracy=0.75\n")
        r = _run(["tools/parse_log.py", str(log), "--format", "csv"])
        assert r.returncode == 0
        assert "train-accuracy" in r.stdout
        assert "0.75" in r.stdout

    def test_empty_log_errors(self, tmp_path):
        log = tmp_path / "e.log"
        log.write_text("nothing here\n")
        assert _run(["tools/parse_log.py", str(log)]).returncode == 1


class TestDiagnose:
    def test_runs(self):
        r = _run(["tools/diagnose.py"])
        assert r.returncode == 0
        assert "mxnet_tpu" in r.stdout
        assert "features" in r.stdout


class TestBandwidth:
    def test_kvstore_bandwidth(self):
        r = _run(["tools/bandwidth/measure.py", "--sizes", "65536",
                  "--repeats", "2"], timeout=180)
        assert r.returncode == 0, r.stderr[-500:]
        assert "GB/s" in r.stdout


class TestOpperf:
    def test_subset_runs(self):
        r = _run(["benchmark/opperf/opperf.py", "--ops", "dot", "relu",
                  "--runs", "2"], timeout=240)
        assert r.returncode == 0, r.stderr[-500:]
        assert "dot" in r.stdout and "relu" in r.stdout

    def test_python_api(self):
        from benchmark.opperf.opperf import run_op_benchmark
        res = run_op_benchmark(["sigmoid"], warmup=1, runs=2)
        assert res[0]["op"] == "sigmoid"
        assert "jit_ms" in res[0]


class TestRTC:
    def test_pallas_module_kernel(self):
        import mxnet_tpu as mx

        def addmul(x_ref, y_ref, o_ref):
            o_ref[...] = x_ref[...] * 2.0 + y_ref[...]

        mod = mx.rtc.PallasModule({"addmul": addmul})
        k = mod.get_kernel("addmul")
        x = mx.nd.array(onp.arange(8, dtype=onp.float32).reshape(2, 4))
        out = k([x, mx.nd.ones((2, 4))])
        onp.testing.assert_allclose(out.asnumpy(), x.asnumpy() * 2 + 1)

    def test_unknown_kernel_and_cuda_gate(self):
        import mxnet_tpu as mx
        from mxnet_tpu.base import MXNetError
        mod = mx.rtc.PallasModule({"k": lambda x_ref, o_ref: None})
        with pytest.raises(MXNetError):
            mod.get_kernel("missing")
        with pytest.raises(MXNetError):
            mx.rtc.CudaModule("source")


class TestSymbolicCheckers:
    def test_check_symbolic_forward_backward(self):
        import mxnet_tpu as mx
        from mxnet_tpu.test_utils import (check_symbolic_forward,
                                          check_symbolic_backward)
        x = onp.random.rand(3, 4).astype(onp.float32) - 0.5
        s = mx.sym.relu(mx.sym.var("x"))
        check_symbolic_forward(s, [x], [onp.maximum(x, 0)])
        check_symbolic_backward(s, [x], [onp.ones_like(x)],
                                [(x > 0).astype(onp.float32)])


class TestProfiler:
    def test_aggregate_stats_capture_and_pause(self, tmp_path):
        import mxnet_tpu as mx
        mx.profiler.set_config(filename=str(tmp_path / "prof.json"),
                               aggregate_stats=True)
        mx.profiler.start()
        a = mx.nd.array(onp.ones((8, 8), onp.float32))
        _ = mx.nd.dot(a, a)
        mx.profiler.pause()
        _ = a + 1  # excluded section
        mx.profiler.resume()
        _ = mx.nd.dot(a, a)
        mx.profiler.stop()
        table = mx.profiler.dumps()
        assert "dot" in table
        mx.profiler.dump()
        import json
        trace = json.load(open(str(tmp_path / "prof.json")))
        assert any(ev["name"] == "dot" for ev in trace["traceEvents"])


def _import_time_env_writes(tree):
    """(line, name) of every write of an ``MXNET_*`` environment variable
    that runs when the module is imported: anywhere but inside a function
    body (class bodies and module-level ``if`` / ``try`` / ``with`` run)."""
    import ast

    def mxnet_names(node):
        return [n.value for n in ast.walk(node)
                if isinstance(n, ast.Constant) and isinstance(n.value, str)
                and n.value.startswith("MXNET_")] + \
               [k.arg for k in getattr(node, "keywords", [])
                if k.arg and k.arg.startswith("MXNET_")]

    def is_environ(node):
        return (isinstance(node, ast.Attribute) and node.attr == "environ") \
            or (isinstance(node, ast.Name) and node.id == "environ")

    found = []

    def visit(node):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            return
        if isinstance(node, (ast.Assign, ast.Delete)):
            for t in node.targets:
                if isinstance(t, ast.Subscript) and is_environ(t.value):
                    found.extend((node.lineno, n)
                                 for n in mxnet_names(t.slice))
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
            f = node.func
            if f.attr in ("putenv", "unsetenv") or (
                    is_environ(f.value)
                    and f.attr in ("setdefault", "update", "pop")):
                found.extend((node.lineno, n) for n in mxnet_names(node))
        for child in ast.iter_child_nodes(node):
            visit(child)

    visit(tree)
    return found


class TestNoImportTimeEnvWrites:
    def test_checker_sees_the_forms(self):
        import ast
        src = ("import os\n"
               "os.environ.setdefault('MXNET_A', '1')\n"
               "os.environ['MXNET_B'] = '1'\n"
               "if True:\n"
               "    os.environ.update(MXNET_C='1')\n"
               "class K:\n"
               "    os.putenv('MXNET_D', '1')\n"
               "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
               "def f():\n"
               "    os.environ['MXNET_E'] = '1'\n")
        assert [n for _, n in _import_time_env_writes(ast.parse(src))] == \
            ["MXNET_A", "MXNET_B", "MXNET_C", "MXNET_D"]

    def test_no_test_module_writes_mxnet_env_at_import(self):
        """A test module that sets an ``MXNET_*`` variable while it is
        imported sets it for every test the worker runs after it, so the
        suite's outcome depends on which files share a process (PERF.md,
        PR 30 "Lost": a TPU-target compile held the INTERPRETED kernel).
        Set it per test, through ``monkeypatch``."""
        import ast
        import pathlib
        here = pathlib.Path(__file__).resolve().parent
        bad = []
        for path in sorted(here.rglob("*.py")):
            tree = ast.parse(path.read_text(), filename=str(path))
            bad += [f"{path.relative_to(here)}:{line} {name}"
                    for line, name in _import_time_env_writes(tree)]
        assert not bad, bad
