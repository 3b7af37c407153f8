"""chip_smoke.py rehearsed on the CPU: its three phases at a tiny geometry
(the flash kernels in interpret mode), its refusal to run off a TPU, and
the compile-cache placement it reports cold/warm times against."""
import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import chip_smoke  # noqa: E402


def _tiny_gpt(dtype):
    from mxnet_tpu import models
    cfg = models.GPTConfig(vocab_size=128, max_length=64, num_layers=2,
                           units=64, num_heads=4, hidden_size=128,
                           dtype=dtype)
    return models.GPT(cfg), cfg


def test_train_phase_tiny():
    """dp over the 8 virtual devices, including the dp=1 loss parity arm."""
    row = chip_smoke.train_phase(
        "cpu", geom=dict(num_layers=2, units=64, num_heads=4,
                         hidden_size=128, vocab_size=512, seq=16,
                         dtype="float32"), batch=8)
    assert row["ok"] and row["devices"] == 8
    assert row["per_device_batch"] == 1
    assert row["dp1_first_loss"] == pytest.approx(row["losses"][0],
                                                  rel=1e-3)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_serve_phase_tiny(dtype, tmp_path):
    row = chip_smoke.serve_phase(
        "cpu", dtype, make_net=_tiny_gpt, max_total_len=64,
        prompt_lens=(5, 14, 20, 40), max_new=8,
        recording=str(tmp_path / "serve.jsonl"))
    assert row["ok"] and row["requests"] == 5
    assert row["prefix_hits"] >= 1 and row["verify_dispatches"] >= 1
    if dtype == "float32":
        assert row["identical_streams"] == 5


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_serve_phase_rejects_a_wrong_stream(dtype, monkeypatch, tmp_path):
    """The correctness rule is not vacuous in either dtype: a reference
    that disagrees where the logits are no near-tie fails the phase."""
    from mxnet_tpu import models
    real = models.kv_generate

    def off_by_one(net, prompt, **kw):
        out = real(net, prompt, **kw)
        out[0, prompt.shape[1]] = (out[0, prompt.shape[1]] + 1) % 128
        return out

    monkeypatch.setattr(models, "kv_generate", off_by_one)
    with pytest.raises(chip_smoke.SmokeFailure, match="left the reference"):
        chip_smoke.serve_phase(
            "cpu", dtype, make_net=_tiny_gpt, max_total_len=64,
            prompt_lens=(5, 14, 20, 40), max_new=8,
            recording=str(tmp_path / "serve.jsonl"))


def test_kernel_phase_interpret(monkeypatch):
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    row = chip_smoke.kernel_phase("cpu", shape=(1, 1, 1280, 64),
                                  dtype="float32")
    assert row["ok"] and set(row["max_rel_err"]) == {"out", "dq", "dk",
                                                     "dv"}


def test_kernel_phase_refuses_interpret_on_the_chip(monkeypatch):
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    with pytest.raises(chip_smoke.SmokeFailure,
                       match="MXNET_FLASH_INTERPRET"):
        chip_smoke.kernel_phase("tpu", shape=(1, 1, 1280, 64),
                                dtype="float32")


def _python(args, env):
    return subprocess.run([sys.executable] + args, cwd=_REPO, env=env,
                          capture_output=True, text=True, timeout=300)


def test_full_size_entry_fails_off_tpu():
    """No accelerator: non-zero exit and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = _python([os.path.join(_REPO, "chip_smoke.py")], env)
    assert proc.returncode != 0
    assert "not a TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


_CACHE_DIR = ("import mxnet_tpu, jax, json; "
              "from jax._src import xla_bridge; "
              "print(json.dumps([jax.config.jax_compilation_cache_dir, "
              "len(xla_bridge._backends)]))")


def _cache_dir(**env):
    base = {k: v for k, v in os.environ.items()
            if k not in ("JAX_COMPILATION_CACHE_DIR", "JAX_PLATFORMS")}
    proc = _python(["-c", _CACHE_DIR], dict(base, **env))
    assert proc.returncode == 0, proc.stderr
    path, n_backends = json.loads(proc.stdout.strip().splitlines()[-1])
    # import alone never initialises a backend (a parent that only
    # imports the package does not take the chip)
    assert n_backends == 0
    return path


def test_compile_cache_placement(tmp_path):
    # placed from outside: the package sets nothing
    assert _cache_dir(JAX_COMPILATION_CACHE_DIR=str(tmp_path)) == \
        str(tmp_path)
    assert _cache_dir(JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                      JAX_PLATFORMS="cpu") == str(tmp_path)
    # not placed: the fixed in-checkout path ...
    assert _cache_dir() == os.path.join(_REPO, ".jax_cache")
    # ... except under the CPU pin (the tests), which gets none
    assert _cache_dir(JAX_PLATFORMS="cpu") is None
