"""mxnet_tpu — a TPU-native deep-learning framework with the capabilities of
the reference (Apache MXNet lineage; see SURVEY.md).

Import as ``import mxnet_tpu as mx`` — the public surface mirrors the
reference's ``import mxnet as mx``: ``mx.nd``, ``mx.autograd``, ``mx.gluon``,
``mx.cpu()/mx.gpu()/mx.tpu()``, ``mx.random``, ``mx.optimizer``, ...
"""
__version__ = "0.1.0"

from .base import MXNetError
from .context import (Context, cpu, gpu, tpu, cpu_pinned, cpu_shared,
                      num_gpus, num_tpus, current_context, gpu_memory_info)
from . import base
from . import ndarray
from . import ndarray as nd
from . import autograd
from . import random

# subpackages loaded lazily to keep import fast and avoid cycles
from importlib import import_module as _imp


def __getattr__(name):
    _lazy = {
        "gluon": ".gluon",
        "optimizer": ".optimizer",
        "initializer": ".initializer",
        "init": ".initializer",
        "metric": ".metric",
        "io": ".io",
        "kvstore": ".kvstore",
        "kv": ".kvstore",
        "profiler": ".profiler",
        "telemetry": ".telemetry",
        "runtime": ".runtime",
        "rtc": ".rtc",
        "checkpoint": ".checkpoint",
        "engine": ".engine",
        "name": ".name",
        "viz": ".visualization",
        "visualization": ".visualization",
        "util": ".util",
        "image": ".image",
        "recordio": ".recordio",
        "parallel": ".parallel",
        "models": ".models",
        "serve": ".serve",
        "np": ".numpy",
        "npx": ".numpy_extension",
        "lr_scheduler": ".optimizer.lr_scheduler",
        "callback": ".callback",
        "module": ".module",
        "symbol": ".symbol",
        "sym": ".symbol",
        "test_utils": ".test_utils",
        "amp": ".amp",
        "onnx": ".onnx",
        "contrib": ".contrib",
        "operator": ".operator",
        "model": ".model",
        "predictor": ".predictor",
    }
    if name == "AttrScope":
        from .name import AttrScope
        globals()["AttrScope"] = AttrScope
        return AttrScope
    if name in _lazy:
        mod = _imp(_lazy[name], __name__)
        globals()[name] = mod
        return mod
    raise AttributeError(f"module 'mxnet_tpu' has no attribute {name!r}")
