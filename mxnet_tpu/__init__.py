"""mxnet_tpu: a TPU-native deep-learning framework with MXNet's
capabilities (reference: gigasquid/incubator-mxnet), rebuilt on
JAX/XLA/PjRt/Pallas. See SURVEY.md for the capability map.

Usage mirrors the reference's ``import mxnet as mx``::

    import mxnet_tpu as mx
    x = mx.nd.ones((2, 3), ctx=mx.tpu(0))
"""
from . import programs as _programs

# before anything can compile: every later compile is either written
# to or loaded from the persistent cache (programs.py, §2)
_programs.configure_compile_cache()

from . import base
from .base import MXNetError
from .context import Context, cpu, gpu, tpu, current_context, num_gpus, num_tpus
from . import ndarray
from . import ndarray as nd
from . import operator
# nd.Custom uses the eager Function-based bridge; sym.Custom / hybridized
# graphs pick up the "Custom" OpDef (pure_callback) operator.py registers.
nd.Custom = operator.custom_ndarray
from . import autograd
from . import random
from .random import seed

from .libinfo import __version__  # single source of truth

# Subpackages that may not exist yet early in the build are imported lazily.
_LAZY = ("symbol", "sym", "gluon", "module", "io", "optimizer", "metric",
         "initializer", "init", "kvstore", "kv", "callback", "lr_scheduler",
         "profiler", "parallel", "test_utils", "image", "recordio", "engine",
         "executor", "model", "monitor", "visualization", "rtc", "contrib",
         "checkpoint", "gradient_compression", "kvstore_server", "storage",
         "config", "rnn", "mod", "name", "attribute", "log", "libinfo",
         "util", "registry", "misc", "executor_manager", "ndarray_doc",
         "symbol_doc", "telemetry", "serving", "serve", "fault",
         "tracing", "quantize", "programs", "forensics")


def __getattr__(name):
    import importlib
    if name == "diagnostics":
        # one-shot environment/device/memory/cache report for bug
        # reports (the libinfo + storage-profiler-dump analog)
        from .telemetry import diagnostics
        globals()["diagnostics"] = diagnostics
        return diagnostics
    if name == "AttrScope":
        from .symbol import AttrScope
        globals()["AttrScope"] = AttrScope
        return AttrScope
    if name == "mod":
        mod = importlib.import_module(".module", __name__)
        globals()["module"] = mod
        globals()["mod"] = mod
        return mod
    if name in ("sym", "symbol"):
        mod = importlib.import_module(".symbol", __name__)
        globals()["symbol"] = mod
        globals()["sym"] = mod
        return mod
    if name in ("init", "initializer"):
        mod = importlib.import_module(".initializer", __name__)
        globals()["initializer"] = mod
        globals()["init"] = mod
        return mod
    if name == "kv":
        mod = importlib.import_module(".kvstore", __name__)
        globals()["kvstore"] = mod
        globals()["kv"] = mod
        return mod
    if name == "viz":
        mod = importlib.import_module(".visualization", __name__)
        globals()["visualization"] = mod
        globals()["viz"] = mod
        return mod
    if name in _LAZY:
        try:
            mod = importlib.import_module("." + name, __name__)
        except ModuleNotFoundError as e:
            if e.name == __name__ + "." + name:
                raise AttributeError(
                    "mxnet_tpu.%s is not available in this build" % name
                ) from None
            raise
        globals()[name] = mod
        return mod
    raise AttributeError("module %r has no attribute %r" % (__name__, name))
