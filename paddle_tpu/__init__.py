"""paddle_tpu — a TPU-native deep-learning framework.

A from-scratch reimplementation of the capabilities of 2017-era PaddlePaddle
(reference: leepaul009/Paddle) built idiomatically on JAX/XLA/Pallas/pjit:

- ``paddle_tpu.layer``     — the declarative v2-style layer API (reference:
  ``python/paddle/v2/layer.py`` + ``trainer_config_helpers/layers.py``), compiled
  to pure JAX functions instead of a protobuf interpreted by a C++ GradientMachine.
- ``paddle_tpu.topology``  — DAG compilation + shape inference (reference:
  ``python/paddle/v2/topology.py`` + ``trainer/config_parser.py``).
- ``paddle_tpu.trainer``   — the SGD train loop with events (reference:
  ``python/paddle/v2/trainer.py``), backed by a jitted, mesh-sharded train step
  instead of ``GradientMachine::forwardBackward`` + parameter-server RPC.
- ``paddle_tpu.optimizer`` — the full optimizer family of
  ``paddle/parameter/FirstOrderOptimizer.h`` as JAX gradient transformations.
- ``paddle_tpu.parallel``  — device-mesh parallelism (data/tensor/pipeline/
  sequence) over XLA ICI collectives, replacing ``paddle/pserver`` +
  ``MultiGradientMachine``.
- ``paddle_tpu.reader`` / ``paddle_tpu.dataset`` — reader decorators and
  datasets (reference: ``python/paddle/v2/reader``, ``v2/dataset``).
- ``paddle_tpu.evaluator`` — the metric registry (reference:
  ``paddle/gserver/evaluators``).
"""

__version__ = "0.1.0"

import importlib as _importlib
import time as _time

_IMPORT_T0 = _time.perf_counter()  # with the reading at the bottom: the
                                   # import_paddle_tpu set-up span

from paddle_tpu.core import flags  # noqa: F401
from paddle_tpu.core.place import (  # noqa: F401
    CPUPlace,
    TPUPlace,
    default_place,
    is_compiled_with_tpu,
    set_default_place,
)

# v2-familiar module names -> implementation modules.  Resolved lazily so that
# `import paddle_tpu` stays cheap.
_API_MAP = {
    "layer": "paddle_tpu.layers.api",
    "topology": "paddle_tpu.config.topology",
    "networks": "paddle_tpu.layers.networks",
    "activation": "paddle_tpu.layers.activation",
    "pooling": "paddle_tpu.layers.pooling",
    "attr": "paddle_tpu.layers.attr",
    "initializer": "paddle_tpu.core.initializer",
    "parameters": "paddle_tpu.core.parameters",
    "trainer": "paddle_tpu.trainer",
    "event": "paddle_tpu.trainer.event",
    "inference": "paddle_tpu.trainer.inference",
    "optimizer": "paddle_tpu.optimizer",
    "parallel": "paddle_tpu.parallel",
    "reader": "paddle_tpu.reader",
    "dataset": "paddle_tpu.dataset",
    "evaluator": "paddle_tpu.evaluator",
    "models": "paddle_tpu.models",
    "config": "paddle_tpu.config",
    "ops": "paddle_tpu.ops",
    "utils": "paddle_tpu.utils",
    "metrics": "paddle_tpu.metrics",
    "telemetry": "paddle_tpu.telemetry",
}


def init(**kwargs):
    """≅ paddle.v2.init(use_gpu=..., trainer_count=...): set runtime flags.
    Imports only the flag registry — the v2 surface stays lazily loaded."""
    from paddle_tpu.core import flags

    mapping = {"use_gpu": "use_tpu"}
    for k, v in kwargs.items():
        k = mapping.get(k, k)
        try:
            flags.set(k, v)
        except KeyError:
            pass  # unknown historical flag: accepted and ignored


def __getattr__(name):
    if name == "v2":
        mod = _importlib.import_module("paddle_tpu.v2")
        globals()["v2"] = mod
        return mod
    target = _API_MAP.get(name)
    if target is not None:
        mod = _importlib.import_module(target)
        globals()[name] = mod
        return mod
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_API_MAP) | {"init", "v2"})


def infer(output_layer, parameters, input, feeding=None, field="value"):
    """Convenience inference entry (reference: ``python/paddle/v2/inference.py:10``)."""
    from paddle_tpu.trainer import inference as _inf

    return _inf.infer(
        output_layer=output_layer,
        parameters=parameters,
        input=input,
        feeding=feeding,
        field=field,
    )


# XLA's build events (trace / lower / compile / cache fetch) are heard from
# here on: one listener a process (telemetry/tracing.py).  jax is loaded
# by now (core.place), so this costs the telemetry package's own import.
from paddle_tpu.telemetry import tracing as _tracing  # noqa: E402

_tracing.install_xla_listener()
_IMPORT_WINDOW = (_IMPORT_T0, _time.perf_counter())
