"""Drop-in ``paddle`` module aliasing + the package's names for three jax
entry points.

Reference config files and demos start with ``from
paddle.trainer_config_helpers import *`` or ``import paddle.v2 as paddle``.
``install_paddle_alias()`` registers this package under the ``paddle`` name
in ``sys.modules`` so those files run unmodified against the TPU runtime
(the compatibility claim of BASELINE.json's "keep the Python v2 API").

The alias is only installed when no real ``paddle`` is importable, and is
idempotent.

``shard_map``, ``tpu_compiler_params`` and ``axis_size`` are this
package's one spelling of three jax entry points (the installed jax is
0.9.0, the same on the chip machine): every shard_map user, every
pallas kernel and every ring collective goes through these names.
"""

from __future__ import annotations

import importlib
import sys

from jax import shard_map  # noqa: F401  (re-exported)
from jax.experimental.pallas.tpu import (  # noqa: F401
    CompilerParams as tpu_compiler_params,
)
from jax.lax import axis_size  # noqa: F401

_ALIASES = {
    "paddle": "paddle_tpu",
    "paddle.trainer_config_helpers": "paddle_tpu.trainer_config_helpers",
    "paddle.trainer_config_helpers.optimizers": "paddle_tpu.trainer_config_helpers.optimizers",
    "paddle.trainer": "paddle_tpu.trainer",
    "paddle.trainer.config_parser": "paddle_tpu.trainer.config_parser",
    "paddle.trainer.PyDataProvider2": "paddle_tpu.reader.py_data_provider2",
    "paddle.proto": "paddle_tpu.proto",
    "paddle.v2": "paddle_tpu.v2",
}


def install_paddle_alias(force: bool = False) -> bool:
    if "paddle" in sys.modules and not force:
        already_ours = getattr(sys.modules["paddle"], "__name__", "").startswith(
            "paddle_tpu"
        )
        if already_ours:
            return True
        return False
    for alias, target in _ALIASES.items():
        try:
            sys.modules[alias] = importlib.import_module(target)
        except ImportError:
            pass
    return True
