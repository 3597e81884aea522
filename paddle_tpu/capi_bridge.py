"""Bridge between the C inference ABI and the Python runtime.

native/capi/paddle_capi.cc embeds CPython (the same technique the
reference uses for its config parser — ``paddle/utils/PythonUtil.cpp``
``Py_Initialize``/``callPythonFunc``) and calls these module-level
functions.  The interface is deliberately buffer-based (raw little-endian
float32 bytes + dims) so the C side needs no numpy C API.
"""

from __future__ import annotations

import numpy as np

from paddle_tpu.utils.merge_model import MergedModel

_machines: dict[int, MergedModel] = {}
_next_handle = [1]


def create_machine(model_bytes: bytes) -> int:
    m = MergedModel(model_bytes)
    h = _next_handle[0]
    _next_handle[0] += 1
    _machines[h] = m
    return h


def create_shared_machine(origin: int) -> int:
    """A new machine handle sharing the ORIGIN's loaded artifact — the
    reference's ``paddle_gradient_machine_create_shared_param``
    (gradient_machine.h:68): weights are baked into the compiled StableHLO
    executable and the machine is a pure function, so sharing is exact
    aliasing with zero per-machine weight copies."""
    m = _machines[origin]
    h = _next_handle[0]
    _next_handle[0] += 1
    _machines[h] = m
    return h


def destroy_machine(handle: int) -> None:
    _machines.pop(handle, None)


def num_inputs(handle: int) -> int:
    return len(_machines[handle].meta["inputs"])


def input_dim(handle: int, i: int) -> int:
    return int(_machines[handle].meta["inputs"][i]["dim"])


def forward(handle: int, in_bufs: list, rows: int):
    """in_bufs: one bytes object of float32 data per input.
    Returns [(bytes, rows, cols), ...] per output."""
    m = _machines[handle]
    arrays = [
        np.frombuffer(buf, dtype="<f4").reshape(rows, spec["dim"])
        for buf, spec in zip(in_bufs, m.meta["inputs"])
    ]
    outs = m.forward(*arrays)
    result = []
    for o in outs:
        o = np.ascontiguousarray(o, dtype="<f4")
        if o.ndim == 1:
            o = o[:, None]
        result.append((o.tobytes(), int(o.shape[0]), int(o.shape[1])))
    return result
