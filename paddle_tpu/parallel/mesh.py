"""Device mesh management — the TPU-native successor of trainer_count/
num_gradient_servers topology flags (``paddle/utils/Flags.h``) and the
pserver shard map (``ParameterServer2`` block hashing).

Axes convention (the scaling-book recipe):
- ``data``  — batch sharding (DP); gradients all-reduce over ICI here.
- ``model`` — weight sharding (TP); activations all-gather/reduce-scatter.
- ``pipe``  — pipeline stages (PP); collective-permute between stages.
- ``seq``   — sequence/context parallelism (ring attention / Ulysses).

A 1-axis all-``data`` mesh reproduces the reference's pure data-parallel
training; the other axes are capability upgrades the reference lacked."""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.core import flags
from paddle_tpu.core.enforce import enforce

AXES = ("data", "model", "pipe", "seq")


def make_mesh(
    shape: dict[str, int] | None = None, devices=None
) -> Mesh:
    """Build a mesh; default = all devices on the ``data`` axis.

    shape e.g. {"data": 4, "model": 2}.  Axis order follows AXES so that the
    innermost (fastest-varying, best-ICI-locality) axis is the model axis.
    """
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if not shape:
        cfg = flags.get("mesh_shape")
        if cfg:
            dims = [int(x) for x in cfg.split(",")]
            names = AXES[: len(dims)]
            shape = dict(zip(names, dims))
        else:
            shape = {"data": n}
    used = int(np.prod(list(shape.values())))
    enforce(used <= n, f"mesh {shape} needs {used} devices, have {n}")
    names = [a for a in AXES if a in shape] + [a for a in shape if a not in AXES]
    dims = [shape[a] for a in names]
    dev_array = np.asarray(devices[:used]).reshape(dims)
    return Mesh(dev_array, tuple(names))


_current: "MeshContext | None" = None


@dataclasses.dataclass
class MeshContext:
    """Holds the mesh + canonical shardings used by the train step."""

    mesh: Mesh

    @property
    def num_replicas(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.mesh.axis_names
                            if a == "data"])) or 1

    def data_sharding(self, ndim: int) -> NamedSharding:
        """Batch dim sharded over 'data' (and 'seq' handled separately)."""
        spec = P("data", *([None] * (ndim - 1)))
        return NamedSharding(self.mesh, spec)

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def param_sharding(self, spec_axes: tuple | None, ndim: int) -> NamedSharding:
        """Parameter sharding from a ParamSpec.sharding tuple (model axes),
        default replicated — pure DP keeps whole weights everywhere like
        MultiGradientMachine's per-thread full copies."""
        if spec_axes is None:
            return self.replicated()
        # known axes absent from this mesh degrade to replicated (a
        # TP-annotated model still runs on a pure-DP mesh); unknown names are
        # errors, not silent replication
        present = set(self.mesh.axis_names)
        for a in spec_axes:
            enforce(
                a is None or a in present or a in AXES,
                f"unknown mesh axis {a!r} in param sharding {spec_axes}",
            )
        axes = [a if a in present else None for a in spec_axes]
        return NamedSharding(self.mesh, P(*axes))

    def shard_batch(self, tree, remainder: str = "error"):
        """Place a feed pytree with batch-dim sharding.  A host leaf (what
        ``DataFeeder`` makes) goes shard by shard straight to the devices
        that hold it: one transfer each, all started by this one
        ``device_put``, which returns before they are done and leaves them
        to run side by side (four chips took 616 MB in 27 ms, one chip
        154 MB in 17 ms; PERF.md, PR 25).  Nothing passes through the
        default device.  A device leaf is resharded from where it is.

        ``remainder`` is the partial-batch policy: "error" (default)
        keeps the strict divisibility check below; "drop"/"pad" first run
        :func:`apply_remainder` so the last partial batch of a pass can't
        kill a multi-device run (opt-in — see that function's caveats).
        A batch that "drop" empties entirely raises here (a direct caller
        gets a clear error); the trainer's feed iterators
        (``reader/prefetch.py``) apply the policy themselves and SKIP
        such batches instead."""
        dp = self.mesh.shape.get("data", 1)
        if remainder != "error":
            # validated (and applied) even at dp=1, so a typo'd policy
            # fails on the dev box, not first on the pod
            adjusted = apply_remainder(tree, dp, remainder)
            enforce(
                adjusted is not None,
                f"batch smaller than the mesh data axis ({dp}) was fully "
                f"dropped by remainder='drop'; nothing left to shard",
            )
            tree = adjusted

        def place(x):
            if hasattr(x, "ndim") and x.ndim >= 1:
                enforce(
                    x.shape[0] % dp == 0,
                    f"batch size {x.shape[0]} is not divisible by the mesh "
                    f"data axis ({dp}); use a batch size that is a multiple "
                    f"of the replica count (drop_last=True in paddle.batch)",
                )
                return jax.device_put(x, self.data_sharding(x.ndim))
            return x

        return jax.tree.map(place, tree)

    def replicate(self, tree):
        sh = self.replicated()
        return jax.tree.map(lambda x: jax.device_put(x, sh), tree)

    def place_params(self, values: dict, specs: dict) -> dict:
        """Place each parameter per its ParamSpec.sharding (tensor parallel);
        unsharded params are replicated — the pure-DP layout that reproduces
        MultiGradientMachine's per-replica full copies."""
        out = {}
        for name, v in values.items():
            spec = specs.get(name)
            axes = getattr(spec, "sharding", None) if spec is not None else None
            out[name] = jax.device_put(v, self.param_sharding(axes, v.ndim))
        return out


def apply_remainder(tree, multiple: int, policy: str):
    """Make every batch-dim leaf of a feed pytree divisible by ``multiple``.

    - ``"drop"``: trim to the largest multiple, dropping tail samples.
      Returns None when nothing is left (callers skip the batch).
    - ``"pad"``: repeat the LAST sample up to the next multiple.  The
      padded rows are real duplicated samples, so the final partial batch
      of a pass weights its last sample slightly more in the loss — fine
      for throughput runs, wrong for exact-metric evaluation (use "drop"
      or full batches there).
    - ``"error"``: return the tree unchanged (shard_batch then enforces).

    Leaves without a leading batch dim (scalars) pass through; ragged
    pytrees (SequenceBatch data+length) stay consistent because every
    batch-dim leaf shares the same leading size.
    """
    if policy == "error":
        return tree
    enforce(policy in ("drop", "pad"),
            f"unknown batch remainder policy {policy!r} "
            "(expected 'error', 'drop' or 'pad')")
    batched = [x for x in jax.tree.leaves(tree)
               if hasattr(x, "ndim") and x.ndim >= 1]
    if not batched:
        return tree
    b = batched[0].shape[0]
    r = b % multiple
    if r == 0:
        return tree
    if policy == "drop":
        keep = b - r
        if keep == 0:
            return None
        return jax.tree.map(
            lambda x: x[:keep]
            if hasattr(x, "ndim") and x.ndim >= 1 else x, tree)
    pad = multiple - r

    def _pad(x):
        if not (hasattr(x, "ndim") and x.ndim >= 1):
            return x
        a = np.asarray(x)
        return np.concatenate([a, np.repeat(a[-1:], pad, axis=0)], axis=0)

    return jax.tree.map(_pad, tree)


def resize_data_axis(ctx: MeshContext, new_dp: int,
                     devices=None) -> MeshContext:
    """A new MeshContext with the ``data`` axis resized to ``new_dp`` —
    the elastic-resharding mesh rebuild (``resilience/elastic.py``).

    Only pure data-parallel meshes resize live: a ``model``/``pipe``/
    ``seq`` axis > 1 would need its parameter shards re-laid-out too,
    which live resharding does not attempt.  ``devices`` selects the
    member devices explicitly (host-loss survivors keep their relative
    order); by default a shrink keeps the first ``new_dp`` of the
    current mesh and a grow extends with unattached devices.
    """
    old = ctx.mesh
    for a in old.axis_names:
        enforce(a == "data" or old.shape[a] == 1,
                f"resize_data_axis needs a pure data mesh; axis {a!r} "
                f"has size {old.shape[a]}")
    enforce(new_dp >= 1, f"new data degree must be >= 1, got {new_dp}")
    if devices is None:
        current = list(old.devices.flat)
        if new_dp <= len(current):
            devices = current[:new_dp]
        else:
            pool = current + [d for d in jax.devices()
                              if d not in current]
            enforce(len(pool) >= new_dp,
                    f"resize to data={new_dp} needs {new_dp} devices; "
                    f"only {len(pool)} attached")
            devices = pool[:new_dp]
    enforce(len(devices) == new_dp,
            f"{len(devices)} devices given for data={new_dp}")
    return MeshContext(mesh=make_mesh({"data": new_dp},
                                      devices=list(devices)))


def get_mesh(shape: dict[str, int] | None = None) -> MeshContext:
    global _current
    if _current is None or shape is not None:
        _current = MeshContext(mesh=make_mesh(shape))
    return _current


def set_mesh(ctx: MeshContext) -> None:
    global _current
    _current = ctx
