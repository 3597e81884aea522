"""Checkpoint -> servable export.

A *servable* is the frozen serving artifact: ``params.npz`` plus a
``servable.json`` manifest carrying the model config and a sha256 per
payload file — the same uuid + content-hash + atomic tmp/rename
convention as ``trainer/checkpoint.py`` (the Go pserver's recovery rule),
so a torn or tampered export is detected at load, never served.

Flows::

    export_servable(dir, cfg, params)               # from live params
    checkpoint_to_servable(ckpt_dir, out_dir, cfg)  # newest VALID ckpt
    cfg, params = load_servable(dir)                # engine input
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import time
import uuid as uuid_mod

import numpy as np

from paddle_tpu.core.enforce import enforce

MANIFEST = "servable.json"
SCHEMA = "paddle_tpu.servable/1"


def _sha256(path: str) -> str:
    # deferred: trainer.checkpoint imports jax at module scope, and this
    # package keeps jax out of import time
    from paddle_tpu.trainer.checkpoint import _sha256 as impl

    return impl(path)


def _cfg_to_json(cfg) -> dict:
    """TransformerConfig -> plain-json dict (dtype stored by name)."""
    d = dataclasses.asdict(cfg)
    d["dtype"] = np.dtype(cfg.dtype).name
    return d


def _cfg_from_json(d: dict):
    import jax.numpy as jnp

    from paddle_tpu.models.transformer import TransformerConfig

    d = dict(d)
    d["dtype"] = jnp.dtype(d["dtype"])
    return TransformerConfig(**d)


def _flatten(params, prefix="") -> dict[str, np.ndarray]:
    """Nested dicts (and lists: a layer pattern's per-layer trees, keyed
    by index) -> {"a/b/0/c": array}."""
    flat = {}
    items = enumerate(params) if isinstance(params, list) else params.items()
    for k, v in items:
        key = f"{prefix}{k}"
        if isinstance(v, (dict, list)):
            flat.update(_flatten(v, key + "/"))
        else:
            flat[key] = np.asarray(v)
    return flat


def _unflatten(flat: dict[str, np.ndarray]) -> dict:
    out: dict = {}
    for key, v in flat.items():
        node, parts = out, key.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v

    def lists(node):
        """A dict keyed 0..n-1 was a list."""
        if not isinstance(node, dict):
            return node
        node = {k: lists(v) for k, v in node.items()}
        if node and all(k.isdigit() for k in node):
            return [node[str(i)] for i in range(len(node))]
        return node

    return lists(out)


def export_servable(out_dir: str, cfg, params: dict,
                    meta: dict | None = None) -> str:
    """Write ``out_dir`` atomically (tmp + rename); returns the path."""
    tmp = out_dir.rstrip("/") + ".tmp-" + uuid_mod.uuid4().hex[:8]
    os.makedirs(tmp, exist_ok=True)
    try:
        np.savez(os.path.join(tmp, "params.npz"), **_flatten(params))
        # record the payload inventory {param name: dtype-as-stored} so a
        # partial or rewritten payload (param dropped, dtype changed)
        # is refused at load even if the manifest hashes were regenerated
        # to match — the manifest is the contract, not just a checksum
        with np.load(os.path.join(tmp, "params.npz")) as z:
            param_inventory = {k: str(z[k].dtype) for k in z.files}
        manifest = {
            "schema": SCHEMA,
            "uuid": uuid_mod.uuid4().hex,
            "created": time.time(),
            "config": _cfg_to_json(cfg),
            "files": {f: _sha256(os.path.join(tmp, f))
                      for f in sorted(os.listdir(tmp))},
            "params": param_inventory,
            "meta": meta or {},
        }
        with open(os.path.join(tmp, MANIFEST), "w") as f:
            json.dump(manifest, f, indent=2)
        # refresh-over-live: move the old artifact ASIDE first so the
        # no-servable window is two renames, not a whole rmtree — a
        # reader never sees a half-deleted directory
        old = None
        if os.path.exists(out_dir):
            old = out_dir.rstrip("/") + ".old-" + uuid_mod.uuid4().hex[:8]
            os.rename(out_dir, old)
        try:
            os.rename(tmp, out_dir)
        except BaseException:
            if old is not None:  # put the previous good artifact back
                os.rename(old, out_dir)
            raise
        if old is not None:
            shutil.rmtree(old, ignore_errors=True)
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    return out_dir


def load_servable(path: str):
    """Validate hashes and return (TransformerConfig, params pytree)."""
    import jax.numpy as jnp

    mpath = os.path.join(path, MANIFEST)
    enforce(os.path.exists(mpath), f"no servable manifest at {mpath}")
    with open(mpath) as f:
        manifest = json.load(f)
    for fname, digest in manifest["files"].items():
        fpath = os.path.join(path, fname)
        enforce(os.path.exists(fpath),
                f"servable {path}: {fname} is listed in the manifest "
                "but missing from disk — refusing a partial artifact")
        enforce(_sha256(fpath) == digest,
                f"servable {path}: {fname} hash mismatch — refusing to "
                "serve a corrupt/tampered artifact")
    cfg = _cfg_from_json(manifest["config"])
    with np.load(os.path.join(path, "params.npz")) as z:
        flat = {k: z[k] for k in z.files}
    # payload-vs-manifest inventory check (manifests before /1's
    # "params" field skip it): a param missing from the payload, an
    # extra one, or a dtype drift means the artifact is NOT what was
    # exported — refuse rather than serve garbage-shaped weights
    inventory = manifest.get("params")
    if inventory is not None:
        missing = sorted(set(inventory) - set(flat))
        extra = sorted(set(flat) - set(inventory))
        enforce(not missing and not extra,
                f"servable {path}: payload params do not match the "
                f"manifest (missing {missing[:4]}, unexpected "
                f"{extra[:4]}) — refusing a partial artifact")
        drift = {k: (inventory[k], str(flat[k].dtype)) for k in inventory
                 if str(flat[k].dtype) != inventory[k]}
        enforce(not drift,
                f"servable {path}: param dtype mismatch vs manifest "
                f"{dict(list(drift.items())[:4])} — refusing to serve "
                "garbage")
    # float payloads come back at the config's compute dtype (npz stores
    # extension dtypes upcast, the checkpoint convention)
    params = {k: jnp.asarray(v, dtype=cfg.dtype if v.dtype.kind == "f"
                             else None)
              for k, v in flat.items()}
    params = _unflatten(params)
    if cfg.pattern is not None and len(params["blocks"]) != cfg.pattern_roll[0]:
        # written one tree a LAYER, before the pattern walk rolled over a
        # period: stacked here by position, as the walk reads them
        from paddle_tpu.models.transformer import lay_blocks

        params["blocks"] = lay_blocks(cfg, params["blocks"])
    return cfg, params


def checkpoint_path_to_servable(path: str, out_dir: str, cfg,
                                meta: dict | None = None) -> str:
    """Export ONE specific checkpoint dir as a servable (validated via
    its manifest first).  The deployment controller uses this form so
    the checkpoint it decided to roll out is the one exported, even if
    a newer one lands mid-export."""
    from paddle_tpu.trainer.checkpoint import load_checkpoint

    params, _, _, manifest = load_checkpoint(path)
    nested = _unflatten(params)
    return export_servable(
        out_dir, cfg, nested,
        meta={**(meta or {}), "checkpoint": path,
              "checkpoint_uuid": manifest.get("uuid")})


def checkpoint_to_servable(ckpt_dir: str, out_dir: str, cfg,
                           meta: dict | None = None) -> str:
    """Export the newest VALID trainer checkpoint under ``ckpt_dir`` as a
    servable.  Parameter names must match ``transformer.init_params``'s
    flat layout (the trainer saves ``params.npz`` keyed by name)."""
    from paddle_tpu.trainer.checkpoint import latest_checkpoint

    found = latest_checkpoint(ckpt_dir)
    enforce(found is not None, f"no valid checkpoint under {ckpt_dir}")
    return checkpoint_path_to_servable(found[0], out_dir, cfg, meta)
