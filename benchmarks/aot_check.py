"""python benchmarks/aot_check.py [--workload <cell> ...]

Compiles each cell's programs at full size for a DESCRIBED ``v5e:2x2``
(no chip attached): the trainer's step on one device or on a 4-device
data mesh, the engine's prefill and decode programs on one device
(which programs a cell has is its driver's to say:
``drivers/<driver>.py``: ``aot_programs``).
Prints ``memory_analysis()``, the Pallas kernel census and the
collectives.  A compile that passes is a compiler fact, not a run: the
figures go into the configuration files' notes under that name.  Run it
before chip time is spent.  ``JAX_PLATFORMS=cpu`` must be set.
"""

import argparse
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from benchmarks.harness import runner  # noqa: E402

GB = 1e9


def _report(tag, lowered):
    t0 = time.perf_counter()
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    census: dict = {}
    for name in re.findall(r'kernel_name = "([^"]+)"', lowered.as_text()):
        census[name] = census.get(name, 0) + 1
    colls = {k: len(re.findall(rf"\b{k}(?:-start)?\(", hlo))
             for k in ("all-reduce", "all-gather", "reduce-scatter",
                       "all-to-all", "collective-permute")}
    out = {"program": tag,
           "argument_GB": ma.argument_size_in_bytes / GB,
           "output_GB": ma.output_size_in_bytes / GB,
           "alias_GB": ma.alias_size_in_bytes / GB,
           "temp_GB": ma.temp_size_in_bytes / GB,
           "tpu_custom_calls_in_hlo": hlo.count("tpu_custom_call"),
           "pallas_kernels": census,
           "collectives": {k: v for k, v in colls.items() if v},
           "compile_s": time.perf_counter() - t0}
    print(json.dumps(out), flush=True)
    return out


def _struct(tree, sharding):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding), tree)


def main() -> int:
    from jax.experimental import topologies

    import paddle_tpu.ops.pallas as pallas_mod
    import paddle_tpu.ops.pallas.tpp as tpp_mod

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", action="append")
    a = p.parse_args()
    with open(os.path.join(os.path.dirname(runner.ROOT),
                           "BENCHMARK.json")) as f:
        cells = a.workload or [w["name"] for w in json.load(f)["workloads"]]
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # this script, not the program, decides that the target is a TPU
    pallas_mod.on_tpu = lambda: True
    tpp_mod.on_tpu = lambda: True
    for name in cells:
        cell = runner.load_json("workloads", name, [runner.ROOT])
        cfg = runner.load_json("configs", cell["config"], [runner.ROOT])
        driver = runner.load_py("drivers", cfg["driver"], [runner.ROOT])
        with pallas_mod.capture_routes() as routes:
            for tag, lowered in driver.aot_programs(
                    cell, cfg, [runner.ROOT], topo, _struct):
                _report(tag, lowered)
        print(json.dumps({"cell": name, "routes": {
            f"{op}:{path}": n for (op, path), n in sorted(routes.items())}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
