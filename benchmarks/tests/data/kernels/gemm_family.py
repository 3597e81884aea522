"""A floor function that came as a file: the matrix products of a
configuration whose reference lists them (``gemm_table``), forward."""

from benchmarks.harness import roofline


def floor(fam: dict, spec: dict, layer: dict, run):
    rows = run.py("references", run.config["reference"]).gemm_table(
        run.config, layer["batch"])
    f = roofline.calls_floor(rows, run.peak, train=False)
    return f["seconds"] * layer["steps"], f"{len(rows)} products a step"
