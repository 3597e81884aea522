"""Floor of a convolution kernel family: every convolution of the
configuration, as its reference's ``conv_table`` lists them at the
per-chip batch, once per traced step (``floor.passes``: "forward" or
"train" = forward, input gradient and weight gradient)."""

from benchmarks.harness import roofline, trace


def floor(fam: dict, spec: dict, layer: dict, run):
    """(least seconds for the traced calls, a note) or None."""
    steps = trace.module_ms(layer["profile"], spec["args"]["per_module"],
                            layer.get("profile_window"))["count"]
    per_chip = layer["batch"] // max(run.chips, 1)
    rows = run.py("references", run.config["reference"]).conv_table(
        run.config, per_chip)
    f = roofline.calls_floor(rows, run.peak,
                             train=fam["floor"]["passes"] == "train")
    return f["seconds"] * steps, (
        f"{len(rows)} convolutions a step (file says "
        f"{fam.get('calls_per_step')}), {steps} steps traced, floor "
        f"{1e3 * f['seconds']:.3f} ms a step, bound by {f['bound']}")
