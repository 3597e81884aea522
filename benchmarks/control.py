"""python benchmarks/control.py --workload <cell> --seeds 1,2,3 [--seconds S]

The control of ``correct``: the plain reference put in the program's
place and computed in the nearest precision BELOW the one the
configuration states (``control_precision``: fp8 for the bf16 trainer,
int8 for the bf16 server).  It has to come out as NOT correct against
the cell's limits.  Run on the chip at the cell's own size on three
seeds or more when a limit is set; the benchmark's own runs never run
it.  ``benchmarks/tests/test_control.py`` keeps it at a toy size.

How a control is read is its driver's own (``drivers/<driver>.py``:
``control(run)``).  Training needs no window: the reference follows the
first three steps in float32 and again in the lower precision, and the
two are compared exactly as the program is.  Serving runs the cell for
``--seconds`` (the program serves), then reads, at each position of the
sampled prompts and served tokens, the gap of the token that the lower
precision puts first.
"""

import time

_PROC_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness import runner  # noqa: E402


def control(workload: str, seed: int, seconds: float, roots=None,
            on_chip: bool = True) -> dict:
    """The control's numbers beside the cell's limits; ``correct`` is
    what the benchmark would have said of it."""
    run = runner.prepare(workload, seed, seconds, False, roots, on_chip,
                         _PROC_T0)
    got = run.py("drivers", run.config["driver"]).control(run)
    import faulthandler

    faulthandler.cancel_dump_traceback_later()
    lim = run.cell["limits"]
    failed = [k for k in lim if got[k] > lim[k]]
    out = {"workload": workload, "seed": seed,
           "precision": run.config["control_precision"], "control": got,
           "limits": lim, "fails": failed, "correct": not failed}
    print(json.dumps(out), flush=True)
    return out


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="1,2,3")
    p.add_argument("--seconds", type=float, default=8.0)
    a = p.parse_args()
    outs = [control(a.workload, int(s), a.seconds)
            for s in a.seeds.split(",")]
    return 0 if not any(o["correct"] for o in outs) else 1


if __name__ == "__main__":
    sys.exit(main())
