"""Readings that set the limits of ``correct``, on the chip.

  python3 bench/control.py --workload <cell> --seeds a,b,c --seconds <s>

For each seed, in one process: a run of the cell (short window, the
cell's own load and sizes), judged twice against the plain reference:
as served (``program``, the lower reading of each compared number), and
with the control in the program's place (``compared``, the upper
reading): the program's own bfloat16 path of the extract on the rows the
served extract read, and the reference's preprocess in bfloat16.
``correct`` is the harness's verdict on the control, under the same
limits; it has to come out false.  One JSON line per seed:
``CONTROL {...}``.  The benchmark's own runs never run this.
"""
import time

T_PROCESS_NS = time.perf_counter_ns()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
# libtpu logs to /tmp/tpu_logs unless told otherwise: write nothing there
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import harness
    import layout
    for seed in [int(s) for s in args.seeds.split(",")]:
        out = harness.run(layout.cell(args.workload), seed, args.seconds,
                          False, time.perf_counter_ns(), control=True)
        print("CONTROL " + json.dumps(
            {"cell": args.workload, "seed": seed,
             "correct": out["correct"], "program": out["program"],
             "compared": {k: v["value"]
                          for k, v in out["compared"].items()}}),
              flush=True)
        del out
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
