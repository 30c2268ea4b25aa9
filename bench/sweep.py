"""Find a cell's knee on the chip: the most feeds whose backlog holds.

  python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --feeds 1,2,3,4

Runs the cell once per feed count, in one process, with the cell's own
mix and fixed arrivals, and prints one line per count, ``SWEEP {...}``:
the ingest lag's trend over the window (ms per s, a least-squares slope
over every pull), the drain after the last due frame, and the end-to-end
metrics.  The last line, ``KNEE {...}``, names the knee, the largest
count at which the slope of this and of every smaller count swept stays
under ``--hold`` ms/s, and the feed count of a cell at four fifths of it.
"""
import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
# libtpu logs to /tmp/tpu_logs unless told otherwise: write nothing there
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def knee(slopes, hold: float) -> int:
    """Largest swept count whose slope, and every smaller count's, holds
    under ``hold``; 0 where even the smallest grows."""
    k = 0
    for feeds in sorted(slopes):
        if slopes[feeds] >= hold:
            break
        k = feeds
    return k


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--feeds", required=True)
    ap.add_argument("--hold", type=float, default=10.0)
    args = ap.parse_args(argv)
    import harness
    import layout
    slopes = {}
    for feeds in [int(k) for k in args.feeds.split(",")]:
        cell = layout.cell(args.workload)
        cell["feeds"] = feeds
        backlog = {}
        out = harness.run(cell, args.seed + feeds, args.seconds, False,
                          time.perf_counter_ns(), backlog=backlog)
        slopes[feeds] = backlog["slope_ms_per_s"]
        print("SWEEP " + json.dumps(
            {"cell": args.workload, "feeds": feeds, **backlog,
             "correct": out["correct"],
             "metrics": {k: v["value"] for k, v in out["metrics"].items()}}),
              flush=True)
        del out
        gc.collect()
    k = knee(slopes, args.hold)
    print("KNEE " + json.dumps({"cell": args.workload, "knee": k,
                                "cell_feeds": max(1, round(0.8 * k))}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
