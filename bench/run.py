"""Benchmark entry: one run of one cell on the chip it is started on.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints the compared numbers with their limits as the last lines of
standard error, and one JSON object as the last line of standard output.
Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for, or where the device kind has no peaks in
``bench/peaks.json``.
"""
import time

T_PROCESS_NS = time.perf_counter_ns()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
# libtpu logs to /tmp/tpu_logs unless told otherwise: write nothing there
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import harness
    import layout
    try:
        out = harness.run(layout.cell(args.workload), args.seed,
                          args.seconds, bool(args.trace), T_PROCESS_NS)
    except (harness.NoDevice, layout.UnknownDevice) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    compared = out["compared"]
    for k, v in compared.items():
        print(f"{k} {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
