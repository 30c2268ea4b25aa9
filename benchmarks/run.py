"""Benchmark driver: one section per paper table/figure + substrate benches.

Prints ``name,us_per_call_or_metric,derived`` CSV rows; with ``--json DIR``
each section additionally writes machine-readable rows to
``DIR/BENCH_<section>.json`` (name, metric, derived, timestamp, plus host
provenance: cpu count, platform, python and jax backend/devices) so the
perf trajectory across PRs can be diffed without scraping stdout — and
attributed to the machine that produced it.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--skip-samsara]
                                          [--sections LIST]
                                          [--samsara-figs LIST]
                                          [--quick-models] [--json DIR]

The CI smoke tier tracks the serving-path perf trajectory per PR with
``--sections samsara --samsara-figs fig_ms,fig_pipeline --quick-models
--json reports/benchmarks`` (tiny models, short streams, no result
cache) and uploads the ``BENCH_*.json`` files as workflow artifacts.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import platform
import sys
import time
import traceback
from typing import List


@functools.lru_cache(maxsize=1)
def _host_info() -> dict:
    """Host provenance stamped on every --json row: perf numbers are
    meaningless in a cross-PR diff without knowing what ran them."""
    info = {
        "host_cpus": os.cpu_count(),
        "host_platform": platform.platform(),
        "host_python": platform.python_version(),
    }
    try:
        import jax

        info["jax_backend"] = jax.default_backend()
        info["jax_devices"] = [str(d) for d in jax.devices()]
        info["jax_version"] = jax.__version__
    except Exception:  # noqa: BLE001 — no-jax hosts still get CPU info
        pass
    return info


def _structured(row: str) -> dict:
    """Split a CSV row into JSON fields with a *numeric* metric.

    Kernel/serving rows are ``name,value,derived``; samsara rows are
    ``section,label,value,derived`` — for those the label folds into the
    name (``fig_ms.forwards``) so ``metric`` always carries the
    measurement.  The derived remainder keeps its commas."""
    parts = row.split(",")
    name = parts[0]
    metric = parts[1] if len(parts) > 1 else ""
    rest = parts[2:]
    if len(parts) >= 3 and metric != "ERROR":
        try:
            float(metric)
        except ValueError:
            name = f"{parts[0]}.{parts[1]}"
            metric = parts[2]
            rest = parts[3:]
    try:
        metric = float(metric)
    except ValueError:
        pass                    # ERROR / non-numeric stays a string
    return {
        "name": name,
        "metric": metric,
        "derived": ",".join(rest),
        "timestamp": time.time(),
        **_host_info(),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fig1b only for the Saṃsāra section")
    ap.add_argument("--skip-samsara", action="store_true")
    ap.add_argument("--sections", default=None,
                    help="comma list of top-level sections to run "
                         "(kernels,serving,samsara,fig_semantic,"
                         "fig_fused,fig_chaos — the last three are "
                         "figures promoted to their own sections, each "
                         "written to BENCH_<name>.json); default: all")
    ap.add_argument("--samsara-figs", default=None,
                    help="comma list of Saṃsāra figures (fig1b,fig5,"
                         "table2,fig_mq,fig_ms,fig_pipeline,fig_fleet,"
                         "fig_semantic,fig_fused); overrides --quick's "
                         "figure choice")
    ap.add_argument("--quick-models", action="store_true",
                    help="tiny smoke models + short serving streams for "
                         "the Saṃsāra section (disables its result cache "
                         "— smoke rows must never mix with full-model "
                         "ones); the CI smoke tier uses this")
    ap.add_argument("--json", metavar="DIR", default=None,
                    help="also write BENCH_<section>.json files to DIR")
    ap.add_argument("--write-baseline", action="store_true",
                    help="after a fully-successful run, copy this run's "
                         "BENCH_*.json into DIR/baseline/ — the anchor "
                         "scripts/bench_gate.py compares against "
                         "(requires --json)")
    args = ap.parse_args()
    assert not args.write_baseline or args.json, \
        "--write-baseline needs --json DIR"
    from repro.common.utils import enable_compile_cache

    enable_compile_cache()

    wanted = args.sections.split(",") if args.sections else None
    known = {"kernels", "serving", "samsara", "fig_semantic", "fig_fused",
             "fig_chaos"}
    assert wanted is None or set(wanted) <= known, \
        f"unknown sections {sorted(set(wanted) - known)} (known: {sorted(known)})"

    def want(name: str) -> bool:
        return wanted is None or name in wanted

    sections = []
    if want("kernels") or want("serving"):
        from benchmarks import kernel_bench, serving_bench

        if want("kernels"):
            sections.append(("kernels", kernel_bench.run_all))
        if want("serving"):
            sections.append(("serving", serving_bench.run_all))
    if not args.skip_samsara and want("samsara"):
        from benchmarks import samsara_bench

        figs = args.samsara_figs.split(",") if args.samsara_figs else None
        # a figure also requested as its own top-level section must not
        # run twice when the samsara default list would include it
        exclude = [s for s in ("fig_semantic", "fig_fused", "fig_chaos")
                   if wanted is not None and s in wanted] or None
        sections.append(("samsara",
                         lambda: samsara_bench.run_all(
                             quick=args.quick,
                             quick_models=args.quick_models,
                             sections=figs, exclude=exclude)))
    for own in ("fig_semantic", "fig_fused", "fig_chaos"):
        if want(own) and wanted is not None:
            # its own top-level section (not just a samsara figure) so
            # these rows land in a dedicated BENCH_<name>.json next to
            # the existing artifacts
            from benchmarks import samsara_bench

            sections.append((own,
                             lambda own=own: samsara_bench.run_all(
                                 quick=args.quick,
                                 quick_models=args.quick_models,
                                 sections=[own])))

    print("name,us_per_call,derived")
    failed: List[str] = []
    for name, fn in sections:
        rows: List[str] = []
        try:
            for row in fn():
                print(row, flush=True)
                rows.append(row)
        except Exception:  # noqa: BLE001
            failed.append(name)
            err = f"{name},ERROR,{traceback.format_exc()[-300:]!r}"
            print(err)
            rows.append(err)       # the JSON must carry the reason too
        if args.json:
            os.makedirs(args.json, exist_ok=True)
            path = os.path.join(args.json, f"BENCH_{name}.json")
            with open(path, "w") as f:
                json.dump({"section": name,
                           "ok": name not in failed,
                           "rows": [_structured(r) for r in rows]},
                          f, indent=1)
    if args.json:
        # perf trajectory: every --json run appends its rows (host-keyed)
        # to the JSONL history riding next to the snapshots
        from benchmarks.history import append_history

        kept = append_history(args.json,
                              os.path.join(args.json, "history.jsonl"))
        print(f"history: {kept} rows appended to "
              f"{os.path.join(args.json, 'history.jsonl')}",
              file=sys.stderr)
    if args.write_baseline and not failed:
        import shutil

        bdir = os.path.join(args.json, "baseline")
        os.makedirs(bdir, exist_ok=True)
        for name, _ in sections:
            src = os.path.join(args.json, f"BENCH_{name}.json")
            if os.path.exists(src):
                shutil.copy2(src, bdir)
        print(f"baseline refreshed under {bdir}", file=sys.stderr)
    if failed:
        print(f"FAILED sections: {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)
    sys.exit(0)


if __name__ == "__main__":
    main()
