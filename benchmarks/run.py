"""Benchmark harness — one entry per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--full]``

Prints ``name,...`` CSV lines per benchmark and writes benchmarks/results.json.
Default sizes are CPU-scaled (this container); --full uses the paper's grids.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import jax

from repro import compile_cache

jax.config.update("jax_enable_x64", True)
compile_cache.configure()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--out", default=os.path.join(os.path.dirname(__file__),
                                                  "results.json"))
    args = ap.parse_args()

    from . import backend_ablation, capacity_streaming, fig5_prediction, \
        fig6_bayesopt, fleet_serving, fused_sweep, gband_update, health, \
        megasolve, multigrid, streaming_updates, table1_complexity

    rows: list[dict] = []
    print("== Fig 5: prediction RMSE/time vs n ==", flush=True)
    # non-full grids are a CPU smoke (scripts/check.sh budget); --full is the
    # paper's grid
    ns = (500, 1000, 2000, 4000, 8000, 16000, 30000) if args.full else (
        500, 1000)
    fig5_prediction.run(fname="schwefel", D=10, ns=ns,
                        reps=2 if not args.full else 5, out_rows=rows)
    if args.full:
        fig5_prediction.run(fname="rastrigin", D=10, ns=ns, reps=3,
                            out_rows=rows)

    print("== Fig 6: Bayesian optimization ==", flush=True)
    fig6_bayesopt.run(D=5, budget=40 if args.full else 4,
                      n_init=20, out_rows=rows)

    print("== Table 1: per-term complexity ==", flush=True)
    table1_complexity.run(
        D=5, ns=(1000, 2000, 4000, 8000, 16000) if args.full else
        (1000, 2000), out_rows=rows)

    print("== Backend ablation: jax scan vs Pallas kernels ==", flush=True)
    backend_ablation.run(full=args.full, out_rows=rows)

    print("== Fused backfitting sweep: 1 dispatch/iteration vs 4 ==",
          flush=True)
    fused_rows: list[dict] = []
    fused_sweep.run(ns=(1000, 4096, 16_384) if args.full else (1000, 4096),
                    out_rows=fused_rows)
    rows += fused_rows

    print("== Whole-solve mega-kernel: 1 dispatch per solve vs per "
          "iteration ==", flush=True)
    mega_rows: list[dict] = []
    megasolve.run(ns=(1000, 4096, 16_384) if args.full else (1000, 4096),
                  out_rows=mega_rows)
    rows += mega_rows

    print("== Streaming: incremental insert vs refit ==", flush=True)
    streaming_rows: list[dict] = []
    streaming_updates.run(
        ns=(1000, 10000, 100000) if args.full else (500, 1000),
        reps=3 if args.full else 2, out_rows=streaming_rows)
    rows += streaming_rows

    print("== Capacity streaming: zero-retrace inserts + bounded-memory "
          "evict ==", flush=True)
    capacity_rows: list[dict] = []
    if args.full:
        capacity_streaming.run(n0=256, capacity=4096, inserts=256, evicts=64,
                               D=5, out_rows=capacity_rows)
    else:
        capacity_streaming.run(n0=32, capacity=512, inserts=256, evicts=32,
                               D=2, baseline_inserts=8,
                               out_rows=capacity_rows)
    rows += capacity_rows

    print("== Fleet serving: multi-tenant throughput, flat compile count ==",
          flush=True)
    fleet_rows: list[dict] = []
    fleet_serving.run(Ts=(1, 8, 64, 256) if args.full else (1, 8, 64),
                      out_rows=fleet_rows)
    rows += fleet_rows

    print("== Kernel multigrid: V-cycle vs plain PCG iterations-to-tol ==",
          flush=True)
    mg_rows: list[dict] = []
    multigrid.run(ns=(4096, 16384) if args.full else (4096,),
                  reps=3 if args.full else 1, out_rows=mg_rows)
    rows += mg_rows

    print("== Windowed Gband maintenance: per-mutation cost vs n ==",
          flush=True)
    gband_rows: list[dict] = []
    gband_update.run(
        ns=(1024, 4096, 16384) if args.full else (256, 1024, 8192),
        reps=10 if args.full else 5, out_rows=gband_rows)
    rows += gband_rows

    print("== Serve-path health: verdict/sentinel overhead + dense-stream "
          "rescue ==", flush=True)
    health_rows: list[dict] = []
    health.run(ns=(2048, 8192) if args.full else (2048, 4096),
               reps=5, out_rows=health_rows)
    rows += health_rows

    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"wrote {len(rows)} rows to {args.out}", flush=True)

    # machine-readable perf-trajectory artifact for the streaming path
    stream_out = os.path.join(os.path.dirname(args.out), "BENCH_streaming.json")
    with open(stream_out, "w") as f:
        json.dump(streaming_rows, f, indent=1)
    print(f"wrote {len(streaming_rows)} rows to {stream_out}", flush=True)

    # perf artifact for the block-CR solve kernel (CR vs LU vs scan rows)
    cr_rows = [r for r in rows if r.get("bench") == "block_cr_ablation"]
    cr_out = os.path.join(os.path.dirname(args.out), "BENCH_block_cr.json")
    with open(cr_out, "w") as f:
        json.dump(cr_rows, f, indent=1)
    print(f"wrote {len(cr_rows)} rows to {cr_out}", flush=True)

    # perf artifact for the fused backfitting-sweep kernel (fused vs unfused)
    fused_out = os.path.join(os.path.dirname(args.out),
                             "BENCH_fused_sweep.json")
    with open(fused_out, "w") as f:
        json.dump(fused_rows, f, indent=1)
    print(f"wrote {len(fused_rows)} rows to {fused_out}", flush=True)

    # retrace/memory artifact for the capacity-padded streaming path (PR 5
    # acceptance: <= 2 insert-step compilations across a 256-insert stream)
    cap_out = os.path.join(os.path.dirname(args.out), "BENCH_capacity.json")
    with open(cap_out, "w") as f:
        json.dump(capacity_rows, f, indent=1)
    print(f"wrote {len(capacity_rows)} rows to {cap_out}", flush=True)

    # multi-tenant fleet serving artifact (PR 6 acceptance: throughput
    # scaling in T with <= 2 retraces per capacity-tier group)
    fleet_out = os.path.join(os.path.dirname(args.out), "BENCH_fleet.json")
    with open(fleet_out, "w") as f:
        json.dump(fleet_rows, f, indent=1)
    print(f"wrote {len(fleet_rows)} rows to {fleet_out}", flush=True)

    # kernel-multigrid preconditioner artifact (PR 7 acceptance: kmg_iters <
    # plain_iters at the largest n on both backends at the same tol)
    mg_out = os.path.join(os.path.dirname(args.out), "BENCH_multigrid.json")
    with open(mg_out, "w") as f:
        json.dump(mg_rows, f, indent=1)
    print(f"wrote {len(mg_rows)} rows to {mg_out}", flush=True)

    # windowed Gband maintenance artifact (PR 8 acceptance: per-mutation
    # windowed cost flat in n while the full RGF sweep grows linearly, and
    # windowed faster at the largest n)
    gband_out = os.path.join(os.path.dirname(args.out), "BENCH_gband.json")
    with open(gband_out, "w") as f:
        json.dump(gband_rows, f, indent=1)
    print(f"wrote {len(gband_rows)} rows to {gband_out}", flush=True)

    # serve-path health artifact (PR 9 acceptance: verdict + sentinel
    # overhead < 5% on the healthy path; the dense-oversampled stream serves
    # correct variances under the stock windowed config)
    health_out = os.path.join(os.path.dirname(args.out), "BENCH_health.json")
    with open(health_out, "w") as f:
        json.dump(health_rows, f, indent=1)
    print(f"wrote {len(health_rows)} rows to {health_out}", flush=True)

    # whole-solve mega-kernel artifact (PR 10 acceptance: one pallas_call
    # per complete solve, zero in host-level loops, same realized iteration
    # count as the per-iteration host loop)
    mega_out = os.path.join(os.path.dirname(args.out), "BENCH_megasolve.json")
    with open(mega_out, "w") as f:
        json.dump(mega_rows, f, indent=1)
    print(f"wrote {len(mega_rows)} rows to {mega_out}", flush=True)

    _append_summary(os.path.join(os.path.dirname(args.out),
                                 "BENCH_summary.json"), rows, args.full)


def _digest(rows: list[dict]) -> dict:
    """Per-bench median of every numeric field, plus the row count."""
    import statistics

    by: dict[str, list[dict]] = {}
    for r in rows:
        by.setdefault(str(r.get("bench", r.get("name", "?"))), []).append(r)
    out = {}
    for bench, rs in sorted(by.items()):
        keys = sorted({k for r in rs for k in r})
        med = {}
        for k in keys:
            vals = [r[k] for r in rs
                    if isinstance(r.get(k), (int, float))
                    and not isinstance(r.get(k), bool)]
            if vals:
                med[k] = statistics.median(vals)
        med["rows"] = len(rs)
        out[bench] = med
    return out


def _append_summary(path: str, rows: list[dict], full: bool) -> None:
    """Append this run's digest to the cross-PR perf trajectory.

    ``BENCH_summary.json`` is a list, one entry per benchmark run, keyed by
    the git revision — committed alongside the code so the perf history
    stays machine-readable across PRs. Re-runs at the same revision and
    grid replace their previous entry instead of duplicating it.
    """
    import subprocess

    try:
        rev = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             capture_output=True, text=True, check=True,
                             cwd=os.path.dirname(os.path.abspath(__file__)),
                             ).stdout.strip()
    except (OSError, subprocess.CalledProcessError):
        rev = "unknown"
    try:
        with open(path) as f:
            history = json.load(f)
        assert isinstance(history, list)
    except (OSError, ValueError, AssertionError):
        history = []
    history = [e for e in history
               if not (e.get("rev") == rev and e.get("full") == full)]
    history.append({"rev": rev, "full": full, "benches": _digest(rows)})
    with open(path, "w") as f:
        json.dump(history, f, indent=1)
    print(f"appended summary for {rev} to {path} "
          f"({len(history)} entries)", flush=True)


if __name__ == "__main__":
    main()
