"""Tiny copies of the benchmark's cells, for CPU tests of the harness.

A tiny root holds a ``BENCHMARK.json`` whose configurations are the real
ones cut to a size a test run can hold, and the benchmark's own directory
(linked, or copied where a test changes it), so the harness runs end to end
exactly as on the chip, minus the look for a chip.
"""
from __future__ import annotations

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELLS = os.path.join(REPO, "benchmarks", "cells")
if CELLS not in sys.path:
    sys.path.insert(0, CELLS)

# Tiny sizes, and limits for them. At n=512 the fit's 40 PCG iterations and
# the mutations' 10 warm ones converge less far than at the cells' sizes.
# Sound runs of stream-ycsb-a at 6/s on the CPU, seeds 1, 2, 3000000005
# (worst of the three): mean 3.1e-5, var 2.0e-7, acq 3.1e-5, grad 1.1e-3.
# The limits lie above those and below what the faults give.
STREAM = {"n": 512, "window": 512, "batch_slots": 8,
          "limits": {"mean_rel": 3e-4, "var_rel": 1e-5, "acq_rel": 3e-4,
                     "grad_rel": 1e-2}}

# An offline configuration (no engine) at n=512. Sound runs of fig5-fit-mll
# on the CPU, 12 seeds (1-8, 2147483659, 3000000001-003), worst of each:
# mean 4.6e-6, var 6.7e-7, mll 6.3e-4 (the MLL's error is the bias of the
# stochastic log-det: 769 against 635 nats at 200 PCG iterations as at
# 40). The float32 control, seeds 1-3 and 2147483659: mean 3.7e-4-3.6e-3,
# var 1.7e-2-4.6e-2, mll 4.7e-4-1.2e-3; the faults of
# test_bench_faults_rounds.py read far above the limits.
ROUNDS = {"n": 512,
          "limits": {"mean_rel": 3e-5, "var_rel": 1e-5, "mll_rel": 3e-3}}


def tiny_config(conf: dict) -> dict:
    """A configuration cut to tiny size: a serving one (it has an
    ``engine``) to STREAM's sizes, an offline one to ROUNDS'."""
    conf = json.loads(json.dumps(conf))
    cut = STREAM if "engine" in conf else ROUNDS
    conf["n"] = cut["n"]
    conf["limits"] = dict(cut["limits"])
    if "engine" in conf:
        conf["engine"].update(window=STREAM["window"],
                              batch_slots=STREAM["batch_slots"])
    return conf


# Cells whose files are in the benchmark's directory but whose entries
# BENCHMARK.json leaves out while the program cannot run them on the chip
# (PERF.md, section 7): the tests run them at tiny size all the same.
PENDING = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "pending_cells.json")


def with_pending(bench: dict) -> dict:
    """``bench`` with the pending entries it does not hold yet."""
    with open(PENDING) as fh:
        pending = json.load(fh)
    for key, entries in pending.items():
        have = {e["name"] for e in bench[key]}
        bench[key] += [e for e in entries if e["name"] not in have]
    return bench


def tiny_root(tmp, copy: bool = False) -> str:
    """A checkout-like root under ``tmp`` with the cells, pending ones among
    them, cut to tiny size; ``copy`` copies the benchmark's directory
    instead of linking it, so that a test may change a mix."""
    tmp = str(tmp)
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = with_pending(json.load(fh))
    os.makedirs(os.path.join(tmp, "cfg"), exist_ok=True)
    for c in bench["configs"]:
        with open(os.path.join(REPO, c["file"])) as fh:
            conf = tiny_config(json.load(fh))
        c["file"] = os.path.join("cfg", os.path.basename(c["file"]))
        with open(os.path.join(tmp, c["file"]), "w") as fh:
            json.dump(conf, fh)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    os.makedirs(os.path.join(tmp, "benchmarks"), exist_ok=True)
    link = os.path.join(tmp, "benchmarks", "cells")
    if copy:
        shutil.copytree(CELLS, link, ignore=shutil.ignore_patterns(
            "__pycache__"))
    elif not os.path.exists(link):
        os.symlink(CELLS, link)
    return tmp


def run_cell(root, workload, seed, seconds=2.0, *extra):
    import run

    return run.run(["--workload", workload, "--seed", str(seed),
                    "--seconds", str(seconds), *extra],
                   require_tpu=False, root=root)
