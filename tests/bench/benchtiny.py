"""Tiny copies of the benchmark's cells, for CPU tests of the harness.

A tiny root holds a ``BENCHMARK.json`` whose configurations are the real
ones cut to a size a test run can hold, and the benchmark's own directory
(linked), so the harness runs end to end exactly as on the chip, minus the
look for a chip.
"""
from __future__ import annotations

import json
import os
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


def tiny_root(tmp) -> str:
    """A checkout-like root under ``tmp`` with the cells cut to tiny size."""
    tmp = str(tmp)
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    os.makedirs(os.path.join(tmp, "cfg"), exist_ok=True)
    for c in bench["configs"]:
        with open(os.path.join(REPO, c["file"])) as fh:
            conf = json.load(fh)
        conf["n"] = STREAM["n"]
        conf["limits"] = dict(STREAM["limits"])
        conf["engine"].update(window=STREAM["window"],
                              batch_slots=STREAM["batch_slots"])
        c["file"] = os.path.join("cfg", os.path.basename(c["file"]))
        with open(os.path.join(tmp, c["file"]), "w") as fh:
            json.dump(conf, fh)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    os.makedirs(os.path.join(tmp, "benchmarks"), exist_ok=True)
    link = os.path.join(tmp, "benchmarks", "cells")
    if not os.path.exists(link):
        os.symlink(CELLS, link)
    return tmp


def run_cell(root, workload, seed, seconds=2.0, *extra):
    import run

    return run.run(["--workload", workload, "--seed", str(seed),
                    "--seconds", str(seconds), *extra],
                   require_tpu=False, root=root)
