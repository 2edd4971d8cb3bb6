#!/usr/bin/env python3
"""Time the jax backend's two unpivoted symmetric-band solves on one chip.

    python scripts/solve_crossover.py

For each block-row count ``n / w`` it times the sequential scan LU
(``core.banded._solve_scan``) against the log-depth block cyclic reduction
(``kernels.cr_jax.block_cr_solve_jax``, unpivoted: its levels rolled into
loops) and, in some cases, CR's compacted unrolled levels
(``cr_jax._solve_compacted``), on a diagonally dominant float64
system of ``D = 10`` bands and ``B = 32`` right-hand sides, the shape of the
serve engine's variance PCG. One jitted program chains ``CHAIN`` solves in a
``fori_loop`` (as the PCG does), so a solve's time is the program's device
time over ``CHAIN``, with no dispatch in it; the executable's serialized
size is what a program holding the solve must load. Prints one JSON line
per case and a table; ``kernels.ops.CR_MIN_BLOCK_ROWS`` is the smallest ``n / w`` at
which CR wins. It refuses to run without a TPU.
"""
from __future__ import annotations

import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

from repro.core.banded import Banded, _solve_scan  # noqa: E402
from repro.kernels.cr_jax import (_solve_compacted,  # noqa: E402
                                  block_cr_solve_jax)
from jax.experimental.serialize_executable import serialize  # noqa: E402

D, B, CHAIN, REPS = 10, 32, 20, 7
CASES = [(1, nb) for nb in (16, 32, 64, 128, 256, 512, 1024, 2048, 4096)] + [
    (2, nb) for nb in (128, 512, 2048)]
COMPACTED = {(1, 512), (1, 4096)}  # cases that also time the compacted levels


def system(w: int, n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    band = rng.uniform(-1.0, 1.0, (D, n, 2 * w + 1))
    band[..., w] = 2.0 * (2 * w + 1) + rng.uniform(0.0, 1.0, (D, n))
    for m in range(1, w + 1):  # zero the entries outside the matrix
        band[:, :m, w - m] = 0.0
        band[:, n - m:, w + m] = 0.0
    rhs = rng.standard_normal((D, n, B))
    return jnp.asarray(band), jnp.asarray(rhs)


def chained(solve):
    def run(band, rhs):
        return jax.lax.fori_loop(
            0, CHAIN, lambda _, x: solve(band, rhs + 1e-3 * x), rhs)
    return jax.jit(run)


def time_one(fn, band, rhs):
    t0 = time.perf_counter()
    exe = fn.lower(band, rhs).compile()
    compile_s = time.perf_counter() - t0
    out = exe(band, rhs).block_until_ready()
    ts = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        exe(band, rhs).block_until_ready()
        ts.append(time.perf_counter() - t0)
    return (compile_s, statistics.median(ts) / CHAIN * 1e3, out,
            len(serialize(exe)[0]) / 2**20)


def main() -> int:
    jax.config.update("jax_enable_x64", True)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"refused: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    rows = []
    for w, nb in CASES:
        n = w * nb
        band, rhs = system(w, n)
        scan = chained(lambda b, r, w=w: _solve_scan(Banded(b, w, w), r,
                                                     pivot=False))
        cr = chained(lambda b, r, w=w: block_cr_solve_jax(b, r, w,
                                                          pivot=False))
        cs, ts, xs, ms = time_one(scan, band, rhs)
        cc, tc, xc, mc = time_one(cr, band, rhs)
        rel = float(jnp.max(jnp.abs(xs - xc)) / jnp.max(jnp.abs(xs)))
        row = {"w": w, "n": n, "block_rows": nb, "scan_ms": ts, "cr_ms": tc,
               "scan_compile_s": cs, "cr_compile_s": cc, "scan_mib": ms,
               "cr_mib": mc, "rel": rel, "device": dev.device_kind}
        if (w, nb) in COMPACTED:
            comp = chained(lambda b, r, w=w: _solve_compacted(b, r, w, False))
            row["compacted_compile_s"], row["compacted_ms"], _, row[
                "compacted_mib"] = time_one(comp, band, rhs)
        rows.append(row)
        print(json.dumps(row), flush=True)
    print("| w | n | n/w | scan ms/solve | CR ms/solve | scan/CR | "
          "compile s scan / CR | MiB scan / CR | rel diff |")
    for r in rows:
        print(f"| {r['w']} | {r['n']} | {r['block_rows']} | {r['scan_ms']:.4f}"
              f" | {r['cr_ms']:.4f} | {r['scan_ms'] / r['cr_ms']:.2f} | "
              f"{r['scan_compile_s']:.2f} / {r['cr_compile_s']:.2f} | "
              f"{r['scan_mib']:.1f} / {r['cr_mib']:.1f} | {r['rel']:.1e} |")
    return 0


if __name__ == "__main__":
    sys.exit(main())
