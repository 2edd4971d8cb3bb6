"""The offline cell's comparison, driven end to end at a tiny size on the
CPU: a sound run is correct; faults planted in the timed path and the
float32 control make ``correct`` come out false; a call that never returns
ends the run at its deadline, with a non-zero exit and no result."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import benchtiny

CELL = "fig5-fit-mll"
SEED = 2147483659


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchtiny.tiny_root(tmp_path_factory.mktemp("cells"))


def _run(root, *extra, seconds=2.0):
    return benchtiny.run_cell(root, CELL, SEED, seconds, *extra)


def _failed(r):
    return {k for k, c in r["checks"].items() if not c["value"] <= c["limit"]}


def test_sound_run_is_correct(root):
    r = _run(root)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"fit_s", "mll_s", "setup_s"}
    assert set(r["checks"]) == {"mean_rel", "var_rel", "mll_rel"}
    assert list(r)[-1] == "checks"


def test_float32_control_is_not_correct(root):
    r = _run(root, "--precision", "float32")
    assert not r["correct"], r["checks"]


def _core():
    from repro import core

    return core


def test_mhat_logdet_left_out(root, monkeypatch):
    import jax

    from repro.core import additive_gp as agp

    core = _core()
    # log p = -(quad + log|Mhat| + ...) / 2: adding half the term back
    # leaves it out
    no_mhat = jax.jit(lambda gp, key: agp.log_likelihood(gp, key)
                      + 0.5 * agp._logdet_mhat(gp, key))
    monkeypatch.setattr(core, "log_likelihood", no_mhat)
    r = _run(root)
    assert _failed(r) == {"mll_rel"}, r["checks"]


def test_one_prediction_altered(root, monkeypatch):
    core = _core()
    mean = core.posterior_mean
    monkeypatch.setattr(core, "posterior_mean",
                        lambda gp, X: mean(gp, X).at[0].multiply(1.0 + 1e-3))
    r = _run(root)
    assert _failed(r) == {"mean_rel"}, r["checks"]


def test_half_the_predictions_left_out(root, monkeypatch):
    core = _core()

    def half(f):
        def g(gp, X):
            out = np.array(f(gp, X))
            h = X.shape[0] // 2
            out[:h] = out[h:2 * h]  # the first half gets the second half's
            return out
        return g

    monkeypatch.setattr(core, "posterior_mean", half(core.posterior_mean))
    monkeypatch.setattr(core, "posterior_var", half(core.posterior_var))
    r = _run(root)
    assert {"mean_rel", "var_rel"} <= _failed(r), r["checks"]


def test_fit_that_keeps_its_first_state(root, monkeypatch):
    """Every round served by the warm round's posterior: the state never
    moves to the round's own data."""
    core = _core()
    fit, first = core.fit, []

    def stale(*a, **k):
        if not first:
            first.append(fit(*a, **k))
        return first[0]

    monkeypatch.setattr(core, "fit", stale)
    r = _run(root)
    assert {"mean_rel", "mll_rel"} <= _failed(r), r["checks"]


def test_traced_run_on_the_cpu(tmp_path):
    """``--trace 1`` end to end: the slice starts at a round; on the CPU the
    device readers find no device planes and give nothing."""
    root = benchtiny.tiny_root(tmp_path, copy=True)
    path = os.path.join(root, "benchmarks", "cells", "traffic", "rounds.json")
    mix = json.load(open(path))
    mix.update(trace_at_s=0.5, trace_seconds=1.0)
    json.dump(mix, open(path, "w"))
    r = _run(root, "--trace", "1", seconds=3.0)
    assert r["correct"], r["checks"]
    assert r["metrics"] == {}
    assert r["device"]["window_s"] > 0 and "breakdown" in r


HANG = """
import sys, threading
sys.path.insert(0, {bench!r})
import benchtiny
from repro import core

mean, calls = core.posterior_mean, []


def never_returns(gp, X):
    calls.append(1)
    if len(calls) > 1:  # the warm round's returns; the window's never does
        threading.Event().wait()
    return mean(gp, X)


core.posterior_mean = never_returns
print(benchtiny.run_cell({root!r}, {cell!r}, 7, 2.0))
"""


def test_deadline_ends_a_call_that_never_returns(tmp_path):
    root = benchtiny.tiny_root(tmp_path, copy=True)
    path = os.path.join(root, "benchmarks", "cells", "traffic", "rounds.json")
    mix = json.load(open(path))
    mix["round_deadline_s"] = 3
    json.dump(mix, open(path, "w"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(benchtiny.REPO, "src"))
    code = HANG.format(bench=os.path.dirname(os.path.abspath(__file__)),
                       root=root, cell=CELL)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode != 0
    assert "'correct'" not in p.stdout
    assert "Timeout" in p.stderr and "never_returns" in p.stderr, p.stderr[-3000:]
