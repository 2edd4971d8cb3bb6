"""The serving cells' comparison, driven end to end at a tiny size on the
CPU: a sound run is correct, and each fault the cell can have, planted in
the timed path, and the float32 control, make ``correct`` come out false."""
import numpy as np
import pytest

import benchtiny

CELL = "stream-ycsb-a"  # half of its operations are inserts
ARGS = ("--rate", "6")
SEED = 2147483659


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchtiny.tiny_root(tmp_path_factory.mktemp("cells"))


def _run(root, *extra):
    return benchtiny.run_cell(root, CELL, SEED, 2.0, *ARGS, *extra)


def test_sound_run_is_correct(root):
    r = _run(root)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] == 12
    m = r["metrics"]
    assert {"query_p50_ms", "setup_s"} == set(m)
    assert list(r)[-1] == "checks"


def test_float32_control_is_not_correct(root):
    r = _run(root, "--precision", "float32")
    assert not r["correct"], r["checks"]


def _engine():
    from repro.streaming import gp_engine

    return gp_engine


def test_mutation_that_keeps_the_state(root, monkeypatch):
    ge = _engine()
    monkeypatch.setattr(ge, "stream_insert", lambda gp, *a, **k: gp)
    monkeypatch.setattr(ge, "stream_evict", lambda gp, *a, **k: gp)
    r = _run(root)
    assert not r["correct"], r["checks"]


def test_half_the_slots_left_out(root, monkeypatch):
    ge = _engine()
    step = ge._engine_step

    def half(gp, X, *a, **k):
        out = [np.array(o) for o in step(gp, X, *a, **k)]
        h = X.shape[0] // 2
        for o in out:  # the first half left out: it gets the second half's
            o[:h] = o[h:2 * h]
        return tuple(out)

    monkeypatch.setattr(ge, "_engine_step", half)
    r = _run(root)
    assert not r["correct"], r["checks"]


def test_one_answer_altered(root, monkeypatch):
    ge = _engine()
    step = ge._engine_step

    def altered(gp, X, *a, **k):
        val, grad, mu, var, xn = [np.array(o) for o in step(gp, X, *a, **k)]
        mu[0] *= 1.0 + 1e-3  # slot 0's mean, as produced
        return val, grad, mu, var, xn

    monkeypatch.setattr(ge, "_engine_step", altered)
    r = _run(root)
    assert not r["correct"], r["checks"]
    assert r["checks"]["mean_rel"]["value"] > r["checks"]["mean_rel"]["limit"]
