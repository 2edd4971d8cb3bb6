"""Cells, mixes, the drivers of traffic kinds and per-layer metrics are
found by name: a new file adds one, with no edit to the harness."""
import json
import os
import shutil

import pytest

import benchtiny
import spec


def test_every_cell_resolves():
    bench = spec.benchmark()
    for w in bench["workloads"]:
        c = spec.cell(w["name"])
        assert c.config["name"] == w["config"]
        assert callable(spec.driver(c.traffic["kind"]).run)
        names = {m["name"] for m in c.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert c.per_layer, w["name"]
        for m in c.per_layer:
            assert callable(spec.layer_reader(m["name"]))


def test_every_layer_metric_has_a_reader():
    bench = spec.benchmark()
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        assert os.path.exists(os.path.join(benchtiny.CELLS, "layers",
                                           m["name"] + ".py")), m["name"]
        # each cell it names reports the end-to-end metric it moves
        for w in m["workloads"]:
            assert m["name"] in [x["name"] for x in spec.cell(w).per_layer]


def test_new_files_add_a_cell_and_a_metric(tmp_path):
    """A copy of the benchmark's directory plus new files: a config, a mix,
    a reader, and entries in BENCHMARK.json. The harness's code is the
    copy's, unchanged."""
    root = tmp_path
    shutil.copytree(benchtiny.CELLS, root / "benchmarks" / "cells")
    bench = spec.benchmark()
    for c in bench["configs"]:
        os.makedirs(root / os.path.dirname(c["file"]), exist_ok=True)
        shutil.copy(os.path.join(benchtiny.REPO, c["file"]), root / c["file"])
    conf = spec.load_json(os.path.join(benchtiny.REPO, bench["configs"][0]["file"]))
    conf["name"] = "stream-rastrigin-d10-w4096"
    conf["function"] = "rastrigin"
    cfile = "benchmarks/cells/configs/stream-rastrigin-d10-w4096.json"
    (root / cfile).write_text(json.dumps(conf))
    mix = spec.traffic("ycsb-b")
    mix["rate_per_s"] = 3.0
    (root / "benchmarks/cells/traffic/ycsb-c.json").write_text(json.dumps(mix))
    (root / "benchmarks/cells/layers/queue_depth.py").write_text(
        "def read(run):\n    return run.counters.get('depth')\n")
    bench["configs"].append({"name": conf["name"], "source": "x", "file": cfile,
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "rastrigin-ycsb-c", "config": conf["name"],
                               "traffic": "ycsb-c", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "queue_depth", "unit": "1",
                               "better": "lower", "source": "program_counter",
                               "layer": "engine", "moves": "query_p50_ms",
                               "workloads": ["rastrigin-ycsb-c"]})
    for m in bench["end_to_end"]:
        if m["name"] == "query_p50_ms":
            m["workloads"].append("rastrigin-ycsb-c")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    c = spec.cell("rastrigin-ycsb-c", root=str(root))
    assert c.config["function"] == "rastrigin" and c.traffic["rate_per_s"] == 3.0
    # the existing metrics name their cells; the new one reports its own
    assert [m["name"] for m in c.per_layer] == ["queue_depth"]
    read = spec.layer_reader("queue_depth", str(root))
    assert read(type("R", (), {"counters": {"depth": 4}})()) == 4
    # the existing cells are untouched by the addition
    assert [m["name"] for m in spec.cell("stream-ycsb-b", root=str(root)).per_layer] \
        == [m["name"] for m in spec.cell("stream-ycsb-b").per_layer]


def test_a_traffic_kind_is_a_driver_file(tmp_path):
    """A mix whose kind has no alias runs ``<kind>.py`` beside the harness;
    ``open_loop`` runs ``serve.py``; a kind with no file is refused."""
    root = tmp_path
    shutil.copytree(benchtiny.CELLS, root / "benchmarks" / "cells")
    (root / "benchmarks/cells/echo.py").write_text(
        "def run(cell, seed, seconds, ctx):\n"
        "    ctx.window_open()\n"
        "    ctx.window_close()\n"
        "    return {'e2e': {'echo_s': seconds + seed}, 'attempted': 3,\n"
        "            'failed': 0, 'checks': [('gap', 0.0, 1.0)],\n"
        "            'complete': True}\n")
    (root / "benchmarks/cells/traffic/echo-mix.json").write_text(json.dumps(
        {"kind": "echo", "about": "a mix of a new kind"}))
    bench = spec.benchmark()
    bench["workloads"].append({"name": "echo-cell", "config": bench["configs"][0]["name"],
                               "traffic": "echo-mix", "chips": 1, "why": "x"})
    bench["end_to_end"].append({"name": "echo_s", "unit": "s", "better": "lower",
                                "bound": 0.1, "source": "host_clock",
                                "workloads": ["echo-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    for c in bench["configs"]:
        os.makedirs(root / os.path.dirname(c["file"]), exist_ok=True)
        shutil.copy(os.path.join(benchtiny.REPO, c["file"]), root / c["file"])

    r = benchtiny.run_cell(str(root), "echo-cell", 2, 1.5)
    assert r["correct"] and r["attempted"] == 3
    assert r["metrics"]["echo_s"] == {"value": 3.5, "unit": "s"}
    assert set(r["metrics"]) == {"echo_s", "setup_s"}
    assert spec.driver("echo", str(root)).__file__ == str(
        root / "benchmarks/cells/echo.py")
    assert spec.driver("open_loop", str(root)).__file__.endswith(
        os.path.join("cells", "serve.py"))
    for bad in ("no_such_kind", "../cells/serve", ""):
        with pytest.raises(KeyError):
            spec.driver(bad, str(root))


def test_pending_cells_resolve(tmp_path):
    """The cells kept out of BENCHMARK.json for now resolve from their own
    files, with a driver, a reader per metric, and every metric moving an
    end-to-end metric the cell reports, as a BENCHMARK.json entry must."""
    root = benchtiny.tiny_root(tmp_path)
    pending = spec.load_json(benchtiny.PENDING)
    assert pending["workloads"]
    for w in pending["workloads"]:
        c = spec.cell(w["name"], root=root)
        assert callable(spec.driver(c.traffic["kind"], root).run)
        names = {m["name"] for m in c.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert {m["name"] for m in c.per_layer} == {
            m["name"] for m in pending["per_layer"]
            if w["name"] in m["workloads"]}
        for m in c.per_layer:
            assert m["moves"] in names
            assert callable(spec.layer_reader(m["name"], root))
