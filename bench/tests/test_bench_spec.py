"""BENCHMARK.json against the contract it is written to, and the lookup of
every cell's files by name."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_and_entry_keys(bench):
    assert set(bench) == TOP
    assert len(json.dumps(bench)) <= 64 * 1024
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_command_and_paths(bench):
    paths = bench["paths"]
    assert 1 <= len(paths) <= 16
    for p in paths:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (spec.ROOT / p).is_dir()
    cmd = bench["command"]
    assert 1 <= len(cmd) <= 32 and all(one_line(w) for w in cmd)
    for word in cmd:
        assert not word.startswith("/") and ".." not in word
    assert 1 <= bench["run_seconds"] <= 51
    assert isinstance(bench["run_seconds"], int)


def test_names_units_and_characters(bench):
    every = bench["configs"] + bench["workloads"] + bench["end_to_end"] \
        + bench["per_layer"]
    for e in every:
        assert NAME.match(e["name"]), e["name"]
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert one_line(w["why"]) and w["chips"] in (1, 4)
    for c in bench["configs"]:
        assert one_line(c["why"]) and one_line(c["source"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["per_layer"]:
        assert one_line(m["layer"])
    for group in ("configs", "workloads"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_end_to_end_metrics_and_bounds(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and 1 <= len(e2e) <= 16
    for m in e2e.values():
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert e2e["setup_s"]["bound"] <= 0.25
    roofs = [m for m in bench["per_layer"] if m["name"].endswith("_roofline")]
    assert roofs and all(m["unit"] == "%" for m in roofs)


def test_every_cell_reports_enough(bench):
    for w in bench["workloads"]:
        e2e = [m["name"] for m in bench["end_to_end"]
               if spec.reports(m, w["name"], bench)]
        layer = [m for m in bench["per_layer"]
                 if spec.reports(m, w["name"], bench)]
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert layer, w["name"]


def test_moves_names_a_metric_every_cell_of_it_reports(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert spec.reports(e2e[m["moves"]], cell, bench), (m, cell)
    layers = {}
    for m in bench["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_four_chip_cells_are_few(bench):
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 2)


def test_every_cell_finds_its_files_by_name(bench):
    used = set()
    for w in bench["workloads"]:
        cell = spec.resolve(w["name"], bench=bench)
        used.add(w["config"])
        assert cell.driver_file.is_file()
        assert cell.chips == w["chips"] == cell.config["chips"]
        for m in cell.per_layer:
            assert hasattr(cell.reader(m["name"]), "read")
        for check, entry in cell.limits["checks"].items():
            assert NAME.match(check)
            assert entry["limit"] > 0
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for c in bench["configs"]:
        assert c["name"] in used
        path = spec.ROOT / c["file"]
        assert path.is_file()
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert spec.load_json(path)["name"] == c["name"]
        assert spec.load_json(path)["reduced"] == c["reduced"]


def test_a_cell_is_added_with_files_and_entries_alone(tmp_path, bench):
    """A new cell, traffic mix and metric need new files and entries only:
    nothing that exists is edited."""
    shutil.copytree(spec.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    new = json.loads(json.dumps(bench))
    new["workloads"].append({"name": "sketch.dense32k.seeded",
                             "config": "dense32k", "traffic": "sketch_seeded",
                             "chips": 1, "why": "a new mix"})
    new["per_layer"].append({"name": "calls_made", "unit": "calls",
                             "better": "higher", "source": "program_counter",
                             "layer": "kernels", "moves": "call_ms",
                             "workloads": ["sketch.dense32k.seeded"]})
    new["end_to_end"][0]["workloads"].append("sketch.dense32k.seeded")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    traffic = spec.load_json(spec.traffic_path("oneshot_sketch"))
    traffic["omega_seed"] = None
    (tmp_path / "bench/traffic/sketch_seeded.json").write_text(
        json.dumps(traffic))
    shutil.copy(spec.limits_path("sketch.dense32k"),
                tmp_path / "bench/limits/sketch.dense32k.seeded.json")
    (tmp_path / "bench/metrics/calls_made.py").write_text(
        "def read(r):\n    return r.outcome.calls or None\n")
    cell = spec.resolve("sketch.dense32k.seeded", root=tmp_path)
    assert cell.traffic["omega_seed"] is None
    assert [m["name"] for m in cell.per_layer][-1] == "calls_made"
    assert cell.reader("calls_made").read is not None
    assert cell.driver_file == tmp_path / "bench/drivers/oneshot.py"


def _run(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "sketch.dense32k",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_means_no_result():
    got = _run(spec.ROOT)
    assert got.returncode != 0
    assert got.stdout.strip() == ""
    assert "no TPU" in got.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(spec.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    got = _run(tmp_path)
    assert got.returncode != 0
    assert got.stdout.strip() == ""
