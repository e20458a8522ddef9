"""The benchmark is data: ``BENCHMARK.json`` keeps to its contract, every
name it gives resolves to a file of its own, and a configuration, a
traffic mix and a metric can be added as new files without editing any
file that is there."""

import hashlib
import json
import os
import re
import shutil
import sys
from types import SimpleNamespace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HARNESS = os.path.join(REPO, "benchmarks", "chip")
sys.path.insert(0, HARNESS)

import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
WIDTH = re.compile(r"(_dim|_rank|hidden|intermediate|latent|state|"
                   r"projection|head|expansion|experts_per_tok)")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return harness.load_bench()


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(REPO, p))
    cmd = bench["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    for word in cmd:
        if os.path.exists(os.path.join(REPO, word)):
            assert any(word.startswith(p + "/") for p in bench["paths"])
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 65536


def test_run_seconds_fit_the_check(bench):
    rs = bench["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_text(bench):
    names = []
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) and not WIDTH.search(k)
                   for k in c["reduced"])
        names.append(c["name"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        names.append(w["name"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        names.append(m["name"])
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)


def test_cells_and_metrics_fit_together(bench):
    cells = {w["name"] for w in bench["workloads"]}
    pairs = {(w["config"], w["traffic"]) for w in bench["workloads"]}
    assert len(pairs) == len(cells)
    assert {w["config"] for w in bench["workloads"]} == {
        c["name"] for c in bench["configs"]}
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(cells) // 2)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25

    def reports(cell, metric):
        return cell in metric.get("workloads", cells)

    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert _line(m["layer"]) and m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in cells and reports(cell, e2e[m["moves"]])
    for cell in cells:
        got = [n for n, m in e2e.items() if reports(cell, m)]
        assert "setup_s" in got and len(got) >= 2
        assert any(reports(cell, m) for m in bench["per_layer"])


def test_every_name_resolves_to_its_file(bench):
    for c in bench["configs"]:
        path = os.path.join(REPO, c["file"])
        assert c["file"].startswith("benchmarks/chip/configs/")
        with open(path) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
    for w in bench["workloads"]:
        cell = harness.load_cell(bench, w["name"])
        sut = cell.traffic["sut"]
        for part in ("sut", "reference"):
            assert os.path.isfile(os.path.join(HARNESS, part, sut + ".py"))
        src = open(os.path.join(HARNESS, "sut", sut + ".py")).read()
        for fn in ("setup", "run", "control", "check"):
            assert f"def {fn}_{cell.traffic['op']}(" in src
        assert cell.traffic["driver"] in ("jobs", "clients")
    for m in bench["per_layer"]:
        assert callable(harness.load_reader(m["name"]).read)


def _digest(root):
    out = {}
    for dirpath, _, files in os.walk(root):
        for f in files:
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_config_mix_and_metric_need_no_edit(tmp_path, monkeypatch,
                                                bench):
    """Add a configuration, a mix and a metric as new files plus new
    entries; the harness resolves them, and no file that was there
    changed."""
    repo = tmp_path / "repo"
    here = repo / "benchmarks" / "chip"
    shutil.copytree(HARNESS, here,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(here)
    with open(here / "configs" / "kmeans_1m.json") as f:
        cfg = json.load(f)
    cfg.update(name="kmeans_dummy", k=128)
    (here / "configs" / "kmeans_dummy.json").write_text(json.dumps(cfg))
    (here / "traffic" / "fit5.json").write_text(json.dumps(
        {"driver": "jobs", "sut": "kmeans", "op": "fit", "num_iter": 5,
         "steps_per_job": 5, "init_pool": 256, "call": {"fused": False}}))
    # a metric split by what it moves shares its base name's reader
    (here / "metrics" / "dummy.steps.py").write_text(
        "def read(ctx):\n    return float(ctx.steps)\n")
    new = json.loads(json.dumps(bench))
    new["configs"].append({"name": "kmeans_dummy", "source": "test",
                           "file": "benchmarks/chip/configs/"
                                   "kmeans_dummy.json",
                           "reduced": [], "why": "test"})
    new["workloads"].append({"name": "kmeans_dummy.fit5",
                             "config": "kmeans_dummy", "traffic": "fit5",
                             "chips": 1, "why": "test"})
    new["per_layer"].append({"name": "dummy.steps.fit", "unit": "steps",
                             "better": "higher", "source": "device_trace",
                             "layer": "device", "moves": "step_ms",
                             "workloads": ["kmeans_dummy.fit5"]})
    new["end_to_end"][0]["workloads"].append("kmeans_dummy.fit5")
    monkeypatch.setattr(harness, "ROOT", str(repo))
    monkeypatch.setattr(harness, "HERE", str(here))
    cell = harness.load_cell(new, "kmeans_dummy.fit5")
    assert cell.config["k"] == 128 and cell.traffic["num_iter"] == 5
    assert cell.traffic["call"] == {"fused": False}
    assert [m["name"] for m in cell.end_to_end] == ["step_ms", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["dummy.steps.fit"]
    reader = harness.load_reader("dummy.steps.fit")
    assert reader.read(SimpleNamespace(steps=7)) == 7.0
    with pytest.raises(FileNotFoundError, match="no reader"):
        harness.load_reader("nothing.here")
    after = _digest(here)
    assert {k: after[k] for k in before} == before
