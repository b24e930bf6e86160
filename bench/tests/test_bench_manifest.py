"""BENCHMARK.json against the benchmark's contract, and every file a cell
or a metric names found by that name."""
import json
import math
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj", "head",
               "expansion", "expert", "ffn", "d_model", "width")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_command(manifest):
    assert set(manifest) == TOP_KEYS
    cmd = manifest["command"]
    assert 1 <= len(cmd) <= 32 and all(isinstance(w, str) for w in cmd)
    for w in cmd:
        assert 1 <= len(w) <= 200 and "\n" not in w and "\t" not in w
        assert not w.startswith("/") and ".." not in w.split("/")
    assert 1 <= len(manifest["paths"]) <= 16
    for p in manifest["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p.split("/")
    for w in cmd[1:]:
        if os.path.exists(os.path.join(ROOT, w)):
            assert any(w == p or w.startswith(p + "/") for p in manifest["paths"])
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51


def test_check_fits_its_time_with_all_cells(manifest):
    runs = 2 + 14 * 24
    total = runs * (manifest["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_names_units_and_entry_keys(manifest):
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in manifest[key]]
        assert len(names) == len(set(names)), key
        for n in names:
            assert NAME.match(n), n
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k)
            assert not k.endswith(("_dim", "_rank")), k
            assert not any(w in k for w in WIDTH_WORDS), k
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for text in ([c["why"] for c in manifest["configs"]]
                 + [c["source"] for c in manifest["configs"]]
                 + [w["why"] for w in manifest["workloads"]]
                 + [m["layer"] for m in manifest["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert len(json.dumps(manifest)) <= 64 * 1024


def test_every_file_is_found_by_name(manifest):
    cfg_files = [c["file"] for c in manifest["configs"]]
    assert len(cfg_files) == len(set(cfg_files))
    used = {w["config"] for w in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}
    for c in manifest["configs"]:
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        path = os.path.join(ROOT, c["file"])
        with open(path) as f:
            assert json.load(f)["name"] == c["name"]
        assert os.path.exists(os.path.splitext(path)[0] + ".py")
    for w in manifest["workloads"]:
        for sub, name in (("traffic", w["traffic"]), ("limits", w["name"])):
            with open(os.path.join(BENCH, sub, name + ".json")) as f:
                json.load(f)
    for m in manifest["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics", m["name"] + ".py"))
    with open(os.path.join(ROOT, manifest["configs"][0]["file"])) as f:
        family = json.load(f)["family"]
    assert os.path.exists(os.path.join(BENCH, "families", family + ".py"))


def test_metrics_cover_every_cell(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    reports = {m["name"]: set(m.get("workloads", cells))
               for m in manifest["end_to_end"]}
    assert "setup_s" in reports and reports["setup_s"] == set(cells)
    for cell in cells:
        assert sum(cell in r for n, r in reports.items() if n != "setup_s") >= 1
        assert any(cell in m.get("workloads", cells)
                   for m in manifest["per_layer"])
    layers = {}
    for m in manifest["per_layer"]:
        assert m["moves"] in reports
        for cell in m.get("workloads", cells):
            assert cell in cells and cell in reports[m["moves"]]
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_at_most_half_the_cells_take_four_chips(manifest):
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, math.floor(len(manifest["workloads"]) / 2))


def test_limits_sit_between_their_readings():
    for name in os.listdir(os.path.join(BENCH, "limits")):
        with open(os.path.join(BENCH, "limits", name)) as f:
            rec = json.load(f)
        for k, limit in rec["limits"].items():
            lower = rec["readings"][k]["lower"]
            assert lower < limit, (name, k)
            upper = rec["readings"][k].get("upper")
            if upper is not None:
                assert limit < upper, (name, k)
