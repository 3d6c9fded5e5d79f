"""BENCHMARK.json keeps to its contract, and every name in it resolves to
the file that holds that configuration, mix, cell's limits or metric."""

import json
import pathlib
import re

import pytest

from bench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
CELLS = [w["name"] for w in SPEC["workloads"]]


def one_line(text, limit=200):
    return isinstance(text, str) and 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_and_entry_keys():
    assert set(SPEC) == TOP
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    for kind, keys in KEYS.items():
        for entry in SPEC[kind]:
            extra = {"workloads"} if kind in ("end_to_end", "per_layer") else set()
            assert keys <= set(entry) <= keys | extra, (kind, entry)


def test_command_and_paths():
    assert SPEC["command"][0] == "python3" and len(SPEC["command"]) <= 32
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    for word in SPEC["command"][1:]:
        assert any(word.startswith(p + "/") for p in SPEC["paths"]), word
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51


def test_names_units_and_lines():
    names = []
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in SPEC[kind]:
            assert NAME.match(e["name"]), e["name"]
            names.append((kind, e["name"]))
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
            for k in ("why", "layer", "source"):
                if k in e:
                    assert one_line(e[k]), (e["name"], k)
    assert len(set(names)) == len(names)
    metric_names = [e["name"] for k in ("end_to_end", "per_layer") for e in SPEC[k]]
    assert len(set(metric_names)) == len(metric_names)


def test_configs_resolve_to_their_files():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files) and 1 <= len(files) <= 24
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["name"] == c["name"] and body["source"] == c["source"]
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k) and not k.endswith(("_dim", "_rank"))
            assert k not in ("d_model", "d_ff", "head_dim", "num_heads", "num_kv_heads")
        assert "assumed" in body and "deployment" in body


def test_cells_resolve_to_mix_driver_and_limits():
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs) and 1 <= len(pairs) <= 24
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(1, len(pairs) // 2)
    for w in SPEC["workloads"]:
        assert w["chips"] in (1, 4) and NAME.match(w["traffic"])
        cell = harness.load_cell(ROOT, w["name"])
        assert (cell.bench / "drivers" / f"{cell.traffic['driver']}.py").is_file()
        assert cell.limits, f"{w['name']}: no limits"
        assert (cell.bench / "limits" / f"{w['name']}.json").is_file()


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_enough(cell):
    c = harness.load_cell(ROOT, cell)
    e2e = {m["name"] for m in c.metrics("end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = c.metrics("per_layer")
    assert per_layer
    for m in per_layer:
        assert m["moves"] in e2e, (cell, m["name"])


def test_metrics_resolve_to_readers():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in SPEC["per_layer"]:
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        if m["name"].split(".")[0].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
        reader = harness.load_module(ROOT / "bench" / "metrics" / f"{m['name']}.py")
        assert callable(reader.read)
    layers = {m["layer"] for m in SPEC["per_layer"]}
    assert all(one_line(l) for l in layers)
