"""BENCHMARK.json against the contract's shape, and discovery by name."""
import json
import re
import shutil

import pytest

from bench import registry

ROOT = registry.ROOT
BENCH = registry.load_benchmark()
LINE = re.compile(r"^[^\n\t]{1,200}$")


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for word in BENCH["command"]:
        assert LINE.match(word) and ".." not in word
        assert not word.startswith("/")
    assert (ROOT / BENCH["command"][1]).is_file()
    assert len(json.dumps(BENCH)) < 64 * 1024


def _names():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            yield group, entry


@pytest.mark.parametrize("group,entry", list(_names()),
                         ids=lambda x: x if isinstance(x, str) else x["name"])
def test_names_units_and_keys(group, entry):
    assert registry.NAME_RE.match(entry["name"]), entry["name"]
    allowed = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source",
                       "workloads"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"},
    }[group]
    assert set(entry) <= allowed, set(entry) - allowed
    if group in ("end_to_end", "per_layer"):
        assert registry.UNIT_RE.match(entry["unit"]), entry["unit"]
        assert entry["better"] in ("lower", "higher")
    if group == "end_to_end":
        assert entry["source"] in ("host_clock", "device_trace")
        assert 0.01 <= entry["bound"] <= 0.25
    if group == "per_layer":
        assert entry["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
        assert LINE.match(entry["layer"])
    if group == "workloads":
        assert entry["chips"] in (1, 4)
        assert registry.NAME_RE.match(entry["traffic"])
    if group == "configs":
        assert all(registry.NAME_RE.match(k) for k in entry["reduced"])
        assert entry["file"].startswith("bench/")
    for key in ("why", "layer", "source"):
        if key in entry:
            assert LINE.match(entry[key])


def test_every_cell_resolves_and_reports_enough():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for w in BENCH["workloads"]:
        cell = registry.resolve(w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2, w["name"]
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m["moves"] in names, (w["name"], m["name"])
            registry.metric_reader(m["name"])
        assert registry.system(cell.config["system"]).run
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e


def test_at_most_half_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_a_new_config_traffic_and_metric_need_only_new_files(tmp_path):
    # Copy the benchmark, then add a cell, a traffic mix and a metric
    # as new files and new entries: nothing that exists is edited.
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".*"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench/configs/dummy.json").write_text(
        json.dumps({"system": "engine", "sizes": 1}))
    (tmp_path / "bench/traffic/dummy-mix.json").write_text(
        json.dumps({"cells_per_sweep": 8}))
    (tmp_path / "bench/metrics/dummy_metric.py").write_text(
        "def read(ctx):\n    return ctx.get('x')\n")
    bench["configs"].append({"name": "dummy", "source": "https://example.org",
                             "file": "bench/configs/dummy.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "dummy.dummy-mix", "config": "dummy",
                               "traffic": "dummy-mix", "chips": 1,
                               "why": "a test"})
    bench["per_layer"].append({"name": "dummy_metric", "unit": "s",
                               "better": "lower", "source": "host_clock",
                               "layer": "a test", "moves": "setup_s",
                               "workloads": ["dummy.dummy-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = registry.resolve("dummy.dummy-mix", root=tmp_path)
    assert cell.config == {"system": "engine", "sizes": 1}
    assert cell.traffic["cells_per_sweep"] == 8
    assert [m["name"] for m in cell.per_layer] == ["dummy_metric"]
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    reader = registry.metric_reader("dummy_metric", root=tmp_path)
    assert reader.read({"x": 2.5}) == 2.5 and reader.read({}) is None
    with pytest.raises(KeyError):
        registry.resolve("no.such-cell", root=tmp_path)
    with pytest.raises(KeyError):
        registry.metric_reader("no_such_metric", root=tmp_path)
