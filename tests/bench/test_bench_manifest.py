"""BENCHMARK.json against the benchmark's contract, and every cell's files
found by name: its configuration, traffic, driver and metric readers. A
later cell or metric is added as files and manifest entries only."""
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def one_line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_and_size():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= MANIFEST["run_seconds"] <= 51


def test_paths_and_command_stay_inside_the_benchmark():
    paths = MANIFEST["paths"]
    assert 1 <= len(paths) <= 16
    for p in paths:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert (ROOT / p).is_dir()
    cmd = MANIFEST["command"]
    assert 1 <= len(cmd) <= 32 and all(one_line(w) for w in cmd)
    for word in cmd[1:]:
        if "/" in word or word.endswith(".py"):
            assert any(word.startswith(p + "/") for p in paths), word
            assert (ROOT / word).is_file()


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries_have_the_allowed_keys_and_names(section):
    for entry in MANIFEST[section]:
        extra = {"workloads"} if section in ("end_to_end", "per_layer") else set()
        assert KEYS[section] <= set(entry) <= KEYS[section] | extra, entry
        assert NAME.match(entry["name"]), entry["name"]
    names = [e["name"] for e in MANIFEST[section]]
    assert len(names) == len(set(names))


def test_metric_names_units_and_sources():
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    for m in METRICS:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(CELLS)
    for m in MANIFEST["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in MANIFEST["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert one_line(m["layer"])
        if m["name"].endswith("_roofline") or "_roofline." in m["name"] \
                or "mfu" in m["name"]:
            assert m["unit"] == "%"
    assert "setup_s" in [m["name"] for m in MANIFEST["end_to_end"]]


def test_configs_are_used_and_their_files_lie_under_paths():
    used = {w["config"] for w in MANIFEST["workloads"]}
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(files) == len(set(files))
    for c in MANIFEST["configs"]:
        assert c["name"] in used
        assert one_line(c["source"]) and one_line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in MANIFEST["paths"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert (ROOT / "bench" / "drivers" / f"{cfg['driver']}.py").is_file()


def test_four_chip_cells_are_few():
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in MANIFEST["workloads"])
    assert len(four) <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_finds_its_files_and_reports_enough(cell):
    w = next(x for x in MANIFEST["workloads"] if x["name"] == cell)
    assert one_line(w["why"]) and NAME.match(w["traffic"])
    spec = harness.cell_spec(MANIFEST, cell)
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec["per_layer"], "a cell reports at least one per-layer metric"
    for m in spec["per_layer"]:
        assert m["moves"] in e2e, (m["name"], m["moves"])
    assert spec["traffic"]["kind"] in ("waves", "products")
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.reader(m["name"]))


def test_pairs_of_config_and_traffic_are_unique():
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_per_layer_metrics_of_one_layer_agree_on_its_name():
    layers = {m["layer"] for m in MANIFEST["per_layer"]}
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in {e["name"] for e in MANIFEST["end_to_end"]}
    assert all(one_line(x) for x in layers)


def test_every_reader_file_is_a_listed_metric():
    listed = {m["name"] for m in METRICS}
    files = {p.stem for p in (ROOT / "bench" / "metrics").glob("*.py")}
    assert files == listed
