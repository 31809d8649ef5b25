"""BENCHMARK.json against the contract's limits, and against the files
it names: every cell's configuration, traffic mix, generator and
per-layer readers are found by name."""

import json
import re

import pytest

from benchmarks import harness, roofline

MANIFEST = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
ROOTS = (harness.HERE,)


def one_line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    assert set(MANIFEST) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert len((harness.ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert 1 <= len(MANIFEST["command"]) <= 32
    assert all(one_line(w) for w in MANIFEST["command"])
    assert 1 <= len(MANIFEST["configs"]) <= 24
    assert 1 <= len(MANIFEST["workloads"]) <= 24
    assert 1 <= len(MANIFEST["end_to_end"]) <= 16
    assert 1 <= len(MANIFEST["per_layer"]) <= 128


def test_paths_hold_the_benchmark_and_the_command_stays_inside():
    paths = MANIFEST["paths"]
    assert 1 <= len(paths) <= 16
    for p in paths:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (harness.ROOT / p).is_dir()
    for word in MANIFEST["command"]:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in paths)
            assert (harness.ROOT / word).is_file()


def test_names_are_unique():
    for key in ("configs", "workloads"):
        names = [e["name"] for e in MANIFEST[key]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(files) == len(set(files))


@pytest.mark.parametrize("cfg", MANIFEST["configs"], ids=lambda c: c["name"])
def test_configuration_entry_and_file(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"])
    assert one_line(cfg["source"]) and one_line(cfg["why"])
    assert len(cfg["reduced"]) <= 16 and all(NAME.match(k) for k in cfg["reduced"])
    assert PATH.match(cfg["file"])
    assert any(cfg["file"].startswith(p + "/") for p in MANIFEST["paths"])
    assert cfg["file"] == f"benchmarks/configs/{cfg['name']}.json"
    body = json.loads((harness.ROOT / cfg["file"]).read_text())
    assert body["name"] == cfg["name"] and body["reduced"] == cfg["reduced"]
    assert body["guarantees"] and "assumed" in body
    assert sum(body["rooms"].values()) == body["slots"]
    assert any(w["config"] == cfg["name"] for w in MANIFEST["workloads"])


@pytest.mark.parametrize("cell", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_its_files_are_found_by_name(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key])
    assert cell["chips"] in (1, 4) and one_line(cell["why"])
    cfg = harness.load_data("configs", cell["config"], ROOTS)
    assert cfg["chips"] == cell["chips"]
    assert cell["config"] in {c["name"] for c in MANIFEST["configs"]}
    traffic = harness.load_data("traffic", cell["traffic"], ROOTS)
    gen = harness.load_module("generators", traffic["generator"], ROOTS)
    assert callable(gen.Generator)
    # the cell reports setup_s, one more end-to-end metric that its
    # traffic knows how to measure, and at least one per-layer metric
    reported = [
        m["name"] for m in MANIFEST["end_to_end"]
        if cell["name"] in m.get("workloads", [cell["name"]])
    ]
    assert "setup_s" in reported and len(reported) >= 2
    assert set(reported) - {"setup_s"} <= set(traffic["end_to_end"])
    assert any(
        cell["name"] in m.get("workloads", [cell["name"]])
        for m in MANIFEST["per_layer"]
    )


def test_four_chip_cells_are_at_most_half():
    four = sum(1 for w in MANIFEST["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(MANIFEST["workloads"]) // 2)


@pytest.mark.parametrize("m", MANIFEST["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.25
    cells = {w["name"] for w in MANIFEST["workloads"]}
    assert set(m.get("workloads", cells)) <= cells


def test_setup_s_is_an_end_to_end_metric_of_every_cell():
    (setup,) = [m for m in MANIFEST["end_to_end"] if m["name"] == "setup_s"]
    assert "workloads" not in setup and setup["bound"] <= 0.25


@pytest.mark.parametrize("m", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_and_its_reader(m):
    assert set(m) - {"workloads"} == {
        "name", "unit", "better", "source", "layer", "moves",
    }
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    assert one_line(m["layer"])
    moved = {e["name"]: e for e in MANIFEST["end_to_end"]}[m["moves"]]
    cells = {w["name"] for w in MANIFEST["workloads"]}
    # every cell that reads this metric reports the metric it moves
    assert set(m.get("workloads", cells)) <= set(moved.get("workloads", cells))
    reader = harness.load_module(
        "layer_metrics", m["name"].split(".", 1)[0], ROOTS
    )
    assert callable(reader.read) and reader.__doc__
    if m["name"].endswith("_roofline") or "mfu" in m["name"]:
        assert m["unit"] == "%"


@pytest.mark.parametrize("times, trend", [
    ([1.0, 1.0, 1.1, 1.1], 10.0), ([2.0, 9.0, 1.0], -50.0), ([1.0], None),
])
def test_window_trend_is_the_later_half_against_the_earlier(times, trend):
    reader = harness.load_module("layer_metrics", "window_trend", ROOTS)
    got = reader.read({}, {"timed_intervals_s": times})
    assert got == trend or got == pytest.approx(trend)


def test_peaks_table_names_its_source_and_refuses_unknown_devices():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in json.loads(roofline.PEAKS.read_text())["source"]
    for kind in ("cpu", "TPU v9", "source"):
        with pytest.raises(KeyError):
            roofline.peaks(kind)


def test_bytes_functions():
    # one link write: its int32 value lane in, one int32 out
    assert roofline.apply_plan2_bytes(10, cap=131072) == 10 * 8
    assert roofline.apply_plan2_bytes(10, cap=2048) == 10 * 6
    # one rebuilt room: right int32 + deleted bool rows and the heads,
    # read and written
    assert roofline.scatter_rows_bytes(1, cap=2048, seg_cap=8) == 2 * (
        2049 * 5 + 9 * 4
    )
