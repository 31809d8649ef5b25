"""Later PRs add and never edit: a cell, a configuration, a traffic mix
and a per-layer metric come as new files alone, found by the names in
the manifest, with no change to any file that is there."""

import copy
import json
import shutil

from conftest import CELLS


def test_a_cell_is_added_as_files_alone(run_tiny, tiny_manifest, tmp_path):
    from benchmarks import harness

    # a new deployment and a new mix: two data files
    cfg = json.loads((CELLS / "configs" / "tiny-1chip.json").read_text())
    cfg.update(name="added-1chip", slots=32,
               rooms={"distinct": 26, "storm": 6, "b4": 0, "prepend": 0})
    mix = json.loads((CELLS / "traffic" / "tiny-flood.json").read_text())
    mix.update(solo_rooms=4, duet_rooms=4, typing_run=1, erasing_run=1)
    mix["unit"] = {"duets": 2, "typed": 2, "erased": 2}
    # a new per-layer metric: one small reader
    reader = (
        '"""updates_a_flush (count): updates the window acknowledged a '
        'flush."""\n\n\n'
        "def read(trace, counters):\n"
        "    return counters['work'] / counters['flushes']\n"
    )
    for sub, name, text in (
        ("configs", "added-1chip.json", json.dumps(cfg)),
        ("traffic", "added-flood.json", json.dumps(mix)),
        ("layer_metrics", "updates_a_flush.py", reader),
    ):
        (tmp_path / sub).mkdir()
        (tmp_path / sub / name).write_text(text)
    m = copy.deepcopy(tiny_manifest)
    m["workloads"].append({
        "name": "added-flood", "config": "added-1chip",
        "traffic": "added-flood", "chips": 1, "why": "tests",
    })
    for metric in m["end_to_end"] + m["per_layer"]:
        if "tiny-flood" in metric.get("workloads", []):
            metric["workloads"].append("added-flood")
    m["per_layer"].append({
        "name": "updates_a_flush.flood", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "provider ingest",
        "moves": "edit_rate", "workloads": ["added-flood"],
    })
    roots = (tmp_path, CELLS, harness.HERE)
    before = {
        p: p.read_bytes() for p in harness.HERE.rglob("*") if p.is_file()
        and "__pycache__" not in p.parts
    }
    r = harness.run_cell("added-flood", 2**31 + 1, 0.3, False, platform="cpu",
                         roots=roots, manifest=m)
    assert r["correct"] is True and set(r["metrics"]) == {"edit_rate", "setup_s"}
    r = harness.run_cell("added-flood", 2**31 + 2, 0.3, True, platform="cpu",
                         roots=roots, manifest=m)
    # 2 duets x 2 + 2 typed + 2 erased = 8 updates a flush
    assert r["metrics"]["updates_a_flush.flood"] == {"value": 8.0, "unit": "count"}
    assert "ingest_share.flood" in r["metrics"]
    # the old cells do not report the new metric, and no file changed
    r = run_tiny("tiny-flood", trace=True)
    assert "updates_a_flush.flood" not in r["metrics"]
    after = {
        p: p.read_bytes() for p in harness.HERE.rglob("*") if p.is_file()
        and "__pycache__" not in p.parts
    }
    assert before == after
    shutil.rmtree(tmp_path)


def test_a_missing_file_is_named(tiny_manifest, roots):
    import pytest

    from benchmarks import harness

    m = copy.deepcopy(tiny_manifest)
    m["workloads"].append({
        "name": "ghost", "config": "no-such-config", "traffic": "tiny-flood",
        "chips": 1, "why": "tests",
    })
    with pytest.raises(harness.BenchError, match="configs 'no-such-config'"):
        harness.run_cell("ghost", 1, 0.1, False, platform="cpu", roots=roots,
                         manifest=m)
