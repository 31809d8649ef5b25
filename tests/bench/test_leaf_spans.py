"""The readers of the leaves' inner spans (``yjs_tpu/obs/trace.py``
``LEAF_SPANS``: the journal's write and fsync inside ``ytpu.wal.append``,
the plan phase's four Python steps inside ``ytpu.plan``): each on a
hand-made trace whose answers are known, its ``None`` where a program
opens no such span, and the sums that a split must keep: what the new
readers read is what the older ones (``wal_share``, ``plan_share``,
``ingest_share`` + ``receive_share``) no longer do."""

import json
from pathlib import Path

import pytest

from benchmarks import harness
from benchmarks import trace_reduce as tr
from yjs_tpu.obs.trace import LEAF_SPANS

DATA = Path(__file__).resolve().parent / "data"
SYNTHETIC = json.loads((DATA / "spans_synthetic.json").read_text())
MANIFEST = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
HOST = ["/host:CPU", "python3"]

# reader -> its span, and its share (%) of the hand-made interval of
# 10000 ns once the parent's trace has the splits below
READERS = {
    "wal_write_share": ("ytpu.wal.write", 3.0),      # 2 x 150
    "wal_fsync_share": ("ytpu.wal.fsync", 0.8),      # one append in two
    "plan_walk_share": ("ytpu.plan.walk", 0.5),
    "plan_keys_share": ("ytpu.plan.keys", 0.7),
    "plan_stage_share": ("ytpu.plan.stage", 0.7),
    "plan_finish_share": ("ytpu.plan.finish", 1.5),
}
LAYERS = {"wal": "provider ingest", "plan": "host planner"}
# listed only where a traced run on the chip reads 0.1% or more (PERF.md
# 5).  Among the `.bulk` cells those are the two that replay the cold
# start, on one chip and on the mesh: test_reconnect_cell.py and
# test_longtail_cell.py pin what their cells report (PERF.md 7b)
CELLS = {
    "flood": ("edit_rate", ["yws-flood"]),
    "bulk": ("bulk_rate", ["yws-coldstart", "mesh4-coldstart"]),
}


def split(events):
    """The parent's hand-made trace as the program draws it with the
    leaves split: ``receive_update`` opens 80 ns earlier (its head moves
    in from ``bench.ingest``), every append holds its write and the
    first its fsync, and the plan phase its four steps around the
    native call.  No span's end moves and none is taken away."""
    out = []
    appends = 0
    for plane, line, name, start, dur in events:
        if name == "ytpu.provider.receive_update":
            start, dur = start - 80, dur + 80
        out.append([plane, line, name, start, dur])
        if name == "ytpu.wal.append":
            out.append([*HOST, "ytpu.wal.write", start + 50, 150])
            if appends == 0:
                out.append([*HOST, "ytpu.wal.fsync", start + 200, 80])
            appends += 1
        if name == "ytpu.plan":  # 5000..6000, the native call 5200..5800
            out += [
                [*HOST, "ytpu.plan.walk", start + 10, 50],
                [*HOST, "ytpu.plan.keys", start + 60, 70],
                [*HOST, "ytpu.plan.stage", start + 130, 70],
                [*HOST, "ytpu.plan.finish", start + 800, 150],
            ]
    return out


def reader(name):
    return harness.load_module("layer_metrics", name, (harness.HERE,))


def share(reduced, name):
    return reader(name).read(reduced, {}) or 0.0


@pytest.fixture(scope="module")
def parent():
    return tr.reduce_events(SYNTHETIC["events"])


@pytest.fixture(scope="module")
def change():
    return tr.reduce_events(split(SYNTHETIC["events"]))


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_on_the_hand_made_trace(change, name):
    span, want = READERS[name]
    r = reader(name)
    assert r.read(change, {}) == pytest.approx(want)
    assert r.SPANS == (span,) and f"`{span}`" in r.__doc__
    assert f"`{LEAF_SPANS[span]}`" in r.__doc__  # the span it opens inside


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_finds_nothing_in_a_trace_without_its_span(parent, name):
    """What the parent commit gives, hand-made or recorded on the chip:
    the metric is left out, not raised."""
    kept = json.loads((DATA / "trace_yws-flood.json").read_text())
    old = tr.reduce_events(kept["events"], kept["n_devices"])
    for trace in (parent, old, {"spans": {}, "window_s": 1.0}):
        assert reader(name).read(trace, {}) is None


def test_readers_and_the_programs_table_name_the_same_spans():
    assert {span for span, _ in READERS.values()} == set(LEAF_SPANS)
    assert not set(LEAF_SPANS) & set(SYNTHETIC["parents"])
    assert set(LEAF_SPANS.values()) <= set(SYNTHETIC["parents"])


@pytest.mark.parametrize("total, parts", [
    # the parent's metric(s) = what reads the same seconds from the split on
    (("wal_share",),
     ("wal_share", "wal_write_share", "wal_fsync_share")),
    (("plan_share",),
     ("plan_share", "plan_walk_share", "plan_keys_share",
      "plan_stage_share", "plan_finish_share")),
    (("ingest_share", "receive_share"),
     ("ingest_share", "receive_share", "wal_write_share",
      "wal_fsync_share")),
], ids=["wal", "plan", "ingest"])
def test_a_split_keeps_the_sum(parent, change, total, parts):
    before = sum(share(parent, n) for n in total)
    after = sum(share(change, n) for n in parts)
    assert before > 0 and after == pytest.approx(before)
    # and each older reader reads less by what its new children took
    assert all(share(change, n) <= share(parent, n) for n in total[:1])


def test_the_split_moves_the_head_and_nothing_else(parent, change):
    """Self times still sum to the interval; the head of
    ``receive_update`` leaves ``ingest_share`` for ``receive_share``;
    a reader of no split span reads what it read."""
    for reduced in (parent, change):
        assert sum(reduced["spans"].values()) == pytest.approx(
            reduced["window_s"]
        )
    head = 100.0 * 2 * 80 / 10000
    assert share(parent, "ingest_share") - share(change, "ingest_share") == (
        pytest.approx(head)
    )
    untouched = (
        "plan_native_share", "slo_share", "emit_share", "pack_share",
        "dispatch_share", "compact_share", "engine_other_share",
        "flush_tick_share", "unspanned_share",
    )
    for name in untouched:
        assert share(change, name) == pytest.approx(share(parent, name))
    # the device idles under the innermost span: the journal's write
    # takes its gap from the append it opens inside (the ten longest
    # gaps are listed)
    was, gaps = dict(parent["idle_gaps"]), dict(change["idle_gaps"])
    assert was["ytpu.wal.append"] == pytest.approx(600e-9)
    assert gaps["ytpu.wal.write"] == pytest.approx(300e-9)
    assert "ytpu.wal.append" not in gaps  # 220 ns left: off the list


@pytest.mark.parametrize("name", sorted(READERS))
def test_manifest_entries_of_a_reader(name):
    entries = {
        m["name"].split(".", 1)[1]: m for m in MANIFEST["per_layer"]
        if m["name"].split(".", 1)[0] == name
    }
    assert set(entries) == set(CELLS)
    for cell, m in entries.items():
        assert (m["source"], m["unit"], m["better"]) == (
            "program_span", "%", "lower",
        )
        assert m["layer"] == LAYERS[name.split("_", 1)[0]]
        assert (m["moves"], m["workloads"]) == CELLS[cell]
