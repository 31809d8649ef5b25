"""The per-layer metrics that read the program's own spans (the one span
API of ``yjs_tpu/obs/trace.py``): each reader against a hand-made trace
whose answers are known, its ``None`` on a trace of a program that opens
no such span, and the contract between the two sides: a reader names
only spans that ``tests/test_span_clock.py`` holds the program to."""

import json
from pathlib import Path

import pytest

from benchmarks import harness
from benchmarks import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
SYNTHETIC = json.loads((DATA / "spans_synthetic.json").read_text())
MANIFEST = json.loads((harness.ROOT / "BENCHMARK.json").read_text())

# reader -> its share (%) of the hand-made interval of 10000 ns
NEW = {
    "receive_share": 20.0,        # 2 x (500 + 200 + 300)
    "wal_share": 6.0,
    "slo_share": 12.0,            # receive 400 + visible 300 + burn 500
    "slo_burn_share": 5.0,
    "flush_tick_share": 5.0,      # provider.flush 300 + cost.on_flush 200
    "engine_other_share": 6.0,    # ytpu.flush 4600 less its five phases
    "compact_scan_share": 2.0,
    "compact_rebuild_share": 5.0,
    "compact_stage_share": 6.0,   # alloc + put + scatter
    "plan_native_share": 6.0,
    "fold_share": 3.0,
}
# what the readers that were there read from this PR on: the residue of
# their span after its new children
OLD = {
    "ingest_share": 10.0,         # bench.ingest less the two calls
    "unspanned_share": 11.0,      # bench.unit 900 + bench.timed 200
    "compact_share": 2.0,
    "plan_share": 4.0,
    "emit_share": 7.0,
    "pack_share": 2.0,
    "dispatch_share": 3.0,
}


def reader(name):
    return harness.load_module("layer_metrics", name, (harness.HERE,))


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce_events(SYNTHETIC["events"])


@pytest.mark.parametrize("name, share", [*NEW.items(), *OLD.items()])
def test_reader_on_the_hand_made_trace(reduced, name, share):
    assert reader(name).read(reduced, {}) == pytest.approx(share)


def test_every_second_of_the_interval_is_read_once(reduced):
    assert sum(reduced["spans"].values()) == pytest.approx(reduced["window_s"])
    read = {s for n in NEW for s in reader(n).SPANS} | {
        "bench.ingest", "bench.unit", "bench.timed", "ytpu.compact",
        "ytpu.plan", "ytpu.pack", "ytpu.dispatch", "ytpu.emit",
    }
    assert set(reduced["spans"]) <= read
    # the device idles under the innermost span that covers it; "no
    # span" is what is left to the benchmark's own bench.unit/bench.timed
    gaps = dict(reduced["idle_gaps"])
    assert gaps["_no_span_"] == pytest.approx(1100e-9)
    assert gaps["ytpu.slo.burn"] == pytest.approx(500e-9)
    assert gaps["ytpu.compact.rebuild"] == pytest.approx(500e-9)


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_finds_nothing_in_a_trace_without_its_spans(name):
    """The trace recorded on the chip before the program had these spans
    (what the parent commit gives): the metric is left out, not raised."""
    kept = json.loads((DATA / "trace_yws-flood.json").read_text())
    old = tr.reduce_events(kept["events"], kept["n_devices"])
    assert reader(name).read(old, {}) is None
    assert reader(name).read({"spans": {}, "window_s": 1.0}, {}) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_names_only_spans_of_the_programs_contract(name):
    r = reader(name)
    assert r.SPANS and set(r.SPANS) <= set(SYNTHETIC["parents"])
    assert all(f"`{s}`" in r.__doc__ for s in r.SPANS)


CELLS = {
    "bulk": ("bulk_rate", ["yws-coldstart"]),
    "flood": ("edit_rate", ["yws-flood"]),
}
# listed only where a traced run reads 0.1% or more (PERF.md 5): the
# flood compacts nothing in its window, a cold start's two flushes fold
# no log and pass a near-empty SLO ring
ONE_CELL = {
    "compact_rebuild_share": {"bulk"}, "compact_stage_share": {"bulk"},
    "fold_share": {"flood"}, "flush_tick_share": {"flood"},
    "slo_burn_share": {"flood"},
}


@pytest.mark.parametrize("name", sorted(NEW))
def test_manifest_entries_of_a_reader(name):
    entries = {
        m["name"].split(".", 1)[1]: m for m in MANIFEST["per_layer"]
        if m["name"].split(".", 1)[0] == name
    }
    assert set(entries) == ONE_CELL.get(name, set(CELLS))
    assert len({m["layer"] for m in entries.values()}) == 1
    for cell, m in entries.items():
        assert (m["source"], m["unit"]) == ("program_span", "%")
        assert (m["moves"], m["workloads"]) == CELLS[cell]


def test_spans_nest_as_the_contract_says():
    """The hand-made events themselves keep the contract they carry."""
    spans = [
        (e[2], e[3], e[3] + e[4]) for e in SYNTHETIC["events"]
        if e[2].startswith("ytpu.")
    ]
    for name, a, b in spans:
        parent = SYNTHETIC["parents"][name]
        if parent is not None:
            assert any(
                p == parent and pa <= a and b <= pb for p, pa, pb in spans
            ), name
