"""Configuration ``yws-prosemirror`` and its cell ``prosemirror-flood``:
the deployment is ``yws-1chip`` with half its rooms ProseMirror
documents; the committed documents are what their fixture script makes
and what a CPU ``Y.Doc`` replays, and ``documents.json`` is recounted
from the files; a unit has the issue's make-up in every seed; the tiny
cell is ``correct``, reports exactly the metrics listed for it, and
stops being correct under each fault control; the five readers this
cell adds."""

import copy
import hashlib
import json
import re
import sys
import time
import zlib

import pytest

from benchmarks import deployment, faults, harness, oracle

ROOTS = (harness.HERE,)
MANIFEST = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
CELL = "prosemirror-flood"
PROSEDOCS = harness.HERE / "prosedocs"
DOCUMENTS = json.loads((PROSEDOCS / "documents.json").read_text())["documents"]
READERS = (
    "nested_row_share", "structure_row_share", "emit_fallback_share",
    "segs_a_flush", "cleanup_share",
)
SHARED = (
    "ingest_share", "unspanned_share", "unit_p95_ms", "window_trend",
    "compact_share", "plan_share", "plan_cache_hit", "pack_share",
    "dispatch_share", "emit_share", "apply_roofline", "fence_share",
    "device_idle", "peak_hbm_gb", "compiles_in_window",
)


def generator():
    return harness.load_module("generators", "prosemirror", ROOTS)


def reader(name):
    return harness.load_module("layer_metrics", name, ROOTS)


@pytest.fixture(scope="module")
def pm_manifest(tiny_manifest):
    """The tiny manifest with ``tiny-prosemirror`` standing in for
    ``prosemirror-flood``."""
    m = copy.deepcopy(tiny_manifest)
    m["workloads"].append({
        "name": "tiny-prosemirror", "config": "tiny-prosemirror",
        "traffic": "tiny-flood-prosemirror", "chips": 1, "why": "tests",
    })
    real = {x["name"]: x for x in MANIFEST["end_to_end"] + MANIFEST["per_layer"]}
    for metric in m["end_to_end"] + m["per_layer"]:
        if CELL in real[metric["name"]].get("workloads", ()):
            metric["workloads"] = metric["workloads"] + ["tiny-prosemirror"]
    return m


@pytest.fixture
def run(run_tiny, pm_manifest):
    def go(**kw):
        return run_tiny("tiny-prosemirror", manifest=pm_manifest, **kw)

    return go


# -- the configuration, the documents and the draw, no device -------------


def test_the_configuration_is_yws_1chip_with_typed_rooms():
    one = harness.load_data("configs", "yws-1chip", ROOTS)
    cfg = harness.load_data("configs", "yws-prosemirror", ROOTS)
    for key in ("chips", "mesh_devices", "slots", "provider", "reduced", "rooms"):
        assert cfg[key] == one[key]
    assert cfg["guarantees"][: len(one["guarantees"])] == one["guarantees"]
    assert len(cfg["guarantees"]) == len(one["guarantees"]) + 2
    assert "root's name" in cfg["guarantees"][-2]
    assert "native planner" in cfg["guarantees"][-1]
    assert cfg["architecture"] is None and cfg["reduced"] == []
    assert (cfg["prosemirror_rooms"], cfg["prosemirror_documents"]) == (2048, 256)
    assert len(set(cfg["prosemirror_document_seeds"])) == 256
    assert cfg["prosemirror_root"] == "prosemirror"
    assert "y-prosemirror" in cfg["source"] and len(cfg["source"]) <= 200
    entry = {c["name"]: c for c in MANIFEST["configs"]}["yws-prosemirror"]
    assert entry["source"] == cfg["source"] and entry["reduced"] == []
    # the rooms' segments, and the width of the list heads they give
    stated = cfg["room_shapes"]
    for key in ("segments", "rows"):
        values = sorted(d[key] for d in DOCUMENTS.values())
        assert stated[key] == {
            "min": values[0], "median": values[len(values) // 2],
            "max": values[-1],
        }
    assert stated["seg_cap"] == 512 > stated["segments"]["max"] > 256
    assert stated["window_headroom_segments"] == 512 - stated["segments"]["max"]
    cell = {w["name"]: w for w in MANIFEST["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "yws-prosemirror", "flood-prosemirror", 1
    )
    assert len(MANIFEST["workloads"]) == 6
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1


def test_the_cell_is_listed_where_the_issue_says():
    listed = {
        m["name"] for m in MANIFEST["per_layer"] if CELL in m.get("workloads", ())
    }
    assert listed == {f"{n}.flood" for n in SHARED + READERS}
    for m in MANIFEST["per_layer"]:
        if m["name"].split(".")[0] in READERS:
            assert (m["moves"], m["workloads"]) == ("edit_rate", [CELL])
        elif m["name"].split(".")[0] in SHARED and m["name"].endswith(".flood"):
            assert m["workloads"] == ["yws-flood", CELL]
    rate = {m["name"]: m for m in MANIFEST["end_to_end"]}["edit_rate"]
    assert rate["workloads"] == ["yws-flood", CELL] and rate["bound"] == 0.15
    # the last entries of their lists: nothing put first or in the middle
    assert MANIFEST["configs"][-1]["name"] == "yws-prosemirror"
    assert MANIFEST["workloads"][-1]["name"] == CELL
    assert [m["name"].split(".")[0] for m in MANIFEST["per_layer"][-5:]] == list(
        READERS
    )


def test_the_traffic_is_the_issues_unit():
    traffic = harness.load_data("traffic", "flood-prosemirror", ROOTS)
    flood = harness.load_data("traffic", "flood", ROOTS)
    assert traffic["generator"] == "prosemirror"
    u = traffic["unit"]
    assert (u["duets"], u["typed"], u["erased"], u["enter"], u["marks"], u["attrs"]) == (
        8, 39, 20, 2, 2, 1
    )
    assert 2 * u["duets"] + u["typed"] + u["erased"] + 5 == 80
    for key in (
        "solo_rooms", "duet_rooms", "units_per_circuit", "typing_run",
        "erasing_run", "jump_every_runs", "trace_units", "ladder",
        "rehearsal_circuits_max", "settled_within", "end_to_end", "work_unit",
    ):
        assert traffic[key] == flood[key], key
    # rehearsal only: one flush wider than a unit's in deletes and heads
    wide = traffic["wide_unit"]
    assert set(wide) == {"typed", "erased", "enter"}
    # a row deleted a backspace and at least one an Enter inside a text,
    # two list heads such an Enter: over 64 deletes, over 16 heads
    assert wide["erased"] + wide["enter"] > 64 > u["erased"]
    assert 2 * wide["enter"] > 16 > 4 * u["enter"]
    assert sum(wide.values()) <= traffic["solo_rooms"]
    assert any("6.25%" in a for a in traffic["assumed"])
    assert any("no join" in a for a in traffic["assumed"])


@pytest.mark.parametrize("name", sorted(DOCUMENTS)[::16])
def test_a_committed_document_is_what_a_ydoc_replays(name):
    """``documents.json`` recounted from the file: state vector, the
    XML string's digest, rows and segments of the host mirror, the text
    blocks; and the plain client reads the same document off a replay."""
    import yjs_tpu as Y
    from benchmarks.plain_prosemirror import ROOT, PlainDoc
    from yjs_tpu.ops.columns import DocMirror

    entry = DOCUMENTS[name]
    update = zlib.decompress((PROSEDOCS / f"{name}.bin.z").read_bytes())
    assert len(update) == entry["update_bytes"]
    assert hashlib.sha256(update).hexdigest() == entry["update_sha256"]
    doc = oracle.Oracle.replay([update])
    sv = Y.decode_state_vector(Y.encode_state_vector(doc))
    assert sorted(sv.items()) == [tuple(x) for x in entry["state_vector"]]
    assert sorted(sv) == entry["clients"]
    xml = doc.get_xml_fragment(ROOT).to_string()
    assert oracle.text_digest(xml) == entry["xml_digest"]
    assert len(xml) == entry["xml_chars"]
    # a replay (a remote transaction that creates every text) cleans nothing
    assert Y.merge_updates([Y.encode_state_as_update(doc)]) == Y.merge_updates(
        [update]
    )
    plain = PlainDoc.of_tree(generator().tree_of(doc), sv)
    assert plain.xml() == xml and len(plain.blocks()) == entry["text_blocks"]
    mirror = DocMirror(ROOT)
    mirror.ingest(update)
    mirror.prepare_step()
    assert (mirror.n_rows, mirror.n_segs) == (entry["rows"], entry["segments"])
    # the schema's nodes and marks are all there, three parents deep
    for tag in ("<heading level=", "<paragraph", "<list_item><paragraph>"):
        assert tag in xml
    assert any(m in xml for m in ("<strong>", "<em>", "<link href=", "<code>"))


def test_the_documents_are_the_fixture_scripts_own():
    argv, sys.argv = sys.argv, [""]
    sys.path.insert(0, str(harness.ROOT / "scripts"))
    try:
        import gen_prosemirror_fixtures as gen
    finally:
        sys.argv = argv
        sys.path.remove(str(harness.ROOT / "scripts"))
    import yjs_tpu as Y

    cfg = harness.load_data("configs", "yws-prosemirror", ROOTS)
    assert {f"pm-{s}" for s in cfg["prosemirror_document_seeds"]} == set(DOCUMENTS)
    assert gen.SESSION == 1500
    seed = cfg["prosemirror_document_seeds"][0]
    update = zlib.decompress((PROSEDOCS / f"pm-{seed}.bin.z").read_bytes())
    assert Y.encode_state_as_update(gen.write_session(seed)) == update
    for entry in DOCUMENTS.values():
        assert list(gen.clients(entry["seed"])) == entry["clients"]
    others = [c for e in DOCUMENTS.values() for c in e["clients"]]
    assert len(others) == len(set(others))
    # the fixtures stay under 4 MB in the tree
    assert sum(p.stat().st_size for p in PROSEDOCS.iterdir()) < 4_000_000


class PaperCell:
    """What the generator sees of a run, with no provider behind it."""

    def __init__(self, cfg, seed):
        self.cfg, self.seed = cfg, seed
        self.plan = deployment.room_plan(cfg, seed)
        self.clock, self.log = time.perf_counter, lambda msg: None
        self.counts, self.refused, self.in_window = {}, [], False


def test_the_typed_rooms_hold_the_same_documents_in_every_seed():
    cfg = harness.load_data("configs", "yws-prosemirror", ROOTS)
    traffic = harness.load_data("traffic", "flood-prosemirror", ROOTS)
    mod = generator()
    a, b = (
        mod.Generator(traffic, PaperCell(cfg, seed)) for seed in (7, 2**31 + 12345)
    )
    for gen in (a, b):
        assert (len(gen.duet_specs), len(gen.solo_specs), len(gen.idle_specs)) == (
            256, 1024, 768
        )
        assert len(gen.home) == 2048
        held = [doc.name for doc in gen.home.values()]
        assert {held.count(name) for name in DOCUMENTS} == {8}
        hot = [gen.home[r.guid].name for r in gen.duet_specs + gen.solo_specs]
        assert {hot.count(name) for name in DOCUMENTS} == {5}
        # every document is a duet room once
        assert sorted(gen.home[r.guid].name for r in gen.duet_specs) == sorted(
            DOCUMENTS
        )
        assert gen.updates_a_unit == 80
    assert set(a.home) != set(b.home)  # which rooms hold them is the seed's


# -- the tiny cell on this CPU ---------------------------------------------


def test_the_tiny_cell_is_correct_and_reports_its_metrics(run, capsys):
    r = run()
    out = capsys.readouterr().out
    assert r["correct"] is True and r["failed"] == 0
    assert set(r["metrics"]) == {"edit_rate", "setup_s"}
    checks = re.findall(r"check (\w+): (\d+) \(limit 0\) (ok|FAILED)", out)
    assert len(checks) >= 19 and all(v == "0" and s == "ok" for _n, v, s in checks)
    assert "16 typed rooms homed over 4 documents" in out
    assert "8 solo and 4 duet rooms" in out and "12 updates a unit" in out
    held = re.search(
        r"(\d+) typed rooms' XML strings held .*\((\d+) hot and (\d+) idle also "
        r"from the device's rows\).* (\d+) differ", out
    )
    assert held and tuple(map(int, held.groups())) == (16, 12, 2, 0)
    # a unit's make-up: 2 duets (4 updates), 1 typed, 2 erased, 5 structure
    made = eval(re.search(r"transactions typed since set-up: (\{.*?\})", out).group(1))
    units = made["duet"] // 4
    assert (made["typed"], made["erased"], made["enter"], made["mark"], made["attr"]) == (
        units, 2 * units, 2 * units, 2 * units, units
    )
    counted = dict(re.findall(
        r"(rows_\w+|segs_created|format_cleanup_deleted|emit_\w+) (\d+)", out
    ))
    assert int(counted["rows_nested"]) > 0.9 * int(counted["rows_planned"]) > 0
    assert int(counted["emit_fallback"]) == 0 < int(counted["emit_batched"])
    assert int(counted["rows_format"]) > 0 and int(counted["segs_created"]) > 0


def test_the_traced_tiny_cell_reports_the_listed_metrics(run):
    r = run(trace=True)
    assert r["correct"] is True
    got = set(r["metrics"])
    listed = {f"{n}.flood" for n in SHARED + READERS}
    assert got <= listed
    # what a CPU cannot give: device memory, a device trace's kernels
    assert listed - got <= {
        "peak_hbm_gb.flood", "apply_roofline.flood", "unit_p95_ms.flood",
    }
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert m["nested_row_share.flood"] > 90
    assert m["emit_fallback_share.flood"] == 0
    assert 0 < m["structure_row_share.flood"] < 100
    assert m["segs_a_flush.flood"] > 0 and m["cleanup_share.flood"] > 0


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_fault_turns_the_tiny_cell_incorrect(run, fault, capsys):
    r = run(fault=fault)
    assert r["correct"] is False and r["failed"] >= 1
    assert "FAILED" in capsys.readouterr().out


def test_a_program_that_reads_no_root_by_name_fails_cleanly(run, monkeypatch):
    """The parent commit: the cell ends at set-up, with an error."""
    from yjs_tpu.provider import TpuProvider

    old = TpuProvider.xml_string
    monkeypatch.setattr(
        TpuProvider, "xml_string", lambda self, guid: old(self, guid)
    )
    with pytest.raises(harness.BenchError, match="reads no root by its name"):
        run()


# -- the readers ------------------------------------------------------------

TRACE = {"window_s": 2.0, "spans": {"ytpu.plan.cleanup": 0.004}}
COUNTERS = {
    "flushes": 50, "rows_planned": 4000, "rows_nested": 3900,
    "rows_format": 200, "rows_attr": 100, "rows_type": 180,
    "segs_created": 300, "emit_batched": 3580, "emit_fallback": 20,
}
WANT = {
    "nested_row_share": 97.5, "structure_row_share": 12.0,
    "emit_fallback_share": 20 / 36.0, "segs_a_flush": 6.0,
    "cleanup_share": 0.2,
}


@pytest.mark.parametrize("name", READERS)
def test_reader_on_hand_made_counters(name):
    assert reader(name).read(TRACE, COUNTERS) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_where_the_program_has_nothing(name):
    """The parent commit keeps no such counter and opens no such span
    (its generator-side sums are then absent): the metric is left out,
    not raised."""
    bare = {"flushes": 50, "units": 50, "plan_cache_hits": 0}
    for trace in ({"spans": {}, "window_s": 1.0},):
        assert reader(name).read(trace, bare) is None
        assert reader(name).read(trace, {}) is None
