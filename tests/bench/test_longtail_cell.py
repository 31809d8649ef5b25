"""Configuration ``yws-longtail`` and its cell ``longtail-coldstart``: the
deployment is ``yws-1chip`` with a heavy tail of room sizes; a restart
group holds an eighth of each kind and 16 distinct long documents in
every seed; the committed long documents are what their fixture script
makes and what a CPU ``Y.Doc`` replays; the tiny cell is ``correct``
with every room planned cold, and stops being so under each fault
control; the six readers this cell adds."""

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
CELL = "longtail-coldstart"
LONGDOCS = harness.HERE / "longdocs"
DOCUMENTS = json.loads((LONGDOCS / "documents.json").read_text())["documents"]
# a seed whose fault control strikes the seam's second call after the
# window opens (faults.install draws 1..40; one tiny load makes 13 calls
# of each seam, and a busy machine's window may hold no second load)
EARLY = 2**31 + 12
READERS = (
    "stage_fill", "stage_mb_a_load", "stage_ms_a_load", "rebuild_ms_a_load",
    "plan_straggler_ms_a_load", "plan_pool_balance",
)


def longtail():
    return harness.load_module("generators", "longtail", ROOTS)


def reader(name):
    return harness.load_module("layer_metrics", name, ROOTS)


@pytest.fixture(scope="module")
def longtail_manifest(tiny_manifest):
    """The tiny manifest with ``tiny-longtail`` standing in for
    ``longtail-coldstart``."""
    m = copy.deepcopy(tiny_manifest)
    m["workloads"].append({
        "name": "tiny-longtail", "config": "tiny-longtail",
        "traffic": "tiny-coldstart-longtail", "chips": 1, "why": "tests",
    })
    real = {x["name"]: x for x in MANIFEST["end_to_end"] + MANIFEST["per_layer"]}
    for metric in m["end_to_end"] + m["per_layer"]:
        if CELL in real[metric["name"]].get("workloads", ()):
            metric["workloads"] = metric["workloads"] + ["tiny-longtail"]
    return m


@pytest.fixture
def run(run_tiny, longtail_manifest):
    def go(**kw):
        return run_tiny("tiny-longtail", manifest=longtail_manifest, **kw)

    return go


# -- the configuration, the documents and the draw, no device -------------


def test_the_configuration_is_yws_1chip_with_a_tail_of_room_sizes():
    one = harness.load_data("configs", "yws-1chip", ROOTS)
    cfg = harness.load_data("configs", "yws-longtail", ROOTS)
    for key in ("chips", "mesh_devices", "slots", "provider", "reduced"):
        assert cfg[key] == one[key]
    # yws-1chip's guarantees word for word, and what the cell compares
    assert cfg["guarantees"][:4] == one["guarantees"]
    assert len(cfg["guarantees"]) == 5 and "documents.json" in cfg["guarantees"][4]
    assert cfg["rooms"] == {"distinct": 3712, "storm": 256, "b4": 96, "prepend": 32}
    assert sum(cfg["rooms"].values()) == cfg["slots"] == 4096
    assert cfg["long_documents"] == {"b4": 12, "prepend": 4}
    assert {k: len(v) for k, v in cfg["long_document_seeds"].items()} == (
        cfg["long_documents"]
    )
    assert cfg["reduced"] == [] and "rectangular" in cfg["why"]
    assert any("share of long rooms" in a for a in cfg["assumed"])
    for key in ("source", "deployment"):
        assert cfg[key]
    cell = {w["name"]: w for w in MANIFEST["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "yws-longtail", "coldstart-longtail", 1
    )
    traffic = harness.load_data("traffic", "coldstart-longtail", ROOTS)
    assert traffic["generator"] == "longtail" and traffic["trace_units"] == 2
    # an eighth of each kind
    assert traffic["group_rooms"] == {k: n // 8 for k, n in cfg["rooms"].items()}
    assert traffic["end_to_end"] == {"bulk_rate": "work_per_timed_second"}


def test_the_cell_is_listed_where_the_issue_says():
    listed = {
        m["name"] for m in MANIFEST["per_layer"] if CELL in m.get("workloads", ())
    }
    shared = {
        "ingest_share", "unspanned_share", "window_trend", "compact_share",
        "plan_share", "plan_cache_hit", "pack_share", "dispatch_share",
        "emit_share", "apply_roofline", "fence_share", "device_idle",
        "peak_hbm_gb", "compiles_in_window", "scatter_ms_a_load",
        "apply_ms_a_load",
    }
    assert listed == {f"{n}.bulk" for n in shared | set(READERS)}
    for m in MANIFEST["per_layer"]:
        if m["name"].split(".")[0] in READERS:
            assert (m["moves"], m["workloads"]) == ("bulk_rate", [CELL])
    bulk = {m["name"]: m for m in MANIFEST["end_to_end"]}["bulk_rate"]
    assert bulk["workloads"][-1] == CELL and bulk["bound"] == 0.08


@pytest.mark.parametrize("name", sorted(DOCUMENTS))
def test_a_committed_long_document_is_what_a_ydoc_replays(name):
    """``documents.json`` (state vector and text digest of every long
    document, from which a load's elements are counted and a reloaded
    long room is judged) against the committed update itself."""
    entry = DOCUMENTS[name]
    update = zlib.decompress((LONGDOCS / f"{name}.bin.z").read_bytes())
    assert len(update) == entry["update_bytes"]
    assert hashlib.sha256(update).hexdigest() == entry["update_sha256"]
    w = oracle.Want(oracle.Oracle.replay([update]))
    assert sorted(w.sv.items()) == [tuple(x) for x in entry["state_vector"]]
    assert oracle.text_digest(w.text) == entry["text_digest"]
    # B4's published counts, or 100,000 rows that cannot merge; typed
    # under client ids no other document has
    assert sum(w.sv.values()) == (182_000 if entry["kind"] == "b4" else 100_000)
    assert sorted(w.sv) == entry["clients"]
    others = [c for n, e in DOCUMENTS.items() if n != name for c in e["clients"]]
    assert not set(entry["clients"]) & set(others)


def test_the_documents_are_the_fixture_scripts_own():
    """``scripts/gen_longtail_fixtures.py`` makes a prepend as
    ``bench.gen_prepend_fragmented`` does (with the client id a
    parameter) and a B4 stand-in by ``gen_b4_fixture.generate``."""
    argv, sys.argv = sys.argv, [""]
    sys.path.insert(0, str(harness.ROOT / "scripts"))
    try:
        import bench
        import gen_b4_fixture
        import gen_longtail_fixtures as gen
    finally:
        sys.argv = argv
        sys.path.remove(str(harness.ROOT / "scripts"))
    assert gen.gen_prepend(300, 3, 77) == bench.gen_prepend_fragmented(300)[0]
    assert gen.gen_prepend(300, 4, 7004) != gen.gen_prepend(300, 4, 7005)
    a, meta = gen_b4_fixture.generate(4000, 1500, seed=13)
    b, _meta = gen_b4_fixture.generate(4000, 1500, seed=13, clients=(101, 202))
    assert a == b and sorted(map(int, meta["state_vector"])) == [101, 202]
    cfg = harness.load_data("configs", "yws-longtail", ROOTS)
    made = {
        f"{kind}-{seed}"
        for kind, seeds in cfg["long_document_seeds"].items() for seed in seeds
    }
    assert made == set(DOCUMENTS)
    for name, entry in DOCUMENTS.items():
        clients = (
            gen.b4_clients(entry["seed"]) if entry["kind"] == "b4"
            else (gen.prepend_client(entry["seed"]),)
        )
        assert list(clients) == entry["clients"], name
    # the fixtures stay under 4 MB in the tree
    assert sum(p.stat().st_size for p in LONGDOCS.iterdir()) < 4_000_000


class PaperCell:
    """What the generator sees of a run, with no provider behind it."""

    def __init__(self, cfg, seed):
        self.cfg, self.seed = cfg, seed
        self.plan = deployment.room_plan(cfg, seed)
        self.clock, self.log = time.perf_counter, lambda msg: None
        self.counts, self.refused, self.in_window = {}, [], False


@pytest.fixture(scope="module")
def full_size():
    cfg = harness.load_data("configs", "yws-longtail", ROOTS)
    traffic = harness.load_data("traffic", "coldstart-longtail", ROOTS)
    mod = longtail()
    return [
        mod.Generator(traffic, PaperCell(cfg, seed))
        for seed in (7, 2**31 + 12345)
    ]


def test_a_group_is_the_issues_counts_and_work_in_every_seed(full_size):
    a, b = full_size
    for gen in full_size:
        kinds = [r.kind for r in gen.short] + [
            doc.entry["kind"] for doc in gen.long.values()
        ]
        assert {k: kinds.count(k) for k in set(kinds)} == {
            "distinct": 464, "storm": 32, "b4": 12, "prepend": 4
        }
        assert len(gen.load) == len({g for g, _u in gen.load}) == 512
        # no room of a load is a clone of another
        assert len({hashlib.sha256(u).digest() for _g, u in gen.load}) == 512
        # the documents' own state vectors: ~3.13M in the short rooms,
        # 12 x 182,000 and 4 x 100,000 in the long ones
        short = sum(oracle.ELEMENTS[r.kind][r.trace] for r in gen.short)
        assert gen.elements == short + 12 * 182_000 + 4 * 100_000
        assert 5.6e6 < gen.elements < 5.8e6
    assert a.elements == b.elements
    assert sorted((r.kind, r.trace) for r in a.short) == sorted(
        (r.kind, r.trace) for r in b.short
    )
    for gen in full_size:
        assert sorted(doc.name for doc in gen.long.values()) == sorted(DOCUMENTS)
        by_guid = {r.guid: r for r in gen.cell.plan}
        # a long document is homed in a room of its kind
        assert all(by_guid[g].kind == d.entry["kind"] for g, d in gen.long.items())
    # which rooms hold them is the seed's
    assert set(a.long) != set(b.long)


# -- the tiny cell on this CPU ---------------------------------------------


def test_the_tiny_cell_is_correct_and_plans_every_room_cold(run, capsys):
    r = run()
    out = capsys.readouterr().out
    assert r["correct"] is True and r["failed"] == 0
    assert set(r["metrics"]) == {"bulk_rate", "setup_s"}
    checks = re.findall(r"check (\w+): (\d+) \(limit 0\) (ok|FAILED)", out)
    assert len(checks) >= 19 and all(v == "0" and s == "ok" for _n, v, s in checks)
    # a load is its group's size in cold plans: no clone, no hit; the
    # keystroke's flush stages the group in its three width classes
    assert "load flushes in the window: cold plans [12], clones and cache hits [0]" in out
    assert (
        "keystroke flushes in the window: cold plans [1], clones and cache "
        "hits [0], rows_staged_blocks [3]"
    ) in out
    assert "2 long rooms held to documents.json" in out and ", 0 differ" in out
    # the work is the documents' own state vectors
    elements, loads = map(int, re.search(
        r"(\d+) elements a load, (\d+) loads", out
    ).groups())
    long_elements = sum(
        n for name in ("b4-14", "prepend-4")
        for _c, n in DOCUMENTS[name]["state_vector"]
    )
    assert long_elements == 282_000 and loads >= 1
    short = re.search(r"group of 12 rooms, (\d+) elements", out)
    assert int(short.group(1)) == elements > long_elements
    assert f"work {elements * loads} elements" in out


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_fault_turns_the_tiny_cell_incorrect(run, fault, capsys):
    r = run(fault=fault, seed=EARLY)
    assert r["correct"] is False and r["failed"] >= 1
    assert "FAILED" in capsys.readouterr().out


# -- the readers ------------------------------------------------------------

TRACE = {
    "window_s": 2.0,
    "spans": {
        "ytpu.compact.alloc": 0.010, "ytpu.compact.put": 0.020,
        "ytpu.compact.scatter": 0.006, "ytpu.compact.rebuild": 0.300,
        "ytpu.plan.native": 0.250,
    },
}
COUNTERS = {
    "units": 2, "rows_staged_bytes": 18_000_000, "rows_held_bytes": 10_800_000,
    "rows_staged_blocks": 6, "plan_room_max_s": 0.4, "plan_pool_s": 1.3,
    "plan_threads_host": 13,
}
WANT = {
    "stage_fill": 60.0, "stage_mb_a_load": 9.0, "stage_ms_a_load": 18.0,
    "rebuild_ms_a_load": 150.0, "plan_straggler_ms_a_load": 200.0,
    "plan_pool_balance": 40.0,
}


@pytest.mark.parametrize("name", READERS)
def test_reader_on_hand_made_counters(name):
    assert reader(name).read(TRACE, COUNTERS) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_where_the_program_has_nothing(name):
    """A program that keeps no such counter or opens no such span (the
    parent commit for the planner's clock): the metric is left out."""
    bare = {"units": 2, "plan_threads_host": 13}
    assert reader(name).read({"spans": {}, "window_s": 1.0}, bare) is None
    assert reader(name).read({"spans": {}, "window_s": 1.0}, {}) is None
    if name.startswith("plan_"):
        # the parent sums the staging counters and has no pool clock
        old = {k: v for k, v in COUNTERS.items() if not k.startswith("plan_")}
        old["plan_threads_host"] = 13
        assert reader(name).read(TRACE, old) is None
