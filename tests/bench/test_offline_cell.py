"""Configuration ``yws-offline`` and its cell ``offline-merge``: the
deployment is ``yws-1chip`` behind offline-first clients, a quarter of
its rooms ``Y.Array``s; a wave holds the same documents, shapes and
arrival classes in every seed; the tiny cell is ``correct``, writes
every link of its waves through the element lanes, reports the metrics
listed for it and stops being correct under the fault controls; the six
readers this cell adds; the earlier cells' pins of the manifest's tail
hold less the cells appended since."""

import copy
import importlib
import json
import re
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks import deployment, faults, harness

ROOTS = (harness.HERE,)
MANIFEST = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
CELL = "offline-merge"
EARLY = 2**31 + 12  # a seed whose fault control strikes early in the window
SHARED = (
    "unspanned_share", "compact_share", "plan_share", "plan_cache_hit",
    "pack_share", "dispatch_share", "emit_share", "apply_roofline",
    "fence_share", "device_idle", "peak_hbm_gb", "compiles_in_window",
)
NEW = {
    "lane_link_share": ("%", "higher", "program_counter", "pack and transfer"),
    "lane_fill": ("%", "higher", "program_counter", "pack and transfer"),
    "pack_lanes_share": ("%", "lower", "program_span", "pack and transfer"),
    "lanes_ms_a_wave": ("ms", "lower", "device_trace", "device kernels"),
    "lanes_roofline": ("%", "higher", "device_trace", "device kernels"),
    "conflict_steps_a_struct": ("count", "lower", "program_counter", "host planner"),
}


def offline_generator():
    return harness.load_module("generators", "offline", ROOTS)


def reader(name):
    return harness.load_module("layer_metrics", name, ROOTS)


@pytest.fixture(scope="module")
def offline_manifest(tiny_manifest):
    """The tiny manifest with ``tiny-offline`` standing in for
    ``offline-merge``."""
    m = copy.deepcopy(tiny_manifest)
    m["workloads"].append({
        "name": "tiny-offline", "config": "tiny-offline",
        "traffic": "tiny-offline-merge", "chips": 1, "why": "tests",
    })
    real = {x["name"]: x for x in MANIFEST["end_to_end"] + MANIFEST["per_layer"]}
    for metric in m["end_to_end"] + m["per_layer"]:
        if CELL in real[metric["name"]].get("workloads", ()):
            metric["workloads"] = metric["workloads"] + ["tiny-offline"]
    return m


@pytest.fixture
def run(run_tiny, offline_manifest):
    def go(**kw):
        return run_tiny("tiny-offline", manifest=offline_manifest, **kw)

    return go


# -- the configuration, the manifest and the draw, no device -----------------


def test_the_configuration_is_yws_1chip_behind_offline_first_clients():
    one = harness.load_data("configs", "yws-1chip", ROOTS)
    cfg = harness.load_data("configs", "yws-offline", ROOTS)
    for key in ("chips", "mesh_devices", "slots", "rooms", "provider"):
        assert cfg[key] == one[key]
    assert cfg["guarantees"][:4] == one["guarantees"]
    assert len(cfg["guarantees"]) == 8
    for word, g in zip(
        ("in any order", "every update of a returning session",
         "native planner", "realloc_bytes 0"),
        cfg["guarantees"][4:],
    ):
        assert word in g
    assert cfg["reduced"] == []
    assert (cfg["array_root"], cfg["array_rooms"], cfg["array_documents"]) == (
        "array", 1024, 256
    )
    assert cfg["array_base_inserts"] == 1500
    assert cfg["offline_writers"] == {"text": 2, "array": 3}
    assert cfg["offline_operations"] == 6000
    assert cfg["room_shapes"]["cap"] == 131072
    for word in ("y-indexeddb", "crdt-benchmarks B2.2-B2.4", "N=6000",
                 "BASELINE.json config 4"):
        assert word in cfg["source"]
    assert len(cfg["source"]) <= 200
    assert any("B2.1" in a for a in cfg["assumed"])
    entry = MANIFEST["configs"][-1]
    assert entry == {
        "name": "yws-offline", "source": cfg["source"],
        "file": "benchmarks/configs/yws-offline.json", "reduced": [],
        "why": entry["why"],
    }
    traffic = harness.load_data("traffic", "offline-merge", ROOTS)
    assert traffic["generator"] == "offline" and traffic["trace_units"] == 12
    assert traffic["wave_rooms"] == 64 == sum(traffic["wave_shapes"].values())
    assert traffic["wave_shapes"] == dict.fromkeys(
        ("b2.2", "b2.3", "b2.4", "array"), 16
    )
    assert (traffic["waves"], traffic["together_share"]) == (2, 0.5)
    assert traffic["lengths"] == {"word": [2, 10], "delete": [1, 10]}
    assert traffic["first_client"] == 3_000_000
    assert traffic["sample_rooms_a_wave"] == 4
    assert traffic["rehearsal_waves_max"] == 6
    assert traffic["end_to_end"] == {"bulk_rate": "work_per_timed_second"}
    assert traffic["work_unit"] == "elements"


def test_the_cell_is_listed_where_the_issue_says():
    cell = MANIFEST["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "yws-offline", "offline-merge", 1
    )
    assert len(MANIFEST["workloads"]) == 8
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    listed = {
        m["name"] for m in MANIFEST["per_layer"] if CELL in m.get("workloads", ())
    }
    assert listed == {f"{n}.bulk" for n in set(SHARED) | set(NEW)}
    # the six new entries are the last six, in the issue's order
    assert [m["name"] for m in MANIFEST["per_layer"][-6:]] == [
        f"{n}.bulk" for n in NEW
    ]
    for m in MANIFEST["per_layer"][-6:]:
        unit, better, source, layer = NEW[m["name"].split(".")[0]]
        assert m == {
            "name": m["name"], "unit": unit, "better": better,
            "source": source, "layer": layer, "moves": "bulk_rate",
            "workloads": [CELL],
        }
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        if CELL in m.get("workloads", ()):  # appended, never put in the middle
            assert m["workloads"][-1] == CELL
    bulk = {m["name"]: m for m in MANIFEST["end_to_end"]}["bulk_rate"]
    assert bulk["workloads"][-2:] == ["crash-recover", CELL]
    assert bulk["bound"] == 0.08 and MANIFEST["run_seconds"] == 20


def crash_cell():
    return importlib.import_module("test_crash_cell")


@pytest.mark.parametrize("module, test, later", [
    ("test_longtail_cell", "test_the_cell_is_listed_where_the_issue_says",
     ("prosemirror-flood", "crash-recover", CELL)),
    ("test_prosemirror_cell",
     "test_the_configuration_is_yws_1chip_with_typed_rooms",
     ("crash-recover", CELL)),
    ("test_prosemirror_cell", "test_the_cell_is_listed_where_the_issue_says",
     ("crash-recover", CELL)),
    ("test_crash_cell", "test_the_cell_is_listed_where_the_issue_says",
     (CELL,)),
])
def test_an_earlier_cells_pin_holds_less_the_later_cells(
    module, test, later, monkeypatch
):
    """The seven tests of earlier cells that pin the manifest's tail
    (``tests/conftest.py`` ``PINNED_TO_AN_EARLIER_TAIL``) pass against the
    manifest with the cells appended since taken off its ends: this cell
    was appended, and nothing was put first or in the middle.  These four
    pin it themselves."""
    mod = importlib.import_module(module)
    monkeypatch.setattr(mod, "MANIFEST", crash_cell().manifest_less(later))
    getattr(mod, test)()


@pytest.mark.parametrize("module, test, later", [
    ("test_longtail_cell", "test_the_cell_is_listed_where_the_issue_says",
     ("prosemirror-flood", "crash-recover")),
    ("test_prosemirror_cell",
     "test_the_configuration_is_yws_1chip_with_typed_rooms", ("crash-recover",)),
    ("test_prosemirror_cell", "test_the_cell_is_listed_where_the_issue_says",
     ("crash-recover",)),
])
def test_the_crash_cells_three_cases_hold_less_this_cell(
    module, test, later, monkeypatch
):
    """``test_crash_cell.test_an_earlier_cells_pin_holds_less_the_later_
    cells`` itself, which takes ``crash-recover`` for the manifest's last
    cell: run against the manifest less this one."""
    crash = crash_cell()
    monkeypatch.setattr(crash, "MANIFEST", crash.manifest_less((CELL,)))
    crash.test_an_earlier_cells_pin_holds_less_the_later_cells(
        module, test, later, monkeypatch
    )


def test_the_pins_this_cell_moved_are_marked():
    conftest = (harness.ROOT / "tests" / "conftest.py").read_text()
    for name in (
        "test_crash_cell.py::test_the_cell_is_listed_where_the_issue_says",
        "test_crash_cell.py::test_an_earlier_cells_pin_holds_less_the_later_cells",
    ):
        assert name in conftest


class PaperCell:
    """What the generator sees of a run, with no provider behind it."""

    def __init__(self, cfg, seed):
        self.cfg, self.seed = cfg, seed
        self.plan = deployment.room_plan(cfg, seed)
        self.prov, self.wal_dir = None, Path("nowhere/wal")
        self.clock, self.log = time.perf_counter, lambda msg: None


def test_a_wave_holds_the_same_documents_in_every_seed():
    cfg = harness.load_data("configs", "yws-offline", ROOTS)
    traffic = harness.load_data("traffic", "offline-merge", ROOTS)
    mod = offline_generator()
    by_seed = []
    for seed in (7, 2**31 + 12345):
        gen = mod.Generator(traffic, PaperCell(cfg, seed))
        assert len(gen.array_specs) == 1024 and len(gen.sets) == 2
        # 256 base arrays, four rooms each
        assert sorted(gen.home.values()) == sorted(list(range(256)) * 4)
        rooms = [spec.guid for rooms in gen.sets for spec, _s, _t in rooms]
        assert len(set(rooms)) == 128  # disjoint sets
        sessions = ticks = None
        for rooms in gen.sets:
            assert len(rooms) == 64
            sessions = sum(
                gen.writers["array" if s == "array" else "text"]
                for _r, s, _t in rooms
            )
            # updates a tick: every writer of a room that arrives
            # together and the first of the others; then the seconds;
            # then the arrays' thirds
            ticks = [0, 0, 0]
            for _r, shape, together in rooms:
                n = gen.writers["array" if shape == "array" else "text"]
                for k in range(n):
                    ticks[0 if together else k] += 1
        assert (sessions, ticks) == (144, [104, 32, 8])
        by_seed.append([
            [(spec.kind, spec.trace, gen.home.get(spec.guid), shape, together)
             for spec, shape, together in rooms]
            for rooms in gen.sets
        ])
    assert by_seed[0] == by_seed[1]
    # text rooms hold the distinct traces after the first 1024 picks'
    assert [t[1] for t in by_seed[0][0][:48]] == list(range(48))
    assert [t[2] for t in by_seed[0][1][48:]] == list(range(16, 32))
    guids = lambda seed: {  # noqa: E731
        spec.guid for rooms in mod.Generator(
            traffic, PaperCell(cfg, seed)
        ).sets for spec, _s, _t in rooms
    }
    assert guids(7) != guids(8)  # the seed draws which room holds which


def test_every_wave_gives_a_sample_and_the_classes_take_turns():
    """Four rooms of every wave are kept for the any-order replays: one
    of each shape and arrival class over two consecutive waves of a set,
    whichever the seed."""
    cfg = harness.load_data("configs", "yws-offline", ROOTS)
    traffic = harness.load_data("traffic", "offline-merge", ROOTS)
    mod = offline_generator()
    gen = mod.Generator(traffic, PaperCell(cfg, 2**31 + 99))
    gen.rooms = [
        [mod.Room(spec, shape, together, b"", {}, (), b"", "text")
         for spec, shape, together in rooms]
        for rooms in gen.sets
    ]
    for lap in range(3):
        for s in range(2):
            number = 2 * lap + s
            first = gen._sample(mod.Wave(number, gen.rooms[s]))
            second = gen._sample(mod.Wave(number + 2, gen.rooms[s]))
            assert len(first) == len(second) == 4
            assert all(room in gen.rooms[s] for room in first + second)
            classes = {(room.shape, room.together) for room in first + second}
            assert len(classes) == 8
            # the same wave of the same seed gives the same sample
            assert first == gen._sample(mod.Wave(number, gen.rooms[s]))


def test_a_program_without_the_lane_counts_is_refused_at_set_up():
    """The parent of PR 46 keeps none of the four counts (and compiles a
    bulk lane key in every window): the command must end soon, with no
    result, and leave no writer process behind."""
    from yjs_tpu.obs import FLUSH_METRICS_SCHEMA

    cfg = harness.load_data("configs", "yws-offline", ROOTS)
    traffic = harness.load_data("traffic", "offline-merge", ROOTS)
    mod = offline_generator()
    assert set(mod.REQUIRED) <= set(FLUSH_METRICS_SCHEMA)
    cell = PaperCell(cfg, 7)
    older = {k: v for k, v in FLUSH_METRICS_SCHEMA.items() if k not in mod.REQUIRED}
    for kept, named in ((older, mod.REQUIRED), (
        {**older, "lane_links": 0, "row_links": 0}, mod.REQUIRED[2:],
    )):
        cell.prov = SimpleNamespace(engine=SimpleNamespace(last_flush_metrics=kept))
        gen = mod.Generator(traffic, cell)
        with pytest.raises(deployment.BenchError, match=", ".join(named) + " in"):
            gen.prepare()
        assert gen.pool is None


# -- the tiny cell on this CPU -------------------------------------------------


def test_the_tiny_cell_is_correct_and_every_link_takes_the_lanes(run, capsys):
    r = run()
    out = capsys.readouterr().out
    assert r["correct"] is True and r["failed"] == 0
    assert set(r["metrics"]) == {"bulk_rate", "setup_s"}
    checks = re.findall(r"check (\w+): (\d+) \(limit 0\) (ok|FAILED)", out)
    assert len(checks) >= 19 and all(v == "0" and s == "ok" for _n, v, s in checks)
    assert "rehearsal wave 1:" in out  # each set once at least
    merged, differ, sampled, sampled_waves, behind = map(int, re.search(
        r"offline: (\d+) merged rooms held to their writers' clocks in \S+ s, "
        r"(\d+) differ; (\d+) sampled rooms of (\d+) waves .* (\d+) differ;",
        out,
    ).groups())
    assert (merged, differ, behind) == (16, 0, 0)
    counts = dict(re.findall(
        r"(lane_links|row_links|lanes_dispatched|conflict_steps|rows_planned|"
        r"emit_batched|emit_fallback) (\d+)", out
    ))
    waves, elements, structs = map(int, re.search(
        r"(\d+) waves in the window, (\d+) elements and (\d+) structs", out
    ).groups())
    assert waves >= 1 and f"work {elements} elements" in out
    # every wave, rehearsed or timed, gives its sample (here all 8 rooms)
    assert sampled_waves >= waves + 2 and sampled == 8 * sampled_waves
    # a wave's links all take the element lanes; a struct is a row planned
    assert int(counts["row_links"]) == 0 < int(counts["lane_links"])
    assert int(counts["lanes_dispatched"]) > int(counts["lane_links"])
    assert int(counts["rows_planned"]) == structs
    assert int(counts["conflict_steps"]) > 0 == int(counts["emit_fallback"])
    # 18 sessions a wave: 18 handshakes and 18 updates acknowledged
    assert r["attempted"] >= 36 * waves


def test_the_traced_tiny_cell_reports_the_listed_metrics(run):
    r = run(trace=True)
    assert r["correct"] is True
    got = set(r["metrics"])
    listed = {f"{n}.bulk" for n in SHARED + tuple(NEW)}
    assert got <= listed
    # what a CPU cannot give: device memory, a device trace's kernels
    assert listed - got <= {
        "peak_hbm_gb.bulk", "apply_roofline.bulk", "lanes_ms_a_wave.bulk",
        "lanes_roofline.bulk",
    }
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert m["compiles_in_window.bulk"] == 0
    assert m["lane_link_share.bulk"] == 100.0
    assert 0 < m["lane_fill.bulk"] < 100
    # ``pack_share`` is the self time of ``ytpu.pack``: from PR 46 what
    # is left of the phase beside its two spans
    assert m["pack_lanes_share.bulk"] > 0 and m["pack_share.bulk"] > 0
    assert 0 < m["conflict_steps_a_struct.bulk"] < 10
    # no two updates of a run are byte-equal: the plan cache serves none
    assert m["plan_cache_hit.bulk"] == 0


@pytest.mark.parametrize("fault", ["drop_update", "drop_in_engine"])
def test_fault_turns_the_tiny_cell_incorrect(run, fault, capsys):
    assert fault in faults.FAULTS
    r = run(fault=fault, seed=EARLY)
    assert r["correct"] is False and r["failed"] >= 1
    out = capsys.readouterr().out
    assert "FAILED" in out
    assert re.search(r"refused_updates: [1-9]\d* \(limit 0\) FAILED", out)
    if fault == "drop_in_engine":  # the log is whole: only the states show it
        assert "acknowledged_not_in_wal: 0 (limit 0) ok" in out


# -- the readers ---------------------------------------------------------------

TRACE = {
    "window_s": 10.0,
    "spans": {"ytpu.pack": 0.5, "ytpu.pack.lanes": 0.25, "ytpu.pack.rows": 0.1},
    "programs": {
        "apply_plan2": {"seconds": 0.004, "launches": 12.0},
        "apply_plan2_rows": {"seconds": 0.5, "launches": 4.0},
        "scatter_rows": {"seconds": 0.25, "launches": 4.0},
    },
}
COUNTERS = {
    "lane_links": 3_000_000, "row_links": 1_000_000,
    "lanes_dispatched": 4_000_000, "conflict_steps": 500_000,
    "offline_structs": 2_000_000, "offline_waves": 4, "units": 12,
    "cap": 131072, "chips": 1, "device_kind": "TPU v5 lite",
}
PARENT = {  # what the parent commit's program gives the same generator
    k: v for k, v in COUNTERS.items()
    if k not in ("lane_links", "row_links", "lanes_dispatched", "conflict_steps")
}


def test_lane_counter_readers():
    assert reader("lane_link_share").read(TRACE, COUNTERS) == pytest.approx(75.0)
    assert reader("lane_fill").read(TRACE, COUNTERS) == pytest.approx(75.0)
    assert reader("conflict_steps_a_struct").read(TRACE, COUNTERS) == (
        pytest.approx(0.25)
    )
    for name in ("lane_link_share", "lane_fill", "conflict_steps_a_struct"):
        # a program that keeps no such counter (the parent): left out
        assert reader(name).read(TRACE, PARENT) is None
    assert reader("lane_link_share").read(
        TRACE, {**COUNTERS, "lane_links": 0, "row_links": 0}
    ) is None
    assert reader("lane_fill").read(
        TRACE, {**COUNTERS, "lanes_dispatched": 0}
    ) is None
    assert reader("conflict_steps_a_struct").read(
        TRACE, {**COUNTERS, "offline_structs": 0}
    ) is None


def test_pack_lanes_share_reads_the_programs_own_span():
    from yjs_tpu.obs.trace import PACK_SPANS

    r = reader("pack_lanes_share")
    assert r.SPAN in PACK_SPANS and PACK_SPANS[r.SPAN] == "ytpu.pack"
    assert r.read(TRACE, COUNTERS) == pytest.approx(2.5)
    assert r.read({"spans": {"ytpu.pack": 1.0}, "window_s": 2.0}, {}) is None


def test_the_lanes_device_readers_leave_the_row_writers_out():
    r = reader("lanes_ms_a_wave")
    assert r.read(TRACE, COUNTERS) == pytest.approx(1.0)  # 4 ms over 4 waves
    rows_only = {"programs": {"apply_plan2_rows": TRACE["programs"]["apply_plan2_rows"]}}
    assert r.read(rows_only, COUNTERS) is None
    assert r.read(TRACE, {**COUNTERS, "offline_waves": 0}) is None
    roof = reader("lanes_roofline")
    from benchmarks import roofline

    needed = roofline.apply_plan2_bytes(3_000_000, 131072)
    peak = roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert roof.read(TRACE, COUNTERS) == pytest.approx(
        100.0 * needed / peak / 0.004
    )
    assert 0 < roof.read(TRACE, COUNTERS) < 100
    assert roof.read(rows_only, COUNTERS) is None
    assert roof.read(TRACE, PARENT) is None
    # a mesh's program name holds no apply_plan2: nothing, and no error
    assert roof.read({"programs": {"local_apply": {"seconds": 1.0}}}, COUNTERS) is None
