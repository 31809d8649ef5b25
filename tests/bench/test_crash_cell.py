"""Configuration ``yws-crash`` and its cell ``crash-recover``: the
deployment is ``yws-1chip`` killed without ``close()`` and made again by
``TpuProvider.recover``; the tails are the same in every seed; the plain
reader of a crashed log agrees with ``persistence.recovery`` on the
committed corpus and on a tail torn at every byte; the tiny cell is
``correct``, reports the metrics listed for it and stops being correct
under each fault control; two recoveries of copies of one log meet the
same programs; the six readers this cell adds."""

import collections
import copy
import json
import re
import shutil
import time
from pathlib import Path

import pytest

from benchmarks import deployment, faults, harness, plain_wal

ROOTS = (harness.HERE,)
MANIFEST = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
CELL = "crash-recover"
CORPUS = harness.ROOT / "tests" / "fixtures" / "wal"
# a seed whose fault control strikes the seam's second call after the
# window opens (faults.install draws 1..40; the predecessor's last two
# flushes make 12 calls of the journal's and the engine's seams)
EARLY = 2**31 + 12
SHARED = (
    "unspanned_share", "compact_share", "plan_share", "plan_cache_hit",
    "pack_share", "dispatch_share", "emit_share", "apply_roofline",
    "fence_share", "device_idle", "peak_hbm_gb", "compiles_in_window",
)
READERS = {
    "recover_construct_share": "ytpu.recover.construct",
    "recover_read_share": "ytpu.recover.read",
    "recover_validate_share": "ytpu.recover.validate",
    "recover_queue_share": "ytpu.recover.queue",
    "recover_other_share": "ytpu.recover",
}


def recover_generator():
    return harness.load_module("generators", "recover", ROOTS)


def reader(name):
    return harness.load_module("layer_metrics", name, ROOTS)


@pytest.fixture(scope="module")
def crash_manifest(tiny_manifest):
    """The tiny manifest with ``tiny-crash`` standing in for
    ``crash-recover``."""
    m = copy.deepcopy(tiny_manifest)
    m["workloads"].append({
        "name": "tiny-crash", "config": "tiny-1chip",
        "traffic": "tiny-crash-recover", "chips": 1, "why": "tests",
    })
    real = {x["name"]: x for x in MANIFEST["end_to_end"] + MANIFEST["per_layer"]}
    for metric in m["end_to_end"] + m["per_layer"]:
        if CELL in real[metric["name"]].get("workloads", ()):
            metric["workloads"] = metric["workloads"] + ["tiny-crash"]
    return m


@pytest.fixture
def run(run_tiny, crash_manifest):
    def go(**kw):
        return run_tiny("tiny-crash", manifest=crash_manifest, **kw)

    return go


# -- the configuration and the draw, no device ------------------------------


def test_the_configuration_is_yws_1chip_killed_and_recovered():
    one = harness.load_data("configs", "yws-1chip", ROOTS)
    cfg = harness.load_data("configs", "yws-crash", ROOTS)
    for key in ("chips", "mesh_devices", "slots", "rooms", "provider"):
        assert cfg[key] == one[key]
    assert cfg["guarantees"][:4] == one["guarantees"]
    assert len(cfg["guarantees"]) == 8
    for word, g in zip(
        ("whole in the log", "every room that was not released",
         "torn_truncations 1", "journals onward"),
        cfg["guarantees"][4:],
    ):
        assert word in g
    assert cfg["reduced"] == ["tail_max"]
    assert (cfg["tail_max"], cfg["tail_max_at_the_source"]) == (249, 500)
    assert "PREFERRED_TRIM_SIZE" in cfg["source"] and len(cfg["source"]) <= 200
    assert cfg["crash"] == {
        "close": False, "checkpoint_file": False, "torn_last_record": True,
        "recover": {"n_docs": 4096, "backend": "device"},
    }
    assert any("checkpoint" in a for a in cfg["assumed"])
    entry = {c["name"]: c for c in MANIFEST["configs"]}["yws-crash"]
    assert entry["file"] == "benchmarks/configs/yws-crash.json"
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    cell = {w["name"]: w for w in MANIFEST["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "yws-crash", "crash-recover", 1
    )
    traffic = harness.load_data("traffic", "crash-recover", ROOTS)
    flood = harness.load_data("traffic", "flood", ROOTS)
    assert traffic["generator"] == "recover" and traffic["trace_units"] == 2
    for key in ("solo_rooms", "duet_rooms", "hot_traces", "hot_storm_rooms",
                "typing_run", "erasing_run", "jump_every_runs"):
        assert traffic[key] == flood[key]
    assert traffic["tail_max"] == cfg["tail_max"]
    assert traffic["updates_a_flush"] == 80 and traffic["final_units"] == 2
    assert traffic["others_compared"] == 256
    assert traffic["end_to_end"] == {"bulk_rate": "work_per_timed_second"}
    assert traffic["work_unit"] == "elements"


def test_the_cell_is_listed_where_the_issue_says():
    listed = {
        m["name"] for m in MANIFEST["per_layer"] if CELL in m.get("workloads", ())
    }
    new = set(READERS) | {"replay_mb_a_recovery"}
    assert listed == {f"{n}.bulk" for n in set(SHARED) | new}
    for m in MANIFEST["per_layer"]:
        if m["name"].split(".")[0] in new:
            assert (m["moves"], m["workloads"], m["layer"]) == (
                "bulk_rate", [CELL], "recovery"
            )
    bulk = {m["name"]: m for m in MANIFEST["end_to_end"]}["bulk_rate"]
    assert bulk["workloads"][-1] == CELL and bulk["bound"] == 0.08


def manifest_less(cells: tuple[str, ...]) -> dict:
    """BENCHMARK.json as it stood before ``cells`` (the last of its
    workloads) were appended: their configurations, their names in the
    metrics' lists and the metrics that only they report taken off the
    ends, and from nowhere else."""
    m = copy.deepcopy(MANIFEST)
    assert tuple(w["name"] for w in m["workloads"][-len(cells):]) == cells
    gone = m["workloads"][-len(cells):]
    del m["workloads"][-len(cells):]
    configs = {w["config"] for w in gone} - {w["config"] for w in m["workloads"]}
    while m["configs"][-1]["name"] in configs:
        m["configs"].pop()
    assert not configs & {c["name"] for c in m["configs"]}
    while set(m["per_layer"][-1].get("workloads", ())) - set(cells) == set():
        m["per_layer"].pop()
    for metric in m["end_to_end"] + m["per_layer"]:
        listed = metric.get("workloads")
        if listed is None:
            continue
        while listed[-1] in cells:
            listed.pop()
        assert not set(listed) & set(cells)
    return m


@pytest.mark.parametrize("module, test, later", [
    ("test_longtail_cell", "test_the_cell_is_listed_where_the_issue_says",
     ("prosemirror-flood", CELL)),
    ("test_prosemirror_cell",
     "test_the_configuration_is_yws_1chip_with_typed_rooms", (CELL,)),
    ("test_prosemirror_cell", "test_the_cell_is_listed_where_the_issue_says",
     (CELL,)),
])
def test_an_earlier_cells_pin_holds_less_the_later_cells(
    module, test, later, monkeypatch
):
    """The earlier cells' tests that pin the manifest's tail
    (``tests/conftest.py`` ``PINNED_TO_AN_EARLIER_TAIL``) pass against
    the manifest with the later cells taken off its ends: this cell was
    appended, and nothing was put first or in the middle."""
    import importlib

    mod = importlib.import_module(module)
    monkeypatch.setattr(mod, "MANIFEST", manifest_less(later))
    getattr(mod, test)()


class PaperCell:
    """What the generator sees of a run, with no provider behind it."""

    def __init__(self, cfg, seed):
        self.cfg, self.seed = cfg, seed
        self.plan = deployment.room_plan(cfg, seed)
        self.prov, self.wal_dir = None, Path("nowhere/wal")
        self.clock, self.log = time.perf_counter, lambda msg: None


def test_the_tails_are_the_same_in_every_seed():
    cfg = harness.load_data("configs", "yws-crash", ROOTS)
    traffic = harness.load_data("traffic", "crash-recover", ROOTS)
    mod = recover_generator()
    lengths = [mod.tail_length(traffic, i) for i in range(1280)]
    spread = collections.Counter(lengths)
    # uniform over 0..249 by the room's index: 5 or 6 rooms a length
    assert sorted(spread) == list(range(250))
    assert set(spread.values()) == {5, 6}
    assert 159_000 < sum(lengths) < 160_000
    by_seed = []
    for seed in (7, 2**31 + 12345):
        gen = mod.Generator(traffic, PaperCell(cfg, seed))
        assert len(gen.hot_specs) == 1280
        assert [n for _r, n in gen.hot_specs] == [2] * 256 + [1] * 1024
        assert gen.keystroke_room.kind == "distinct"
        assert gen.keystroke_room.guid not in {r.guid for r, _n in gen.hot_specs}
        # the document a length falls to, and how many type it
        by_seed.append([
            (room.kind, room.trace, n, mod.tail_length(traffic, i))
            for i, (room, n) in enumerate(gen.hot_specs)
        ])
    assert by_seed[0] == by_seed[1]


# -- the plain reader against persistence.recovery ---------------------------


def _recovered(tmp_path, case_dir):
    """What ``TpuProvider.recover`` makes of a copy of ``case_dir``."""
    from yjs_tpu.provider import TpuProvider

    work = tmp_path / "copy"
    shutil.copytree(case_dir, work)
    prov = TpuProvider.recover(work, backend="cpu")
    return prov, prov.last_recovery


@pytest.mark.parametrize("case", ["clean", "torn_tail_00"])
def test_plain_reader_agrees_with_recovery_on_the_corpus(case, tmp_path):
    """The directories of the committed corpus that hold no checkpoint
    file and no mid-log damage: what a killed process leaves."""
    manifest = json.loads((CORPUS / "manifest.json").read_text())
    (want,) = [c["expected"] for c in manifest["cases"] if c["dir"] == case]
    log = plain_wal.read_crashed(CORPUS / case)
    prov, stats = _recovered(tmp_path, CORPUS / case)
    assert log.records == stats["records_applied"] + stats["released"]
    assert (log.torn_at is not None) == bool(stats["torn_truncations"])
    assert log.files == stats["segments"]
    if "bytes_read" in stats:
        assert log.bytes == stats["bytes_read"]
    for guid, payloads in log.rooms.items():
        sv, text = plain_wal.replay(payloads)
        assert text == want["texts"][guid] == prov.text(guid)
        assert sv == prov.state_vector(guid)
    assert set(log.rooms) == set(want["texts"])
    prov.close(checkpoint=False)


@pytest.mark.parametrize("case", sorted(
    p.name for p in CORPUS.iterdir()
    if p.is_dir() and p.name not in ("clean", "torn_tail_00")
))
def test_plain_reader_refuses_what_no_kill_leaves(case):
    """A checkpoint file, or damage anywhere but the last record."""
    with pytest.raises(plain_wal.DamagedLog):
        plain_wal.read_crashed(CORPUS / case)


def test_plain_reader_cuts_a_torn_tail_at_every_byte(tmp_path):
    """Two segments; the last record of the last cut after each of its
    bytes: the reader stops at the record's first byte, as
    ``iter_file_events`` does, and loses nothing before it."""
    from yjs_tpu.persistence.records import SEG_HEADER, encode_record
    from yjs_tpu.persistence.recovery import iter_file_events

    payloads = [bytes([k]) * (3 + 5 * k) for k in range(6)]
    whole = [plain_wal.record(plain_wal.KIND_UPDATE, f"room-{k % 2}", p)
             for k, p in enumerate(payloads)]
    # the benchmark's writer writes what the program's does
    assert whole == [
        encode_record(1, f"room-{k % 2}", p) for k, p in enumerate(payloads)
    ]
    d = tmp_path / "wal"
    d.mkdir()
    (d / "wal-00000000.log").write_bytes(SEG_HEADER + b"".join(whole[:3]))
    last = d / "wal-00000001.log"
    kept = SEG_HEADER + b"".join(whole[3:5])
    for cut in range(len(whole[5]) + 1):
        last.write_bytes(kept + whole[5][:cut])
        log = plain_wal.read_crashed(d)
        events = list(iter_file_events(last, final=True))
        if cut == len(whole[5]):
            assert log.torn_at is None and log.records == 6
            assert [e[0] for e in events] == ["record"] * 3
        else:
            assert log.records == 5
            torn = [e for e in events if e[0] == "torn"]
            if cut == 0:  # the file ends with a whole record
                assert log.torn_at is None and not torn
            else:
                assert log.torn_at == (last, len(kept)) == (last, torn[0][1])
            assert log.rooms == {
                "room-0": [payloads[0], payloads[2], payloads[4]],
                "room-1": [payloads[1], payloads[3]],
            }
    # the same damage in a segment that is not the last is no kill's
    (d / "wal-00000000.log").write_bytes(
        SEG_HEADER + b"".join(whole[:2]) + whole[2][:7]
    )
    with pytest.raises(plain_wal.DamagedLog):
        plain_wal.read_crashed(d)
    # a release ends a room
    last.write_bytes(kept + plain_wal.record(plain_wal.KIND_RELEASE, "room-1", b""))
    (d / "wal-00000000.log").write_bytes(SEG_HEADER + b"".join(whole[:3]))
    assert set(plain_wal.read_crashed(d).rooms) == {"room-0"}


# -- the tiny cell on this CPU -------------------------------------------------


def test_the_tiny_cell_is_correct_and_every_successor_is_held(run, capsys):
    r = run()
    out = capsys.readouterr().out
    assert r["correct"] is True and r["failed"] == 0
    assert set(r["metrics"]) == {"bulk_rate", "setup_s"}
    checks = re.findall(r"check (\w+): (\d+) \(limit 0\) (ok|FAILED)", out)
    assert len(checks) >= 26 and all(v == "0" and s == "ok" for _n, v, s in checks)
    assert {
        "acknowledged_not_whole_in_crashed_copy",
        "predecessor_state_vector_differs_from_typists",
        "successor_state_vector_differs_from_predecessor",
        "successor_state_vector_differs_from_plain_reference",
        "survivor_text_differs_from_plain_reference",
        "recoveries_not_as_guaranteed",
        "dropped_successors_still_on_the_device",
        "compiles_in_window",
    } <= {n for n, _v, _s in checks}
    # 48 whole-room records and the tail, 12 of it held back; the torn
    # record cut; every recovery of the window read the same log
    tail, held = map(int, re.search(
        r"a tail of (\d+) keystrokes .* (\d+) held back", out
    ).groups())
    n, records, rooms, elements = map(int, re.search(
        r"(\d+) recoveries in the window, each of (\d+) records whole .* "
        r"(\d+) rooms, (\d+) elements", out
    ).groups())
    assert (records, rooms, held) == (48 + tail, 48, 12) and n >= 1
    assert f"work {elements * n} elements" in out
    assert re.search(r"rehearsal recovery 1: 0 programs first met", out)
    assert f"records_applied {records} torn_truncations 1" in out


def test_the_traced_tiny_cell_reports_the_listed_metrics(run):
    r = run(trace=True)
    assert r["correct"] is True
    got = set(r["metrics"])
    listed = {f"{n}.bulk" for n in SHARED + tuple(READERS)} | {
        "replay_mb_a_recovery.bulk"
    }
    assert got <= listed
    # what a CPU cannot give: device memory, a device trace's kernels
    assert listed - got <= {"peak_hbm_gb.bulk", "apply_roofline.bulk"}
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert m["compiles_in_window.bulk"] == 0
    assert 0 < m["replay_mb_a_recovery.bulk"] < 2
    shares = [m[f"{n}.bulk"] for n in READERS]
    assert all(s > 0 for s in shares) and sum(shares) < 100
    # a recovery plans every room cold: a new process has no plan cache
    assert m["plan_cache_hit.bulk"] < 100


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_fault_turns_the_tiny_cell_incorrect(run, fault, capsys):
    r = run(fault=fault, seed=EARLY)
    assert r["correct"] is False and r["failed"] >= 1
    out = capsys.readouterr().out
    assert "FAILED" in out
    if fault in ("drop_update", "skip_wal"):
        assert re.search(
            r"acknowledged_not_whole_in_crashed_copy: [1-9]\d* \(limit 0\) FAILED",
            out,
        )
    if fault == "drop_in_engine":  # the log is whole: only the states show it
        assert "acknowledged_not_whole_in_crashed_copy: 0 (limit 0) ok" in out
        assert re.search(
            r"predecessor_state_vector_differs_from_typists: [1-9]", out
        )


def test_two_recoveries_of_copies_of_one_log_meet_the_same_programs(tmp_path):
    """The second recovery of a copy of a log compiles nothing, and plans
    every room as the first did: what the cell's rehearsal rests on."""
    from yjs_tpu.ops import plan_cache
    from yjs_tpu.provider import TpuProvider

    cfg = harness.load_data("configs", "tiny-1chip", (Path(__file__).parent / "cells",))
    plan = deployment.room_plan(cfg, 11)[:12]
    compiles = deployment.CompileCounter()
    first = TpuProvider(16, backend="device", wal_dir=str(tmp_path / "wal"))
    for room in plan:
        assert first.receive_update(room.guid, room.base)
    first.flush()
    seen = []
    for k in range(3):
        copy_k = tmp_path / f"copy-{k}"
        shutil.copytree(tmp_path / "wal", copy_k)
        plan_cache.reset_cache()
        before = compiles.programs
        prov = TpuProvider.recover(str(copy_k), n_docs=16, backend="device")
        deployment.fence(prov)
        m = prov.engine.last_flush_metrics
        seen.append((
            compiles.programs - before, m["n_sched_entries"],
            m["plan_cache_misses"], prov.last_recovery["records_applied"],
        ))
        prov.close(checkpoint=False)
    first.close(checkpoint=False)
    assert [s[0] for s in seen[1:]] == [0, 0]
    assert len({s[1:] for s in seen}) == 1 and seen[0][3] == 12


# -- the readers ---------------------------------------------------------------

TRACE = {
    "window_s": 10.0,
    "spans": {
        "ytpu.recover.construct": 0.5, "ytpu.recover.read": 0.25,
        "ytpu.recover.validate": 4.0, "ytpu.recover.queue": 1.0,
        "ytpu.recover": 0.125, "ytpu.plan": 2.0,
    },
}
WANT = {
    "recover_construct_share": 5.0, "recover_read_share": 2.5,
    "recover_validate_share": 40.0, "recover_queue_share": 10.0,
    "recover_other_share": 1.25,
}


@pytest.mark.parametrize("name", sorted(READERS))
def test_span_reader_reads_its_span_of_the_programs_own(name):
    from yjs_tpu.obs.trace import RECOVER_SPANS

    r = reader(name)
    assert r.SPANS == (READERS[name],) and set(r.SPANS) <= set(RECOVER_SPANS)
    assert r.read(TRACE, {}) == pytest.approx(WANT[name])
    # a program that opens no such span (the parent commit): left out
    assert r.read({"spans": {"ytpu.plan": 1.0}, "window_s": 2.0}, {}) is None


def test_the_readers_cover_every_recovery_span():
    from yjs_tpu.obs.trace import RECOVER_SPANS

    assert set(READERS.values()) == set(RECOVER_SPANS)


def test_replay_mb_reader():
    r = reader("replay_mb_a_recovery")
    counters = {"recoveries": 3, "recover_bytes_read": 405_000_000}
    assert r.read(TRACE, counters) == pytest.approx(135.0)
    # the parent keeps no bytes_read: the generator sums nothing
    assert r.read(TRACE, {"recoveries": 3}) is None
    assert r.read(TRACE, {}) is None
