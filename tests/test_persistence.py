"""Durability suite (ISSUE 3): WAL record codec, rotation, compaction,
and crash-recovery semantics — torn tails truncate, mid-log corruption
dead-letters, replay is idempotent, the DLQ survives a checkpoint.

Recovery property under test throughout: the CRDT merge contract makes
log replay safe — updates commute and are idempotent, so any prefix of
snapshot+tail replay, applied any number of times, converges to the
state the journaled traffic describes.
"""

from __future__ import annotations

import json
import random
import shutil
from pathlib import Path

import pytest

import yjs_tpu as Y
from yjs_tpu.persistence import (
    KIND_SNAPSHOT,
    KIND_UPDATE,
    SEG_HEADER,
    WalConfig,
    WriteAheadLog,
    encode_record,
    list_checkpoints,
    list_segments,
    replay_wal,
    try_decode_at,
)
from yjs_tpu.provider import ProviderFullError, TpuProvider
from yjs_tpu.resilience import DiskFaultInjector

pytestmark = pytest.mark.durability

FIXTURES = Path(__file__).parent / "fixtures" / "wal"
SMALL = WalConfig(segment_bytes=256, fsync="never")


def phased_streams(seed: int, rooms=("alpha", "beta"), phases=(30, 12)):
    """Per-room per-phase incremental update streams from CONTINUING
    client sessions (phase 2 extends phase 1's causal history)."""
    out = {}
    for j, room in enumerate(rooms):
        gen = random.Random(seed + j)
        docs, updates = [], []
        for k in range(3):
            d = Y.Doc(gc=False)
            d.client_id = 1000 * (j + 1) + k
            d.on("update", lambda u, origin, doc: updates.append(bytes(u)))
            docs.append(d)
        room_phases = []
        for n in phases:
            for _ in range(n):
                d = gen.choice(docs)
                t = d.get_text("text")
                if len(t) and gen.random() < 0.3:
                    t.delete(gen.randrange(len(t)), 1)
                else:
                    t.insert(gen.randrange(len(t) + 1), gen.choice("abcdef "))
            room_phases.append(list(updates))
            updates.clear()
        out[room] = room_phases
    return out


def canonical(prov: TpuProvider, guid: str) -> bytes:
    """Canonical full-state bytes: merge_updates normalizes struct
    splits, so equal stores yield IDENTICAL bytes regardless of the
    order their history arrived in."""
    return Y.merge_updates([prov.encode_state_as_update(guid)])


# -- record codec --------------------------------------------------------


def test_record_roundtrip_and_crc():
    rec = encode_record(KIND_UPDATE, "room/x", b"payload bytes", v2=True)
    status, decoded, end = try_decode_at(rec, 0)
    assert status == "ok" and end == len(rec)
    assert decoded.kind == KIND_UPDATE
    assert decoded.guid == "room/x"
    assert decoded.payload == b"payload bytes"
    assert decoded.v2 is True
    # every single-byte damage is caught (CRC-32 covers header + body)
    for i in range(len(rec)):
        bad = bytearray(rec)
        bad[i] ^= 0x40
        status, _v, _e = try_decode_at(bytes(bad), 0)
        assert status != "ok" or bytes(bad) == rec

    short, _v, _e = try_decode_at(rec[: len(rec) - 3], 0)
    assert short == "short"


# -- journal + recover ---------------------------------------------------


def test_recover_matches_uninterrupted_reference(tmp_path):
    streams = phased_streams(seed=11)
    ref = TpuProvider(2, backend="cpu")
    victim = TpuProvider(2, backend="cpu", wal_dir=tmp_path, wal_config=SMALL)
    for room, (p1, p2) in streams.items():
        for u in p1 + p2:
            ref.receive_update(room, u)
            victim.receive_update(room, u)
    victim.flush()
    assert len(list_segments(tmp_path)) > 1  # rotation happened
    victim.wal.abandon()  # crash: no orderly close

    rec = TpuProvider.recover(tmp_path, backend="cpu")
    assert rec.last_recovery["outcome"] == "clean"
    for room in streams:
        assert rec.text(room) == ref.text(room)
        assert rec.state_vector(room) == ref.state_vector(room)
        assert canonical(rec, room) == canonical(ref, room)


def test_recover_integrates_without_new_traffic_on_auto(tmp_path):
    """Replay enqueues below the provider's dirty-tracking seam; on a
    device-backed engine the final flush must still run — the recovered
    state has to be readable IMMEDIATELY, not after the next unrelated
    update happens to dirty the provider (regression: replay left the
    records queued and every read path no-op'd the flush)."""
    streams = phased_streams(seed=77)
    prov = TpuProvider(2, wal_dir=tmp_path, wal_config=SMALL)
    for room, (p1, p2) in streams.items():
        for u in p1 + p2:
            prov.receive_update(room, u)
    prov.flush()
    texts = {room: prov.text(room) for room in streams}
    prov.close()  # orderly: the dir is checkpoint-only (pure snapshots)
    assert list_segments(tmp_path) == []

    rec = TpuProvider.recover(tmp_path)  # default (auto) backend
    assert rec.last_recovery["snapshots_applied"] == 2
    for room in streams:
        assert rec.text(room) == texts[room]


def test_checkpoint_compacts_and_recovers(tmp_path):
    streams = phased_streams(seed=22)
    prov = TpuProvider(2, backend="cpu", wal_dir=tmp_path, wal_config=SMALL)
    for room, (p1, _p2) in streams.items():
        for u in p1:
            prov.receive_update(room, u)
    before = len(list_segments(tmp_path))
    stats = prov.checkpoint()
    assert stats["docs"] == 2
    assert stats["segments_removed"] == before
    assert len(list_checkpoints(tmp_path)) == 1
    # post-checkpoint traffic lands in fresh tail segments
    for room, (_p1, p2) in streams.items():
        for u in p2:
            prov.receive_update(room, u)
    prov.flush()
    texts = {room: prov.text(room) for room in streams}
    prov.wal.abandon()

    rec = TpuProvider.recover(tmp_path, backend="cpu")
    assert rec.last_recovery["snapshots_applied"] == 2
    for room in streams:
        assert rec.text(room) == texts[room]

    # a second checkpoint supersedes the first
    rec.checkpoint()
    assert len(list_checkpoints(tmp_path)) == 1


def test_close_writes_final_checkpoint(tmp_path):
    streams = phased_streams(seed=33, phases=(20,))
    prov = TpuProvider(2, backend="cpu", wal_dir=tmp_path, wal_config=SMALL)
    for room, (p1,) in streams.items():
        for u in p1:
            prov.receive_update(room, u)
    texts = {room: prov.text(room) for room in streams}
    prov.close()
    assert len(list_checkpoints(tmp_path)) == 1
    assert list_segments(tmp_path) == []  # everything folded in
    with pytest.raises(RuntimeError):
        prov.wal.append(KIND_UPDATE, "alpha", b"x")
    rec = TpuProvider.recover(tmp_path, backend="cpu")
    for room in streams:
        assert rec.text(room) == texts[room]


def test_torn_tail_truncated_and_reconverges(tmp_path, rng):
    streams = phased_streams(seed=44)
    ref = TpuProvider(2, backend="cpu")
    victim = TpuProvider(2, backend="cpu", wal_dir=tmp_path, wal_config=SMALL)
    for room, (p1, _p2) in streams.items():
        for u in p1:
            ref.receive_update(room, u)
            victim.receive_update(room, u)
    victim.wal.abandon()
    inj = DiskFaultInjector(seed=rng.randrange(1 << 30))
    _idx, last = list_segments(tmp_path)[-1]
    assert inj.tear(last) > 0
    size_after_tear = last.stat().st_size

    rec = TpuProvider.recover(tmp_path, backend="cpu")
    assert rec.last_recovery["torn_truncations"] >= 1
    assert rec.last_recovery["outcome"] == "torn_tail"
    # recovery TRUNCATED the torn tail in place: the file now ends at
    # the last intact record
    assert last.stat().st_size <= size_after_tear
    # the lost suffix is bounded traffic; a sync round re-delivers it
    for room in streams:
        diff = ref.encode_state_as_update(
            room, Y.encode_state_vector_from_update(canonical(rec, room))
        )
        rec.receive_update(room, diff)
        assert rec.text(room) == ref.text(room)
        assert canonical(rec, room) == canonical(ref, room)
    # and a re-recovery of the truncated dir is clean
    rec.wal.abandon()
    rec2 = TpuProvider.recover(tmp_path, backend="cpu")
    assert rec2.last_recovery["torn_truncations"] == 0


def test_midlog_corruption_dead_letters_not_aborts(tmp_path):
    streams = phased_streams(seed=55)
    prov = TpuProvider(2, backend="cpu", wal_dir=tmp_path, wal_config=SMALL)
    for room, (p1, p2) in streams.items():
        for u in p1 + p2:
            prov.receive_update(room, u)
    prov.flush()
    prov.wal.abandon()
    segs = list_segments(tmp_path)
    assert len(segs) > 2
    inj = DiskFaultInjector(seed=5)
    off = inj.bitflip(segs[0][1], lo=len(SEG_HEADER))
    assert off >= len(SEG_HEADER)

    rec = TpuProvider.recover(tmp_path, backend="cpu")
    lr = rec.last_recovery
    assert lr["outcome"] == "corrupt_records"
    assert lr["corrupt_records"] >= 1
    # the damaged record went to the DLQ with the wal-corrupt reason...
    reasons = [d["reason"] for d in rec.dead_letters()]
    assert any(r.startswith("wal-corrupt") for r in reasons)
    # ...and everything after it still applied (one record lost, the
    # rest of the log replayed: strictly more than the damaged segment)
    assert lr["records_applied"] > 0


def test_recovery_idempotent_same_wal_twice(tmp_path):
    """Property: replaying the same WAL into the same provider twice
    (or recovering the same directory twice) is a no-op the second
    time — per doc AND per batch, SV and canonical bytes equal."""
    streams = phased_streams(seed=66)
    prov = TpuProvider(2, backend="cpu", wal_dir=tmp_path, wal_config=SMALL)
    for room, (p1, _p2) in streams.items():
        for u in p1:
            prov.receive_update(room, u)
    prov.checkpoint()  # snapshot + tail both present
    for room, (_p1, p2) in streams.items():
        for u in p2:
            prov.receive_update(room, u)
    prov.flush()
    prov.wal.abandon()

    once = TpuProvider.recover(tmp_path, backend="cpu")
    svs1 = {room: once.state_vector(room) for room in streams}
    exports1 = {room: canonical(once, room) for room in streams}
    # replay the SAME directory into the already-recovered provider
    replay_wal(once, tmp_path, exclude_from=once.wal.first_index)
    for room in streams:
        assert once.state_vector(room) == svs1[room]
        assert canonical(once, room) == exports1[room]
    # batched export path agrees with the per-doc path
    docs = sorted(once._guid_of)
    batch = once.engine.encode_states_batched(docs)
    for i, u in zip(docs, batch):
        room = once._guid_of[i]
        assert Y.merge_updates([u]) == exports1[room]

    # an independent second recovery converges to the same state
    twice = TpuProvider.recover(tmp_path, backend="cpu")
    for room in streams:
        assert twice.state_vector(room) == svs1[room]
        assert canonical(twice, room) == exports1[room]


def test_recovery_idempotent_prefix_then_full(tmp_path):
    """Property: replaying a PREFIX of the log and then the full log
    equals replaying the full log once (snapshot/tail overlap is the
    real-world case: a checkpoint covers traffic the tail repeats)."""
    streams = phased_streams(seed=77)
    prov = TpuProvider(2, backend="cpu", wal_dir=tmp_path, wal_config=SMALL)
    for room, (p1, p2) in streams.items():
        for u in p1 + p2:
            prov.receive_update(room, u)
    prov.flush()
    prov.wal.abandon()
    segs = list_segments(tmp_path)
    assert len(segs) >= 2
    cut = segs[len(segs) // 2][0]

    full = TpuProvider(2, backend="cpu")
    replay_wal(full, tmp_path, truncate_torn=False)

    prefixed = TpuProvider(2, backend="cpu")
    replay_wal(prefixed, tmp_path, exclude_from=cut, truncate_torn=False)
    replay_wal(prefixed, tmp_path, truncate_torn=False)

    for room in streams:
        assert prefixed.state_vector(room) == full.state_vector(room)
        assert canonical(prefixed, room) == canonical(full, room)


# -- DLQ persistence -----------------------------------------------------


def test_dlq_survives_checkpoint_and_replays(tmp_path):
    streams = phased_streams(seed=88, phases=(20,))
    prov = TpuProvider(2, backend="cpu", wal_dir=tmp_path, wal_config=SMALL)
    (good,) = streams["alpha"]
    held_back = good[-1]
    for u in good[:-1]:
        prov.receive_update("alpha", u)
    # dead-letter a VALID update (simulates an operator-fixable refusal:
    # the bytes themselves replay fine once re-admitted)
    prov.engine._dead_letter(prov.doc_id("alpha"), held_back, False, "test-hold")
    prov.checkpoint()
    prov.wal.abandon()

    rec = TpuProvider.recover(tmp_path, backend="cpu")
    assert rec.last_recovery["dlq_restored"] == 1
    letters = rec.dead_letters("alpha")
    assert [d["reason"] for d in letters] == ["test-hold"]
    res = rec.replay_dead_letters("alpha")
    assert res["replayed"] == 1
    oracle = Y.Doc(gc=False)
    for u in good:
        Y.apply_update(oracle, u)
    assert rec.text("alpha") == str(oracle.get_text("text"))


# -- slot lifecycle ------------------------------------------------------


def test_recovery_overflow_dead_letters_instead_of_dropping(tmp_path):
    # regression (ISSUE 6 satellite): replay used to DISCARD a doc's
    # records silently when the recovered provider was smaller than the
    # journaled fleet — durably-written state vanished.  Overflowed
    # records must ride the DLQ with their guid in the reason (so an
    # operator or the fleet rebalancer can re-route them) and count on
    # ytpu_wal_recovery_overflow_total.
    streams = phased_streams(
        seed=33, rooms=("alpha", "beta", "gamma"), phases=(10,)
    )
    prov = TpuProvider(3, backend="cpu", wal_dir=tmp_path, wal_config=SMALL)
    for room, (p1,) in streams.items():
        for u in p1:
            prov.receive_update(room, u)
    prov.flush()
    prov.wal.abandon()  # crash

    rec = TpuProvider.recover(tmp_path, n_docs=2, backend="cpu")
    stats = rec.last_recovery
    assert stats["overflowed"] >= 1
    admitted = [r for r in streams if rec.has_doc(r)]
    assert len(admitted) == 2  # first-come admission filled both slots
    (evicted,) = set(streams) - set(admitted)
    letters = [
        e for e in rec.dead_letters()
        if e["reason"].startswith("wal-overflow:")
    ]
    assert len(letters) == stats["overflowed"]
    assert all(repr(evicted) in e["reason"] for e in letters)
    # the new counter moved in lockstep with the stats
    overflow = rec.engine.obs.registry.get(
        "ytpu_wal_recovery_overflow_total"
    )
    assert overflow.value == stats["overflowed"]
    assert stats["dead_lettered"] >= stats["overflowed"]


def test_full_release_reuse_and_eviction_counter(tmp_path):
    streams = phased_streams(seed=99, phases=(15,))
    prov = TpuProvider(2, backend="cpu", wal_dir=tmp_path, wal_config=SMALL)
    for room, (p1,) in streams.items():
        for u in p1:
            prov.receive_update(room, u)
    with pytest.raises(ProviderFullError, match="provider is full"):
        prov.doc_id("gamma")
    # the typed error still satisfies legacy except ValueError handlers
    with pytest.raises(ValueError):
        prov.doc_id("gamma")

    slot = prov.doc_id("beta")
    final = prov.release_doc("beta")
    assert prov._wal_metrics is not None
    assert prov.engine.obs.registry.counter(
        "ytpu_provider_docs_evicted_total"
    ).value == 1
    # the final snapshot is the room's complete state
    d = Y.Doc(gc=False)
    Y.apply_update(d, final)
    oracle = Y.Doc(gc=False)
    for u in streams["beta"][0]:
        Y.apply_update(oracle, u)
    assert str(d.get_text("text")) == str(oracle.get_text("text"))
    # the slot is reusable and starts empty
    assert prov.doc_id("gamma") == slot
    assert prov.text("gamma") == ""
    prov.receive_update("gamma", streams["beta"][0][0])
    prov.flush()
    prov.wal.abandon()

    # recovery honors the release record: beta is NOT resurrected into
    # a slot (its archived snapshot is in the log, deliberately parked)
    rec = TpuProvider.recover(tmp_path, n_docs=2, backend="cpu")
    assert rec.last_recovery["released"] == 1
    assert "beta" not in rec._guids
    assert sorted(rec._guids) == ["alpha", "gamma"]


def _wal_records(path):
    from yjs_tpu.persistence.recovery import iter_file_events

    segs = list_segments(path)
    return [
        (ev[1].kind, ev[1].guid, ev[1].payload)
        for j, (_i, p) in enumerate(segs)
        for ev in iter_file_events(p, final=j == len(segs) - 1)
        if ev[0] == "record"
    ]


@pytest.mark.parametrize("mesh", [False, True], ids=["one_device", "cpu_mesh"])
def test_release_of_a_device_room_journals_what_table_copies_did(
    tmp_path, mesh
):
    """A release on the device path (rows blanked by one donated
    program) leaves the journal a release by whole-table copies left —
    the reference kept in test_tpu_engine.py — record for record: the
    KIND_RELEASE with the final state, then the KIND_DLQ that keeps the
    slot's dead letters; and recovery honours both."""
    from test_tpu_engine import _reset_by_table_copies
    from yjs_tpu.persistence import KIND_DLQ, KIND_RELEASE

    if mesh:
        from yjs_tpu.parallel import doc_mesh

        mesh = doc_mesh(4, backend="cpu")
    streams = phased_streams(seed=27, rooms=("alpha", "beta", "gamma"))
    provs = [
        TpuProvider(4, mesh=mesh or None, wal_dir=tmp_path / name,
                    wal_config=SMALL)
        for name in ("rows", "copies")
    ]
    _reset_by_table_copies(provs[1].engine)
    finals = []
    for prov in provs:
        for room, (p1, _p2) in streams.items():
            for u in p1:
                prov.receive_update(room, u)
        prov.flush()
        assert prov.engine._right is not None and not prov.engine.fallback
        # a poisoned update: beta rolls back onto the CPU core, its
        # device rows stay behind until the release blanks them
        prov.receive_update("beta", b"\x01\xff\xff\xff")
        prov.flush()
        finals.append((prov.release_doc("beta"), prov.release_doc("gamma")))
        for u in streams["alpha"][1]:
            prov.receive_update("alpha", u)
        # the slot's next tenant, journaled after the release
        prov.receive_update("delta", streams["beta"][0][0])
        prov.flush()
        prov.wal.abandon()
    assert finals[0] == finals[1]
    got, want = (_wal_records(tmp_path / n) for n in ("rows", "copies"))
    assert got == want
    released = [r for r in got if r[0] == KIND_RELEASE]
    assert [r[1:] for r in released] == [
        ("beta", finals[0][0]), ("gamma", finals[0][1])
    ]
    released = released[0]
    # the poisoned bytes ride the DLQ record that follows the release
    after = got[got.index(released) + 1]
    assert after[0] == KIND_DLQ and b"evicted 'beta'" in after[2]

    rec = TpuProvider.recover(tmp_path / "rows", n_docs=4, mesh=mesh or None)
    assert rec.last_recovery["released"] == 2
    assert sorted(rec._guids) == ["alpha", "delta"]
    assert any("'beta'" in e["reason"] for e in rec.dead_letters())
    oracle = Y.Doc(gc=False)
    Y.apply_update(oracle, streams["beta"][0][0])
    assert rec.text("delta") == str(oracle.get_text("text"))
    oracle = Y.Doc(gc=False)
    for u in streams["alpha"][0] + streams["alpha"][1]:
        Y.apply_update(oracle, u)
    assert rec.text("alpha") == str(oracle.get_text("text"))
    gamma = Y.Doc(gc=False)
    for u in streams["gamma"][0]:
        Y.apply_update(gamma, u)
    assert Y.merge_updates([finals[0][1]]) == Y.merge_updates(
        [Y.encode_state_as_update(gamma)]
    )


def test_release_unknown_room_raises():
    prov = TpuProvider(1, backend="cpu")
    with pytest.raises(KeyError):
        prov.release_doc("nope")


# -- fixture corpus ------------------------------------------------------


def _fixture_cases():
    manifest = json.loads((FIXTURES / "manifest.json").read_text())
    return [pytest.param(c, id=c["dir"]) for c in manifest["cases"]]


@pytest.mark.parametrize("case", _fixture_cases())
def test_fixture_corpus_recovers_as_recorded(case, tmp_path):
    """The versioned damaged-WAL corpus (scripts/gen_wal_fixtures.py)
    recovers to its manifest-recorded golden state — a format change
    that breaks old logs fails HERE, not in production."""
    work = tmp_path / "wal"
    shutil.copytree(FIXTURES / case["dir"], work)  # recovery mutates
    prov = TpuProvider.recover(work, backend="cpu")
    lr = prov.last_recovery
    exp = case["expected"]
    assert lr["outcome"] == exp["outcome"]
    assert lr["torn_truncations"] == exp["torn_truncations"]
    assert lr["corrupt_records"] == exp["corrupt_records"]
    assert {g: prov.text(g) for g in sorted(prov._guids)} == exp["texts"]


# -- fsync policy + metrics ----------------------------------------------


@pytest.mark.parametrize("mode", ["always", "interval", "never"])
def test_fsync_policy_counters(tmp_path, mode):
    cfg = WalConfig(segment_bytes=1 << 20, fsync=mode, fsync_interval=4)
    wal = WriteAheadLog(tmp_path, cfg)
    prov_like_metrics = wal.metrics  # no-op bundle; count manually
    assert prov_like_metrics is not None
    import yjs_tpu.persistence.wal as walmod

    calls = []
    orig = walmod.os.fsync
    walmod.os.fsync = lambda fd: calls.append(fd)
    try:
        for k in range(10):
            wal.append(KIND_UPDATE, "g", b"x" * 8)
        wal.close()
    finally:
        walmod.os.fsync = orig
    if mode == "always":
        assert len(calls) == 11  # one per append + seal
    elif mode == "interval":
        assert len(calls) == 3  # appends 4 and 8, + seal
    else:
        assert calls == []


def test_env_config_and_validation(tmp_path, monkeypatch):
    monkeypatch.setenv("YTPU_WAL_SEGMENT_BYTES", "12345")
    monkeypatch.setenv("YTPU_WAL_FSYNC", "never")
    monkeypatch.setenv("YTPU_WAL_FSYNC_INTERVAL", "7")
    cfg = WalConfig()
    assert cfg.as_dict() == {
        "segment_bytes": 12345, "fsync": "never", "fsync_interval": 7
    }
    monkeypatch.setenv("YTPU_WAL_FSYNC", "sometimes")
    with pytest.raises(ValueError, match="YTPU_WAL_FSYNC"):
        WalConfig()
    # YTPU_WAL_DIR enables journaling without a constructor arg
    monkeypatch.setenv("YTPU_WAL_FSYNC", "never")
    monkeypatch.setenv("YTPU_WAL_DIR", str(tmp_path / "envwal"))
    prov = TpuProvider(1, backend="cpu")
    assert prov.wal is not None
    prov.receive_update("r", phased_streams(3, rooms=("r",))["r"][0][0])
    assert list_segments(tmp_path / "envwal")


@pytest.mark.parametrize("planner", ["native", "python"])
def test_recover_on_the_device_backend_merges_a_long_tail(
    tmp_path, monkeypatch, planner
):
    """One room's log of 200 updates comes back through
    ``backend="device"`` in ONE flush (a whole tail merged in one plan)
    equal to a ``Y.Doc`` fed the updates one by one."""
    if planner == "python":
        monkeypatch.setenv("YTPU_NO_NATIVE_PLAN", "1")
    doc = Y.Doc(gc=False)
    doc.client_id = 77
    updates: list[bytes] = []
    doc.on("update", lambda u, *_: updates.append(u))
    text = doc.get_text("text")
    k = 0
    while len(updates) < 200:  # a keystroke an update, a backspace in seven
        if k % 7 == 6:
            text.delete(k // 7, 1)
        else:
            at = len(str(text))
            text.insert(at // 2 if k % 3 else at, "ab"[k % 2])
        k += 1
    prov = TpuProvider(2, backend="device", wal_dir=tmp_path / "wal")
    for u in updates:
        assert prov.receive_update("room", u)
        prov.flush()
    prov.wal.abandon()  # killed: no close(), no checkpoint
    new = TpuProvider.recover(tmp_path / "wal", n_docs=2, backend="device")
    r = new.last_recovery
    assert (r["records_applied"], r["records_max_a_room"]) == (len(updates),) * 2
    assert (r["outcome"], r["dead_lettered"]) == ("clean", 0)
    oracle = Y.Doc(gc=False)
    for u in updates:
        Y.apply_update(oracle, u)
    assert new.text("room") == oracle.get_text("text").to_string() == str(text)
    assert new.state_vector("room") == Y.decode_state_vector(
        Y.encode_state_vector(oracle)
    )
    assert canonical(new, "room") == Y.merge_updates(
        [Y.encode_state_as_update(oracle)]
    )
    assert not new.engine.fallback and not new.engine.demotions
    new.close(checkpoint=False)


def test_wal_metric_families_always_registered():
    prov = TpuProvider(1, backend="cpu")  # no WAL attached
    names = set(prov.engine.obs.registry.names())
    expected = {
        "ytpu_wal_records_appended_total",
        "ytpu_wal_bytes_appended_total",
        "ytpu_wal_fsyncs_total",
        "ytpu_wal_segments_sealed_total",
        "ytpu_wal_compactions_total",
        "ytpu_wal_compaction_reclaimed_bytes_total",
        "ytpu_wal_recoveries_total",
        "ytpu_wal_replay_records_total",
        "ytpu_wal_torn_tail_truncations_total",
        "ytpu_wal_corrupt_records_total",
        "ytpu_wal_replay_seconds",
        "ytpu_wal_replay_bytes_total",
        "ytpu_wal_replay_phase_seconds_total",
        "ytpu_provider_docs_evicted_total",
    }
    assert expected <= names


def test_wal_counters_move_with_traffic(tmp_path):
    prov = TpuProvider(1, backend="cpu", wal_dir=tmp_path, wal_config=SMALL)
    (p1,) = phased_streams(7, rooms=("r",), phases=(20,))["r"]
    for u in p1:
        prov.receive_update("r", u)
    m = prov._wal_metrics
    assert m.records.labels(kind="update").value == len(p1)
    assert m.bytes.value > 0
    assert m.segments.value > 0  # rotation sealed at least one
    prov.checkpoint()
    assert m.compactions.value == 1
    assert m.reclaimed.value > 0
