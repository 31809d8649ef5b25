"""Crash-point chaos harness (ISSUE 3 acceptance): kill a journaling
provider at randomized points under the full transport-fault mix, tear
and bit-flip its WAL files, recover, and require byte-identical
reconvergence with an uninterrupted reference.

Both providers consume the SAME faulted stream (one injector pass), so
any divergence is recovery's fault, not the transport's.  The crash is
``WriteAheadLog.abandon()`` — the file handle is dropped with no
seal-time fsync, leaving the directory exactly as a killed process
would.  Mid-log at-rest damage (a flipped bit in a sealed segment) must
land in the dead-letter queue, never abort the replay.
"""

from __future__ import annotations

import random

import pytest

import yjs_tpu as Y
from yjs_tpu.lib0 import encoding
from yjs_tpu.lib0.encoding import Encoder
from yjs_tpu.persistence import WalConfig, list_segments
from yjs_tpu.provider import TpuProvider
from yjs_tpu.resilience import ChaosConfig, ChaosInjector, DiskFaultInjector
from yjs_tpu.sync import protocol

pytestmark = [pytest.mark.chaos, pytest.mark.durability]

ROOM = "room"
BACKENDS = ("cpu", "auto")
# the test_chaos.py "everything" mix: every fault class at once
EVERYTHING = dict(
    corrupt=0.15, truncate=0.1, duplicate=0.25, reorder=0.6, drop=0.15
)


def client_updates(seed: int, n_ops: int = 50, n_clients: int = 3):
    """Per-op incremental updates from independent editing clients
    (same traffic texture as tests/test_chaos.py)."""
    gen = random.Random(seed)
    docs = []
    updates: list[bytes] = []
    for k in range(n_clients):
        d = Y.Doc(gc=False)
        d.client_id = 1000 + k
        d.on("update", lambda u, origin, doc: updates.append(bytes(u)))
        docs.append(d)
    for _ in range(n_ops):
        d = gen.choice(docs)
        t = d.get_text("text")
        if len(t) and gen.random() < 0.3:
            t.delete(gen.randrange(len(t)), 1)
        else:
            t.insert(gen.randrange(len(t) + 1), gen.choice("abcdef "))
    return updates


def frame(update: bytes) -> bytes:
    enc = Encoder()
    encoding.write_var_uint(enc, protocol.MESSAGE_YJS_UPDATE)
    encoding.write_var_uint8_array(enc, update)
    return enc.to_bytes()


def sync_repair(pa: TpuProvider, pb: TpuProvider, rounds: int = 5) -> None:
    """Clean bidirectional step1/step2 exchange (post-chaos heal)."""
    for _ in range(rounds):
        reply = pb.handle_sync_message(ROOM, pa.sync_step1(ROOM))
        if reply is not None:
            pa.handle_sync_message(ROOM, reply)
        reply = pa.handle_sync_message(ROOM, pb.sync_step1(ROOM))
        if reply is not None:
            pb.handle_sync_message(ROOM, reply)


def canonical(prov: TpuProvider) -> bytes:
    """merge_updates-normalized full state: equal stores yield
    IDENTICAL bytes regardless of split/arrival history."""
    return Y.merge_updates([prov.encode_state_as_update(ROOM)])


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("crash_seed", range(10))
def test_crash_recover_reconverges_bytewise(backend, crash_seed, tmp_path):
    updates = client_updates(seed=400 + crash_seed)
    frames = [frame(u) for u in updates]
    # ONE injector pass feeds BOTH replicas: identical faulted stream
    inj = ChaosInjector(
        ChaosConfig(seed=crash_seed, **EVERYTHING), kind="frame"
    )
    faulted = inj.apply(frames)
    assert sum(inj.fault_counts.values()) > 0

    ref = TpuProvider(2, backend=backend)
    victim = TpuProvider(
        2,
        backend=backend,
        wal_dir=tmp_path,
        wal_config=WalConfig(segment_bytes=256, fsync="never"),
    )
    for f in faulted:
        ref.handle_sync_message(ROOM, f)

    crash_rng = random.Random(9000 + crash_seed)
    c = crash_rng.randrange(1, len(faulted))
    for k, f in enumerate(faulted[:c]):
        victim.handle_sync_message(ROOM, f)
        if k == c // 2 and k > 0:
            victim.checkpoint()  # compaction mid-life, like production
    victim.wal.abandon()  # kill -9

    # disk damage on what the dead process left behind
    disk = DiskFaultInjector(seed=7000 + crash_seed)
    segs = list_segments(tmp_path)
    flipped = False
    if segs:
        disk.tear(segs[-1][1])  # torn tail on the active segment
        if len(segs) > 1:
            flipped = disk.bitflip(segs[0][1], lo=8) >= 0

    victim = TpuProvider.recover(
        tmp_path,
        n_docs=2,
        backend=backend,
        wal_config=WalConfig(segment_bytes=256, fsync="never"),
    )
    if flipped:
        assert victim.last_recovery["corrupt_records"] >= 1
        assert any(
            d["reason"].startswith("wal-corrupt")
            for d in victim.dead_letters()
        )

    # the rest of the stream arrives at the recovered victim
    for f in faulted[c:]:
        victim.handle_sync_message(ROOM, f)

    # heal: quarantine backoff cleared (operator readmission, as in
    # test_chaos), then clean sync rounds
    ref.engine.health.reset(None)
    victim.engine.health.reset(None)
    sync_repair(ref, victim)

    assert victim.text(ROOM) == ref.text(ROOM)
    assert victim.state_vector(ROOM) == ref.state_vector(ROOM)
    assert canonical(victim) == canonical(ref)


def _invalid_record_log(tmp_path, two_files: bool):
    """A log of three update records for one room, all with a good CRC:
    a valid one, one that does not decode, a valid one after it."""
    from yjs_tpu.persistence import KIND_UPDATE, SEG_HEADER, encode_record

    a = client_updates(seed=7, n_ops=2, n_clients=1)
    assert len(a) == 2
    other = Y.Doc(gc=False)
    other.client_id = 2000
    other.get_text("text").insert(0, "never applied")
    bad = Y.encode_state_as_update(other)[:-3]
    payloads = [a[0], bad, a[1]]
    files = [payloads[:2], payloads[2:]] if two_files else [payloads]
    for i, group in enumerate(files):
        (tmp_path / f"wal-{i:08d}.log").write_bytes(
            SEG_HEADER
            + b"".join(encode_record(KIND_UPDATE, ROOM, p) for p in group)
        )
    ref = Y.Doc(gc=False)
    for u in a:
        Y.apply_update(ref, u)
    return bad, len(files), ref


@pytest.mark.parametrize("native_core", (True, False))
@pytest.mark.parametrize("two_files", (False, True))
def test_invalid_record_is_one_dead_letter(
    two_files, native_core, tmp_path, monkeypatch
):
    """Recovery's refusals read as before the validate pass became one
    native call a file: an undecodable record with a good CRC is ONE
    dead letter with ``validate_update``'s words, the valid records
    around it apply in the log's order, and ``last_recovery`` counts
    who gave each verdict: with ``YTPU_NO_NATIVE`` the same stats
    through the fallback."""
    from yjs_tpu import native

    if not native_core:
        monkeypatch.setenv("YTPU_NO_NATIVE", "1")
        for name in ("_lib", "_error"):
            monkeypatch.setattr(native, name, None)
        monkeypatch.setattr(native, "_tried", False)
    elif native.load() is None:
        pytest.skip(f"no native core: {native.load_error()}")
    bad, n_files, ref = _invalid_record_log(tmp_path, two_files)
    prov = TpuProvider.recover(tmp_path, n_docs=2, backend="cpu")
    s = prov.last_recovery
    assert (s["files"], s["records_applied"], s["dead_lettered"]) == (
        n_files, 2, 1
    )
    assert (s["corrupt_records"], s["torn_truncations"]) == (0, 0)
    assert (s["validated_native"], s["validated_fallback"]) == (
        (2, 1) if native_core else (0, 3)
    )
    (letter,) = prov.dead_letters()
    assert letter["reason"] == (
        "wal-invalid: InvalidUpdate: ValueError: unexpected end of array"
    )
    assert (letter["bytes"], letter["v2"]) == (len(bad), False)
    assert prov.text(ROOM) == ref.get_text("text").to_string()
    assert prov.state_vector(ROOM) == Y.get_state_vector(ref.store)
