"""Sharded engine tests on the virtual 8-device CPU mesh (conftest sets
XLA_FLAGS=--xla_force_host_platform_device_count=8)."""

import numpy as np
import pytest

import jax

import yjs_tpu as Y
from yjs_tpu.ops import BatchEngine
from yjs_tpu.parallel import doc_mesh


@pytest.fixture(scope="module")
def mesh8():
    # the virtual 8-device host mesh (XLA_FLAGS in conftest); under
    # YTPU_TEST_PLATFORM=tpu the default backend is the chip, so ask for
    # cpu explicitly
    if len(jax.devices("cpu")) < 8:
        pytest.skip("needs 8 virtual cpu devices")
    return doc_mesh(8, backend="cpu")


def build_docs(n):
    docs = []
    for i in range(n):
        d = Y.Doc(gc=False)
        d.client_id = 1000 + i
        t = d.get_text("text")
        t.insert(0, f"doc{i}-")
        t.insert(len(t.to_string()), "payload " * (i % 4 + 1))
        t.delete(1, 2)
        docs.append(d)
    return docs


def test_sharded_flush_matches_cpu(mesh8):
    n = 16
    docs = build_docs(n)
    eng = BatchEngine(n, mesh=mesh8)
    for i, d in enumerate(docs):
        eng.queue_update(i, Y.encode_state_as_update(d))
    eng.flush()
    assert eng.last_metrics is not None and eng.last_metrics["integrated"] > 0
    for i, d in enumerate(docs):
        assert eng.text(i) == d.get_text("text").to_string()
        assert eng.state_vector(i) == {
            c: v for c, v in Y.get_state_vector(d.store).items() if v > 0
        }


def test_sharded_incremental_concurrent(mesh8):
    n = 8
    docs = build_docs(n)
    eng = BatchEngine(n, mesh=mesh8)
    for i, d in enumerate(docs):
        eng.queue_update(i, Y.encode_state_as_update(d))
    eng.flush()
    # second round: concurrent remote edits from a second client per doc
    for i, d in enumerate(docs):
        remote = Y.Doc(gc=False)
        remote.client_id = 2000 + i
        Y.apply_update(remote, Y.encode_state_as_update(d))
        remote.get_text("text").insert(0, "R:")
        u = Y.encode_state_as_update(remote, Y.encode_state_vector(d))
        Y.apply_update(d, u)
        eng.queue_update(i, u)
    eng.flush()
    for i, d in enumerate(docs):
        assert eng.text(i) == d.get_text("text").to_string()


def test_meshed_engine_state_vectors_are_the_host_mirrors(mesh8):
    # a meshed engine answers state vectors as an unmeshed one does: from
    # the host mirrors, equal to the CPU core's, with no device program
    n = 8
    docs = build_docs(n)
    eng = BatchEngine(n, mesh=mesh8)
    for i, d in enumerate(docs):
        eng.queue_update(i, Y.encode_state_as_update(d))
    eng.flush()
    from yjs_tpu.obs.prof import kernel_profiler

    before = kernel_profiler().snapshot()
    for i in (0, 3, 5):
        assert eng.state_vector(i) == {
            c: v for c, v in Y.get_state_vector(docs[i].store).items() if v > 0
        }
        assert Y.decode_state_vector(eng.encode_state_vector(i)) == (
            eng.state_vector(i)
        )
    assert kernel_profiler().snapshot() == before


def test_meshed_tables_equal_unmeshed(mesh8):
    """The sharded bulk apply leaves the tables the one-device apply
    leaves: the same eight docs through a meshed and an unmeshed engine,
    two flushes each, give equal ``_right``/``_deleted``/``_starts``, and
    the mesh's psum'd counters saw the work."""
    n = 8
    docs = build_docs(n)
    first = [Y.encode_state_as_update(d) for d in docs]
    second = []
    for d in docs:
        sv = Y.encode_state_vector(d)
        d.get_text("text").insert(3, "more")
        second.append(Y.encode_state_as_update(d, sv))
    tables = {}
    for name, mesh in (("mesh", mesh8), ("one", None)):
        eng = BatchEngine(n, mesh=mesh)
        for round_ in (first, second):
            for i, u in enumerate(round_):
                eng.queue_update(i, u)
            eng.flush()
        if mesh is not None:
            assert eng.last_metrics is not None
            assert eng.last_metrics["integrated"] > 0
        for i, d in enumerate(docs):
            assert eng.text(i) == d.get_text("text").to_string()
        rows = max(m.n_rows for m in eng.mirrors)
        segs = max(m.n_segs for m in eng.mirrors)
        tables[name] = (
            np.asarray(eng._right)[:, :rows],
            np.asarray(eng._deleted)[:, :rows],
            np.asarray(eng._starts)[:, :segs],
        )
    for x, y in zip(tables["mesh"], tables["one"]):
        assert (x == y).all()


def test_meshed_engine_arrays_stay_on_mesh(mesh8):
    """Every device array of a meshed engine lives on the mesh's devices —
    an unpinned transfer would land on the default backend/device instead
    (the r1/r2 MULTICHIP failure mode: a virtual CPU mesh engine touching
    the real accelerator)."""
    mesh_devs = set(mesh8.devices.flat)
    n = 8
    docs = build_docs(n)
    eng = BatchEngine(n, mesh=mesh8, compact_min_rows=4)

    def check_all():
        arrays = {
            "_right": eng._right,
            "_deleted": eng._deleted,
            "_starts": eng._starts,
        }
        for name, arr in arrays.items():
            if arr is None:
                continue
            devs = set(arr.devices())
            assert devs == mesh_devs, (
                f"{name} on {devs}, expected the full mesh {mesh_devs}"
            )

    for i, d in enumerate(docs):
        eng.queue_update(i, Y.encode_state_as_update(d))
    eng.flush()
    check_all()
    # second flush: exercises capacity growth and (compact_min_rows=4)
    # the compaction scatter path
    for i, d in enumerate(docs):
        sv = Y.encode_state_vector(d)
        d.get_text("text").insert(0, "x" * 40)
        eng.queue_update(i, Y.encode_state_as_update(d, sv))
    eng.flush()
    check_all()
    # a batch of handshakes on a meshed engine leaves the tables placed
    eng.sync_step2_batch([(i, None) for i in range(n)])
    check_all()
    for i, d in enumerate(docs):
        assert eng.text(i) == d.get_text("text").to_string()


def test_meshed_provider_full_surface(mesh8):
    """The whole Provider surface on a sharded engine: receive/flush,
    sync handshake, snapshot capture + scoped render, server undo —
    device-resident rooms over the mesh throughout."""
    from yjs_tpu.provider import TpuProvider

    prov = TpuProvider(n_docs=16, mesh=mesh8)
    prov.enable_undo("room-0", capture_timeout=0)
    clients = []
    for i in range(16):
        d = Y.Doc(gc=False)
        d.client_id = 3000 + i
        d.get_text("text").insert(0, f"room{i} hello")
        clients.append(d)
        prov.receive_update(
            f"room-{i}", Y.encode_state_as_update(d), undoable=(i == 0)
        )
    prov.flush()
    snap = prov.snapshot("room-3")
    for i, d in enumerate(clients):
        d.get_text("text").insert(0, "more! ")
        prov.receive_update(
            f"room-{i}",
            Y.encode_state_as_update(d, None),
            undoable=(i == 0),
        )
    prov.flush()
    assert prov.engine.last_metrics["integrated"] > 0  # psum'd collectives
    for i, d in enumerate(clients):
        assert prov.text(f"room-{i}") == d.get_text("text").to_string()
    # snapshot-scoped render on a meshed room
    assert prov.to_delta("room-3", snapshot=snap) == [
        {"insert": "room3 hello"}
    ]
    # sync handshake: a fresh peer pulls room-5 over the wire frames
    from yjs_tpu.lib0.encoding import Encoder
    from yjs_tpu.sync import protocol

    peer = Y.Doc(gc=False)
    enc = Encoder()
    protocol.write_sync_step1(enc, peer)
    reply = prov.handle_sync_message("room-5", enc.to_bytes())
    assert reply
    from yjs_tpu.lib0.decoding import Decoder

    out = Encoder()
    protocol.read_sync_message(Decoder(reply), out, peer, "prov")
    assert (
        peer.get_text("text").to_string()
        == clients[5].get_text("text").to_string()
    )
    # server-side undo against the meshed room
    prov.undo("room-0")
    assert prov.text("room-0") == "room0 hello"
    prov.redo("room-0")
    assert prov.text("room-0") == "more! room0 hello"
    assert prov.engine.fallback == {}  # everything stayed device-resident
