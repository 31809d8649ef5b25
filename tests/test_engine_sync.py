"""Wire emission from the columnar mirror: sync steps without a CPU Doc."""

import random

import pytest

import yjs_tpu as Y
from yjs_tpu.ops import BatchEngine


def build_traced_doc(seed, client_id):
    gen = random.Random(seed)
    d = Y.Doc(gc=False)
    d.client_id = client_id
    t = d.get_text("text")
    for _ in range(30):
        ln = len(t.to_string())
        if gen.random() < 0.7 or ln == 0:
            t.insert(gen.randint(0, ln), gen.choice(["ab", "c", "ddd", "🙂"]))
        else:
            pos = gen.randrange(ln)
            t.delete(pos, min(gen.randint(1, 2), ln - pos))
    return d


def loaded_engine(doc):
    eng = BatchEngine(1)
    eng.queue_update(0, Y.encode_state_as_update(doc))
    eng.flush()
    return eng


class TestMirrorEmission:
    @pytest.mark.parametrize("v2", [False, True])
    def test_full_state_round_trip(self, v2):
        doc = build_traced_doc(1, 11)
        eng = loaded_engine(doc)
        update = eng.encode_state_as_update(0, v2=v2)
        fresh = Y.Doc(gc=False)
        (Y.apply_update_v2 if v2 else Y.apply_update)(fresh, update)
        assert fresh.get_text("text").to_string() == doc.get_text("text").to_string()
        assert Y.decode_state_vector(Y.encode_state_vector(fresh)) == (
            Y.decode_state_vector(Y.encode_state_vector(doc))
        )
        # delete sets must be equivalent after merge
        from yjs_tpu.core import create_delete_set_from_struct_store

        ds_a = create_delete_set_from_struct_store(fresh.store)
        ds_b = create_delete_set_from_struct_store(doc.store)
        assert {
            c: [(d.clock, d.len) for d in v] for c, v in ds_a.clients.items()
        } == {c: [(d.clock, d.len) for d in v] for c, v in ds_b.clients.items()}

    def test_diff_against_state_vector(self):
        doc = Y.Doc(gc=False)
        doc.client_id = 21
        updates = []
        doc.on("update", lambda u, o, d: updates.append(u))
        t = doc.get_text("text")
        for i in range(12):
            t.insert(len(t.to_string()) // 2, f"w{i} ")
            if i % 3 == 2:
                t.delete(0, 2)
        # peer holds a true prefix of the history
        partial = Y.Doc(gc=False)
        for u in updates[:5]:
            Y.apply_update(partial, u)

        eng = loaded_engine(doc)
        # ask the engine for exactly what `partial` is missing
        diff = eng.encode_state_as_update(0, Y.encode_state_vector(partial))
        Y.apply_update(partial, diff)
        assert partial.get_text("text").to_string() == t.to_string()

    def test_engine_to_engine_sync(self):
        a = build_traced_doc(3, 31)
        b = build_traced_doc(4, 32)
        ea, eb = loaded_engine(a), loaded_engine(b)
        # 2-step handshake in both directions, engine-to-engine
        upd_for_b = ea.encode_state_as_update(0, eb.encode_state_vector(0))
        upd_for_a = eb.encode_state_as_update(0, ea.encode_state_vector(0))
        ea.queue_update(0, upd_for_a)
        eb.queue_update(0, upd_for_b)
        ea.flush()
        eb.flush()
        assert ea.text(0) == eb.text(0)
        assert ea.state_vector(0) == eb.state_vector(0)
        # oracle: CPU docs syncing the same histories agree with the engines
        Y.apply_update(a, Y.encode_state_as_update(b))
        assert ea.text(0) == a.get_text("text").to_string()

    def test_emitted_update_feeds_engine(self):
        doc = build_traced_doc(5, 41)
        eng = loaded_engine(doc)
        again = BatchEngine(1)
        again.queue_update(0, eng.encode_state_as_update(0))
        again.flush()
        assert again.text(0) == eng.text(0)
        assert again.state_vector(0) == eng.state_vector(0)

    def test_incremental_then_emit(self):
        doc = Y.Doc(gc=False)
        doc.client_id = 51
        updates = []
        doc.on("update", lambda u, o, d: updates.append(u))
        t = doc.get_text("text")
        eng = BatchEngine(1)
        for step in range(5):
            t.insert(len(t.to_string()) // 2, f"<{step}>")
            if step % 2:
                t.delete(0, 1)
            for u in updates:
                eng.queue_update(0, u)
            updates.clear()
            eng.flush()
        out = Y.Doc(gc=False)
        Y.apply_update(out, eng.encode_state_as_update(0))
        assert out.get_text("text").to_string() == t.to_string()


class TestBatchedSyncKernels:
    """Sync step 1 + 2 across many docs, from the host mirrors
    (reference encoding.js:490-526,94-116 batched)."""

    def _make_engine(self, n):
        import yjs_tpu as Y
        from yjs_tpu.ops import BatchEngine

        docs, eng = [], BatchEngine(n)
        for i in range(n):
            d = Y.Doc(gc=False)
            d.client_id = 100 + i
            t = d.get_text("text")
            t.insert(0, f"doc{i} " * (i + 1))
            t.delete(0, 2)
            d.get_map("m").set("k", i)
            docs.append(d)
            eng.queue_update(i, Y.encode_state_as_update(d))
        eng.flush()
        return docs, eng

    def test_state_vectors_match_cpu_core(self):
        import yjs_tpu as Y

        docs, eng = self._make_engine(6)
        for i in range(6):
            want = Y.decode_state_vector(Y.encode_state_vector(docs[i]))
            assert eng.state_vector(i) == want
            assert Y.decode_state_vector(eng.encode_state_vector(i)) == want

    def test_sync_step2_batch_matches_per_doc_and_cpu(self):
        import yjs_tpu as Y

        docs, eng = self._make_engine(6)
        # mixed targets: empty, full, and partial state vectors
        partial = {100 + 3: 4}
        requests = [(0, None), (1, {}), (3, partial), (5, None)]
        replies = eng.sync_step2_batch(requests)
        for (i, sv), u in zip(requests, replies):
            import yjs_tpu.updates as upd
            from yjs_tpu.coding import DSEncoderV1

            enc_sv = None
            if sv:
                e = DSEncoderV1()
                upd.write_state_vector(e, sv)
                enc_sv = e.to_bytes()
            assert u == eng.encode_state_as_update(i, enc_sv)
            fresh = Y.Doc(gc=False)
            if sv:  # partial target: seed the fresh doc with the prefix
                continue
            Y.apply_update(fresh, u)
            assert fresh.get_text("text").to_string() == docs[i].get_text(
                "text"
            ).to_string()
            assert fresh.get_map("m").to_json() == docs[i].get_map("m").to_json()

    def test_partial_target_resyncs_stale_client(self):
        import yjs_tpu as Y

        docs, eng = self._make_engine(4)
        stale = Y.Doc(gc=False)
        stale.client_id = 900
        # stale client knows a prefix of doc 2
        d = docs[2]
        t = d.get_text("text")
        Y.apply_update(stale, Y.encode_state_as_update(d))
        t.insert(3, "[new]")
        u = Y.encode_state_as_update(d, Y.encode_state_vector(stale))
        eng.queue_update(2, u)
        eng.flush()
        sv = {c: v for c, v in Y.decode_state_vector(
            Y.encode_state_vector(stale)).items()}
        (reply,) = eng.sync_step2_batch([(2, sv)])
        Y.apply_update(stale, reply)
        Y.apply_update(d, u)  # author applies its own edit too (already has)
        assert stale.get_text("text").to_string() == d.get_text("text").to_string()

    def test_provider_batch_handshake(self):
        import yjs_tpu as Y
        from yjs_tpu.provider import TpuProvider
        from yjs_tpu.lib0.encoding import Encoder
        from yjs_tpu.lib0.decoding import Decoder
        from yjs_tpu.sync import protocol

        n = 5
        prov = TpuProvider(n)
        clients = []
        for i in range(n):
            d = Y.Doc(gc=False)
            d.client_id = 200 + i
            d.get_text("text").insert(0, f"room{i}")
            prov.receive_update(f"r{i}", Y.encode_state_as_update(d))
            clients.append(d)
        # every client reconnects at once: one dispatch answers all
        msgs = []
        for i, d in enumerate(clients):
            enc = Encoder()
            protocol.write_sync_step1(enc, d)
            msgs.append((f"r{i}", enc.to_bytes()))
        replies = prov.handle_sync_step1_batch(msgs)
        for d, reply in zip(clients, replies):
            protocol.read_sync_message(Decoder(reply), Encoder(), d)
        for i, d in enumerate(clients):
            assert prov.text(f"r{i}") == d.get_text("text").to_string()


# ---------------------------------------------------------------------------
# The reconnect handshake against the CPU core (ISSUE 32): the reference
# of a step 2 is ``Y.encode_state_as_update(doc, sv)`` on a ``Y.Doc`` fed
# what the room was sent, and a client that is a ``Y.Doc`` holding a
# stale prefix of it.  Rooms of the deployment's four kinds (the committed
# traces; the prepend room at 3000 characters for the suite's time), each
# with a seeded recent past of two typists.
# ---------------------------------------------------------------------------

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:  # the fixtures' reader, as tests/bench does
    sys.path.insert(0, str(ROOT))

KINDS = ("distinct", "storm", "b4", "prepend")
CLASSES = ("empty", "prefix", "current", "ahead", "unknown")
TYPIST_A, TYPIST_B, NEWCOMER = 700_001, 700_002, 700_003
SHARED, LATER = 8, 6  # entries both typists saw; entries B sent after A left


def _frame_step1(sv_bytes: bytes) -> bytes:
    from yjs_tpu.lib0 import encoding
    from yjs_tpu.lib0.encoding import Encoder
    from yjs_tpu.sync import protocol

    enc = Encoder()
    encoding.write_var_uint(enc, protocol.MESSAGE_YJS_SYNC_STEP_1)
    encoding.write_var_uint8_array(enc, sv_bytes)
    return enc.to_bytes()


def _step2_payload(reply: bytes) -> bytes:
    from yjs_tpu.lib0 import decoding
    from yjs_tpu.lib0.decoding import Decoder
    from yjs_tpu.sync import protocol

    dec = Decoder(reply)
    assert decoding.read_var_uint(dec) == protocol.MESSAGE_YJS_SYNC_STEP_2
    return decoding.read_var_uint8_array(dec)


def _sv(doc) -> dict:
    return Y.decode_state_vector(Y.encode_state_vector(doc))


def _replay(updates, client_id=None):
    doc = Y.Doc(gc=False)
    if client_id is not None:
        doc.client_id = client_id
    for u in updates:
        Y.apply_update(doc, u)
    return doc


def _edit(doc, gen, n):
    """``n`` transactions on ``doc``'s text, one update each."""
    out = []
    doc.on("update", lambda u, *_: out.append(u))
    text = doc.get_text("text")
    for _ in range(n):
        ln = len(text)
        if ln and gen.random() < 0.3:
            text.delete(gen.randrange(ln), 1)
        else:
            text.insert(gen.randint(0, ln), gen.choice(["a", "bc", "d e", "🙂"]))
    return out


def _structs_by_client(update: bytes) -> dict:
    """Per client of a v1 update: the clock its structs start at and the
    elements they hold, read by the core's own struct reader."""
    from yjs_tpu.coding import UpdateDecoderV1
    from yjs_tpu.lib0.decoding import Decoder
    from yjs_tpu.updates import read_clients_struct_refs

    refs = read_clients_struct_refs(
        UpdateDecoderV1(Decoder(update)), {}, Y.Doc(gc=False)
    )
    return {
        client: (structs[0].id.clock, sum(s.length for s in structs))
        for client, structs in refs.items() if structs
    }


def _base(kind: str, gen) -> bytes:
    from benchmarks import deployment

    if kind == "b4":
        return (deployment.FIXTURES / "b4_trace.bin").read_bytes()
    if kind == "prepend":  # prepend_frag_100000's shape, 3000 long
        doc = Y.Doc(gc=False)
        doc.client_id = 77
        text = doc.get_text("text")
        for _ in range(3000):
            text.insert(0, gen.choice("abcdefgh "))
        return Y.encode_state_as_update(doc)
    return gen.choice(deployment.load_traces(f"{kind}_traces"))


@pytest.fixture(scope="module")
def handshake(tmp_path_factory):
    """A provider with a WAL that holds one room of each kind: the
    committed trace, then SHARED entries two typists made while both
    were connected, then LATER entries B made after A had left.  ``sent``
    is what the room was sent; ``a`` is A's own document, which went on
    typing offline (``a_unsent``)."""
    from yjs_tpu.persistence import WalConfig
    from yjs_tpu.provider import TpuProvider

    wal = tmp_path_factory.mktemp("handshake") / "wal"
    prov = TpuProvider(
        8, wal_dir=str(wal), wal_config=WalConfig(fsync="never")
    )
    heard = {}
    prov.on_update(lambda guid, u: heard.setdefault(guid, []).append(u))
    rooms = {}
    for k, kind in enumerate(KINDS):
        gen = random.Random(f"handshake:{kind}")
        base = _base(kind, gen)
        a, b = _replay([base], TYPIST_A), _replay([base], TYPIST_B)
        sent = [base]
        for turn in range(SHARED):
            (u,) = _edit(a if turn % 2 else b, gen, 1)
            Y.apply_update(b if turn % 2 else a, u)
            sent.append(u)
        a_unsent = _edit(a, gen, 3)
        sent += _edit(b, gen, LATER)
        guid = f"room/{kind}"
        for u in sent:
            assert prov.receive_update(guid, u)
        rooms[kind] = {
            "guid": guid, "sent": sent, "a": a, "a_unsent": a_unsent,
            "oracle": _replay(sent), "gen": gen,
        }
    prov.flush()
    yield prov, rooms, heard, wal
    prov.close(checkpoint=False)


def _client(room, cls):
    """A client of class ``cls`` and the document that holds what it
    must hold once it has applied its answer."""
    sent, gen = room["sent"], random.Random(f"client:{cls}")
    if cls == "empty":
        return Y.Doc(gc=False), room["oracle"]
    if cls == "prefix":
        return _replay(sent[: 1 + gen.randint(1, SHARED + LATER - 1)]), room["oracle"]
    if cls == "current":
        return _replay(sent), room["oracle"]
    if cls == "ahead":  # A: all it sent and more, and none of B's later entries
        client = _replay([Y.encode_state_as_update(room["a"])], TYPIST_A)
        return client, _replay(sent + room["a_unsent"])
    # a newcomer that synced a prefix once and typed before it connected
    client = _replay(sent[: 1 + SHARED], NEWCOMER)
    typed = _edit(client, gen, 2)
    return client, _replay(sent + typed)


class TestReconnectHandshake:
    @pytest.mark.parametrize("cls", CLASSES)
    @pytest.mark.parametrize("kind", KINDS)
    def test_answer_is_the_gap_and_the_client_ends_where_the_oracle_is(
        self, handshake, kind, cls
    ):
        prov, rooms, _heard, _wal = handshake
        room = rooms[kind]
        client, want = _client(room, cls)
        session_sv, room_sv = _sv(client), _sv(room["oracle"])
        frame = _frame_step1(Y.encode_state_vector(client))
        (reply,) = prov.handle_sync_step1_batch([(room["guid"], frame)])
        # (c) the batch's answer is handle_sync_message's, byte for byte
        assert reply == prov.handle_sync_message(room["guid"], frame)
        answer = _step2_payload(reply)
        # (b) the gap and no more: per client, from the session's clock,
        # as many elements as the room holds past it
        assert _structs_by_client(answer) == {
            c: (session_sv.get(c, 0), n - session_sv.get(c, 0))
            for c, n in room_sv.items() if n > session_sv.get(c, 0)
        }
        assert _structs_by_client(answer) == _structs_by_client(
            Y.encode_state_as_update(
                room["oracle"], Y.encode_state_vector(client)
            )
        )
        # (a) applied to the stale document: the oracle's state vector,
        # text and canonical encoded state
        Y.apply_update(client, answer)
        assert _sv(client) == _sv(want)
        assert client.get_text("text").to_string() == (
            want.get_text("text").to_string()
        )
        assert Y.merge_updates([Y.encode_state_as_update(client)]) == (
            Y.merge_updates([Y.encode_state_as_update(want)])
        )
        m = prov.last_sync_metrics
        assert (m["n_requests"], m["n_bad"]) == (1, 0)
        assert m["n_full"] == (cls == "empty")
        assert m["reply_bytes"] == len(reply)
        assert m["encode_buffer_bytes"] >= len(answer)

    def test_a_batch_answers_every_room_as_one_at_a_time(self, handshake):
        prov, rooms, _heard, _wal = handshake
        msgs = [
            (rooms[kind]["guid"],
             _frame_step1(Y.encode_state_vector(_client(rooms[kind], cls)[0])))
            for kind in KINDS for cls in CLASSES
        ]
        before = prov.engine.obs.registry.get(
            "ytpu_provider_sync_step2_total"
        ).value
        replies = prov.handle_sync_step1_batch(msgs)
        assert replies == [prov.handle_sync_message(g, f) for g, f in msgs]
        m = prov.last_sync_metrics
        assert (m["n_requests"], m["n_full"], m["n_bad"]) == (20, 4, 0)
        assert m["reply_bytes"] == sum(map(len, replies))
        assert m["t_decode_s"] > 0 and m["t_encode_s"] > 0
        # 20 from the batch, 20 one at a time
        assert prov.engine.obs.registry.get(
            "ytpu_provider_sync_step2_total"
        ).value == before + 40

    def test_a_bad_frame_costs_its_own_answer_and_no_other(self, handshake):
        """(d) ``handle_sync_message``'s contract, frame by frame."""
        from yjs_tpu.sync import protocol

        prov, rooms, _heard, _wal = handshake
        guid = rooms["distinct"]["guid"]
        good = _frame_step1(Y.encode_state_vector(Y.Doc(gc=False)))
        update = rooms["distinct"]["sent"][1]
        bad = {
            "not step 1": bytes([protocol.MESSAGE_YJS_UPDATE, len(update)]) + update,
            "no type": b"",
            "truncated": good[:1] + b"\x09\x02",
            "garbage state vector": _frame_step1(b"\xff\xff\xff\xff"),
            "out of range": _frame_step1(b"\x01\x01" + b"\xff" * 9 + b"\x01"),
        }
        msgs = [(guid, good)]
        for frame in bad.values():
            msgs += [(guid, frame), (guid, good)]
        queue, doc = prov.engine.dead_letters, prov.doc_id(guid)
        letters = len(queue.list(doc=doc))
        counted = prov.engine.obs.registry.get(
            "ytpu_provider_sync_messages_total"
        )
        n_bad = counted.labels(type="bad").value
        replies = prov.handle_sync_step1_batch(msgs)
        want = prov.handle_sync_message(guid, good)
        assert replies == [want] + [None, want] * len(bad)
        assert prov.last_sync_metrics["n_bad"] == len(bad)
        assert counted.labels(type="bad").value == n_bad + len(bad)
        new = queue.list(doc=doc)[letters:]
        assert [bytes(e.update) for e in new] == list(bad.values())
        assert all(e.reason.startswith("bad-frame: ") for e in new)
        # one at a time, the frames that are step 1 fail the same way
        for name in ("no type", "truncated", "garbage state vector", "out of range"):
            assert prov.handle_sync_message(guid, bad[name]) is None
        assert not prov.engine.rollbacks and not prov.engine.fallback

    def test_what_a_session_sends_back_is_an_update_like_any_other(
        self, handshake
    ):
        """(e) the newcomer answers the server's step 1 with what it
        typed while away: acknowledged, journaled, integrated, broadcast;
        a second session of the room ends where the first did."""
        from benchmarks.oracle import read_wal

        prov, rooms, heard, wal = handshake
        room = rooms["storm"]
        guid = room["guid"]
        first, _want = _client(room, "unknown")
        second = _replay(room["sent"])
        (reply,) = prov.handle_sync_step1_batch(
            [(guid, _frame_step1(Y.encode_state_vector(first)))]
        )
        Y.apply_update(first, _step2_payload(reply))
        # the server's own step 1, answered by the client's core
        server_sv = _step2_payload(
            bytes([1]) + prov.sync_step1(guid)[1:]
        )
        assert Y.decode_state_vector(server_sv) == _sv(room["oracle"])
        back = Y.encode_state_as_update(first, server_sv)
        assert set(_structs_by_client(back)) == {NEWCOMER}
        n_heard = len(heard.get(guid, []))
        assert prov.receive_update(guid, back) is True
        prov.flush()
        assert read_wal(wal)[guid][-1][-1] == back
        assert prov.state_vector(guid) == _sv(first)
        assert prov.text(guid) == first.get_text("text").to_string()
        for u in heard[guid][n_heard:]:
            Y.apply_update(second, u)
        assert len(heard[guid]) > n_heard
        assert _sv(second) == _sv(first)
        assert Y.merge_updates([Y.encode_state_as_update(second)]) == (
            Y.merge_updates([Y.encode_state_as_update(first)])
        )
        # and a third, that reconnects only now, is owed it as a gap
        third = _replay(room["sent"])
        (reply,) = prov.handle_sync_step1_batch(
            [(guid, _frame_step1(Y.encode_state_vector(third)))]
        )
        assert set(_structs_by_client(_step2_payload(reply))) == {NEWCOMER}


# ---------------------------------------------------------------------------
# Step 1 from the core's own state vector (ISSUE 39): a server that only
# announces state vectors reads no Python shadow and builds none.
# ---------------------------------------------------------------------------

from yjs_tpu.ops.columns import DocMirror
from yjs_tpu.ops.native_mirror import NativeMirror, native_plan_available

native_only = pytest.mark.skipif(
    not native_plan_available(), reason="native plan core unavailable"
)


def _typed(client_id, text, base=None):
    doc = _replay([base] if base else [], client_id)
    doc.get_text("text").insert(0, text)
    return doc


def _step1_updates(case):
    if case == "no client":
        return []
    a = _typed(5, "hello")
    if case == "one client":
        return [Y.encode_state_as_update(a)]
    b = _typed(1 << 40, "B", Y.encode_state_as_update(a))
    c = _typed(300, "a third", Y.encode_state_as_update(b))
    if case == "several clients":
        return [Y.encode_state_as_update(c)]
    # heard of and not held: client 9's second entry arrives without its
    # first and is parked, so its state in the room is 0 and stays out
    d = _typed(9, "X", Y.encode_state_as_update(a))
    sv = Y.encode_state_vector(d)
    d.get_text("text").insert(0, "Y")
    return [Y.encode_state_as_update(a), Y.encode_state_as_update(d, sv)]


@native_only
class TestStep1FromTheCore:
    @pytest.mark.parametrize(
        "case",
        ["no client", "one client", "several clients", "a client at state 0"],
    )
    def test_bytes_are_the_shadows(self, case):
        m = NativeMirror("text")
        for u in _step1_updates(case):
            m.ingest(u)
        m.prepare_step()
        assert m.has_pending() == (case == "a client at state 0")
        got = m.encode_state_vector()
        assert m._synced_gen == -1  # the shadow was never built
        m._sync()
        assert got == DocMirror.encode_state_vector(m._py)
        assert Y.decode_state_vector(got) == m.state_vector()
        assert len(m.state_vector()) == {
            "no client": 0, "one client": 1, "several clients": 3,
            "a client at state 0": 1,
        }[case]

    def test_a_state_vector_longer_than_the_first_buffer(self):
        m = NativeMirror("text")
        base = None
        for k in range(40):  # 40 clients of 8-byte ids: over 256 bytes
            doc = _typed((1 << 52) + k, "x", base)
            base = Y.encode_state_as_update(doc)
        m.ingest(base)
        m.prepare_step()
        got = m.encode_state_vector()
        assert len(got) > 256 and len(Y.decode_state_vector(got)) == 40
        m._sync()
        assert got == DocMirror.encode_state_vector(m._py)

    def test_the_handshake_builds_no_shadow(self, monkeypatch):
        """``sync_step1``, ``handle_sync_step1_batch`` and a session
        host's ``state_vector`` on native rooms: ``_sync()`` never runs
        and ``_synced_gen`` stays where it was."""
        from yjs_tpu.provider import TpuProvider, _ProviderSessionHost

        prov = TpuProvider(4)
        docs = {}
        for k, guid in enumerate(("r/a", "r/b", "r/c")):
            docs[guid] = d = _typed(40 + k, f"room {guid} " * (k + 1))
            d.get_text("text").delete(1, 2)
            prov.receive_update(guid, Y.encode_state_as_update(d))
        prov.flush()
        mirrors = [prov.engine.mirrors[prov.doc_id(g)] for g in docs]
        assert all(isinstance(m, NativeMirror) for m in mirrors)
        gens = [m._synced_gen for m in mirrors]
        ran = []
        real = NativeMirror._sync
        monkeypatch.setattr(
            NativeMirror, "_sync", lambda self: ran.append(self) or real(self)
        )
        step1 = {g: prov.sync_step1(g) for g in docs}
        # more typing moves every room's generation; the next flush is the
        # batch handler's own
        for g, d in docs.items():
            sv = Y.encode_state_vector(d)
            d.get_text("text").insert(0, "more ")
            prov.receive_update(g, Y.encode_state_as_update(d, sv))
        msgs = [
            (g, _frame_step1(sv))
            for g in docs
            for sv in (b"\x00", Y.encode_state_vector(docs[g]))
        ]
        replies = prov.handle_sync_step1_batch(msgs)
        m = prov.last_sync_metrics
        assert (m["encode_batched"], m["encode_fallback"]) == (len(msgs), 0)
        assert m["encode_buffer_bytes"] == sum(
            len(_step2_payload(r)) for r in replies
        )
        hosted = {
            g: _ProviderSessionHost(prov, g, "peer").state_vector()
            for g in docs
        }
        assert not ran
        assert [m._synced_gen for m in mirrors] == gens
        monkeypatch.setattr(NativeMirror, "_sync", real)
        for (g, d), mirror in zip(docs.items(), mirrors):
            sv = _step2_payload(bytes([1]) + prov.sync_step1(g)[1:])
            assert sv == hosted[g]
            assert Y.decode_state_vector(sv) == _sv(d)
            mirror._sync()
            assert sv == DocMirror.encode_state_vector(mirror._py)
            assert step1[g] != prov.sync_step1(g)  # it had typed since
        for (g, frame), reply in zip(msgs, replies):
            assert reply == prov.handle_sync_message(g, frame)
