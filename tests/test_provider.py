"""TpuProvider: CPU clients syncing against the batched device backend with
randomized delivery — the provider-boundary fuzz of SURVEY.md §4.2-4.3."""

import random

import pytest

import yjs_tpu as Y
from yjs_tpu.provider import TpuProvider


def client_edit(gen, doc):
    t = doc.get_text("text")
    ln = len(t.to_string())
    if gen.random() < 0.7 or ln == 0:
        t.insert(gen.randint(0, ln), gen.choice(["x", "yy", "zzz", "🙂", "word "]))
    else:
        pos = gen.randrange(ln)
        t.delete(pos, min(gen.randint(1, 3), ln - pos))


class TestProvider:
    def test_single_room_two_clients(self):
        prov = TpuProvider(4)
        a = Y.Doc(gc=False)
        a.client_id = 1
        b = Y.Doc(gc=False)
        b.client_id = 2
        a.get_text("text").insert(0, "from-a ")
        b.get_text("text").insert(0, "from-b ")
        prov.receive_update("room", Y.encode_state_as_update(a))
        prov.receive_update("room", Y.encode_state_as_update(b))
        # handshake: each client syncs down the provider's merged state
        for d in (a, b):
            reply = prov.handle_sync_message("room", _step1(d))
            _apply_step2(d, reply)
        assert a.get_text("text").to_string() == b.get_text("text").to_string()
        assert prov.text("room") == a.get_text("text").to_string()

    def test_many_rooms_batched(self):
        n = 8
        prov = TpuProvider(n)
        docs = []
        for i in range(n):
            d = Y.Doc(gc=False)
            d.client_id = 100 + i
            d.get_text("text").insert(0, f"room-{i} content")
            docs.append(d)
            prov.receive_update(f"room{i}", Y.encode_state_as_update(d))
        prov.flush()
        for i, d in enumerate(docs):
            assert prov.text(f"room{i}") == d.get_text("text").to_string()

    def test_unsupported_room_falls_back(self):
        prov = TpuProvider(2)
        d = Y.Doc(gc=False)
        d.client_id = 5
        d.get_map("meta").set("sub", Y.Doc(guid="child"))  # ContentDoc
        d.get_text("text").insert(0, "t")
        prov.receive_update("mixed", Y.encode_state_as_update(d))
        prov.flush()
        assert prov.n_fallback_docs == 1
        assert prov.text("mixed") == "t"
        # the demotion is visible with its reason, not silent
        assert prov.demotions == [
            {"guid": "mixed", "reason": "subdocument (content ref 9)"}
        ]
        assert prov.metrics["n_demoted"] == 1

    def test_backend_cpu_serves_everything_without_device(self):
        prov = TpuProvider(2, backend="cpu")
        d = Y.Doc(gc=False)
        d.client_id = 5
        d.get_text("text").insert(0, "cpu-only")
        d.get_map("m").set("sub", Y.Doc(guid="child"))  # fine on CPU
        prov.receive_update("room", Y.encode_state_as_update(d))
        prov.flush()
        assert prov.text("room") == "cpu-only"
        assert prov.n_fallback_docs == 1  # lazily, only the allocated room
        assert prov.demotions == []  # by configuration, not by gap

    def test_backend_device_forbids_fallback(self):
        import pytest as _pytest

        prov = TpuProvider(2, backend="device")
        ok = Y.Doc(gc=False)
        ok.client_id = 6
        ok.get_text("text").insert(0, "fine")
        prov.receive_update("a", Y.encode_state_as_update(ok))
        prov.flush()
        assert prov.text("a") == "fine"
        bad = Y.Doc(gc=False)
        bad.client_id = 7
        bad.get_map("m").set("sub", Y.Doc(guid="child"))
        prov.receive_update("b", Y.encode_state_as_update(bad))
        with _pytest.raises(RuntimeError, match="forbids CPU fallback"):
            prov.flush()
        # the alert persists on every flush while the demotion exists —
        # not a one-shot warning (data stays served by the CPU core)
        prov.receive_update("a", Y.encode_state_as_update(ok))
        with _pytest.raises(RuntimeError, match="forbids CPU fallback"):
            prov.flush()

    def test_nested_room_stays_on_device(self):
        prov = TpuProvider(2)
        d = Y.Doc(gc=False)
        d.client_id = 5
        inner = Y.YMap()
        d.get_map("meta").set("nested", inner)
        inner.set("x", 1)
        prov.receive_update("room", Y.encode_state_as_update(d))
        prov.flush()
        assert prov.n_fallback_docs == 0
        assert prov.engine.map_json(0, "meta") == {"nested": {"x": 1}}

    def test_flush_metrics_phases_and_occupancy(self):
        prov = TpuProvider(4)
        for room in ("r0", "r1"):
            d = Y.Doc(gc=False)
            d.client_id = 7
            d.get_text("text").insert(0, "hello")
            prov.receive_update(room, Y.encode_state_as_update(d))
        prov.flush()
        m = prov.metrics
        assert m["n_docs_flushed"] == 2
        assert m["n_demoted"] == 0 and m["n_fallback_docs"] == 0
        assert m["n_sched_entries"] >= 2
        assert 0.0 < m["schedule_occupancy"] <= 1.0
        assert m["n_pending_docs"] == 0 and m["pending_depth"] == 0
        for k in ("t_compact_s", "t_plan_s", "t_pack_s", "t_dispatch_s",
                  "t_emit_s", "t_total_s"):
            assert m[k] >= 0.0
        assert m["t_total_s"] >= m["t_plan_s"]

    def test_map_room_served_on_device(self):
        prov = TpuProvider(2)
        a = Y.Doc(gc=False)
        a.client_id = 5
        b = Y.Doc(gc=False)
        b.client_id = 6
        a.get_map("meta").set("k", 1)
        a.get_text("text").insert(0, "t")
        b.get_map("meta").set("k", 2)  # concurrent LWW conflict
        prov.receive_update("room", Y.encode_state_as_update(a))
        prov.receive_update("room", Y.encode_state_as_update(b))
        prov.flush()
        assert prov.n_fallback_docs == 0
        # both clients sync down; all three agree on the LWW winner
        for d in (a, b):
            _apply_step2(d, prov.handle_sync_message("room", _step1(d)))
        assert a.get_map("meta").to_json() == b.get_map("meta").to_json()
        assert prov.engine.map_json(prov.doc_id("room"), "meta") == \
            a.get_map("meta").to_json()

    @pytest.mark.parametrize("seed", range(4))
    def test_fuzz_random_delivery(self, seed):
        gen = random.Random(seed)
        n_clients = 3
        prov = TpuProvider(2)
        docs = [Y.Doc(gc=False) for _ in range(n_clients)]
        queues = [[] for _ in range(n_clients)]  # provider -> nothing; client updates
        for i, d in enumerate(docs):
            d.client_id = 10 + i
            d.on("update", lambda u, o, dd, i=i: queues[i].append(u))
        for _ in range(60):
            i = gen.randrange(n_clients)
            client_edit(gen, docs[i])
            if gen.random() < 0.4:
                # deliver a random prefix of a random client's updates
                src = gen.randrange(n_clients)
                if queues[src]:
                    k = gen.randint(1, len(queues[src]))
                    picks = gen.sample(queues[src], k)  # random order + subset
                    for u in picks:
                        prov.receive_update("room", u)
            if gen.random() < 0.3:
                prov.flush()
        # final: everything reaches the provider, clients sync down
        for q in queues:
            for u in q:
                prov.receive_update("room", u)
        prov.flush()
        for d in docs:
            reply = prov.handle_sync_message("room", _step1(d))
            _apply_step2(d, reply)
            # push anything the provider missed (none expected) then compare
        texts = {d.get_text("text").to_string() for d in docs}
        assert len(texts) == 1
        assert prov.text("room") in texts
        assert not prov.engine.has_pending(prov.doc_id("room"))


def _step1(doc):
    from yjs_tpu.lib0.encoding import Encoder
    from yjs_tpu.sync import protocol

    enc = Encoder()
    protocol.write_sync_step1(enc, doc)
    return enc.to_bytes()


def _apply_step2(doc, reply):
    from yjs_tpu.lib0.decoding import Decoder
    from yjs_tpu.lib0.encoding import Encoder
    from yjs_tpu.sync import protocol

    protocol.read_sync_message(Decoder(reply), Encoder(), doc)


class TestUpdateEmission:
    """After flush() the engine emits per-doc incremental
    updates (reference Transaction.js:339-352) so a server can broadcast
    to peers; a third replica stays in sync purely from emitted updates."""

    def test_observer_replica_syncs_from_emissions_only(self):
        gen = random.Random(7)
        prov = TpuProvider(2)
        observer = Y.Doc(gc=False)
        observer.client_id = 999
        prov.on_update(
            lambda guid, u: Y.apply_update(observer, u) if guid == "room" else None
        )
        a = Y.Doc(gc=False)
        a.client_id = 1
        b = Y.Doc(gc=False)
        b.client_id = 2
        pending = []
        for d in (a, b):
            d.on("update", lambda u, o, dd: pending.append(u))
        for step in range(30):
            client_edit(gen, gen.choice((a, b)))
            a_map = a.get_map("meta")
            if gen.random() < 0.3:
                a_map.set(gen.choice("xyz"), step)
            if gen.random() < 0.5 and pending:
                gen.shuffle(pending)
                for u in pending:
                    prov.receive_update("room", u)
                pending.clear()
                prov.flush()
        for u in pending:
            prov.receive_update("room", u)
        prov.flush()
        # the observer NEVER talked to the provider: emissions only
        i = prov.doc_id("room")
        assert observer.get_text("text").to_string() == prov.text("room")
        assert observer.get_map("meta").to_json() == prov.engine.map_json(i, "meta")
        assert not observer.store.pending_clients_struct_refs
        assert not observer.store.pending_stack

    def test_emission_after_demotion_keeps_flowing(self):
        prov = TpuProvider(2)
        observer = Y.Doc(gc=False)
        observer.client_id = 998
        prov.on_update(lambda guid, u: Y.apply_update(observer, u))
        d = Y.Doc(gc=False)
        d.client_id = 3
        d.get_text("text").insert(0, "pre ")
        prov.receive_update("r", Y.encode_state_as_update(d))
        prov.flush()
        # demote mid-stream with a subdocument, then keep editing
        d.get_map("m").set("sub", Y.Doc(guid="child"))
        sv = Y.encode_state_vector(d)
        prov.receive_update("r", Y.encode_state_as_update(d, None))
        prov.flush()
        assert prov.n_fallback_docs == 1
        d.get_text("text").insert(4, "post")
        prov.receive_update("r", Y.encode_state_as_update(d, sv))
        prov.flush()
        assert observer.get_text("text").to_string() == d.get_text("text").to_string()


def test_server_demo_runs():
    """examples/server_demo.py is the documented end-to-end product loop;
    keep it green."""
    import examples.server_demo as demo

    demo.main(n_rooms=4)


class TestReleaseDoc:
    """``release_doc``'s contract does not depend on how the engine
    blanks the slot: beside a provider whose engine still resets by
    whole-table copies (the reference kept in test_tpu_engine.py), it
    returns the same bytes, preserves the same dead letters, and hands
    the slot to a next tenant that is byte-identical with a CPU doc."""

    ROOMS = [f"room{i}" for i in range(8)]

    def _pair(self, mesh):
        from test_tpu_engine import _reset_by_table_copies

        if mesh:
            from yjs_tpu.parallel import doc_mesh

            mesh = doc_mesh(4, backend="cpu")
        prov, ref = (TpuProvider(8, mesh=mesh or None) for _ in range(2))
        _reset_by_table_copies(ref.engine)
        gen = random.Random(27)
        docs = {}
        for k, room in enumerate(self.ROOMS):
            d = Y.Doc(gc=False)
            d.client_id = 500 + k
            for _ in range(20 + 30 * (k % 3)):
                client_edit(gen, d)
            docs[room] = d
            for p in (prov, ref):
                p.receive_update(room, Y.encode_state_as_update(d))
        for p in (prov, ref):
            p.flush()
            # a poisoned update: the room rolls back, the bytes become
            # a dead letter of its slot
            p.receive_update("room2", b"\x01\xff\xff\xff")
            p.flush()
            assert len(p.dead_letters("room2")) == 1
        return prov, ref, docs

    @pytest.mark.parametrize("mesh", [False, True], ids=["one_device", "cpu_mesh"])
    def test_same_bytes_same_letters_same_next_tenant(self, mesh):
        import numpy as np

        prov, ref, docs = self._pair(mesh)
        assert prov.engine._right is not None  # device rows to blank
        gone = ["room2", "room5", "room7"]
        slots = {room: prov.doc_id(room) for room in gone}
        for room in gone:
            final, want = prov.release_doc(room), ref.release_doc(room)
            assert final == want
            assert Y.merge_updates([final]) == Y.merge_updates(
                [Y.encode_state_as_update(docs[room])]
            )
            assert not prov.has_doc(room)
        # the poisoned bytes stayed, named for the room that left
        for p in (prov, ref):
            (letter,) = [e for e in p.dead_letters() if e["doc"] == -1]
            assert "'room2'" in letter["reason"]
        assert [e["reason"] for e in prov.dead_letters()] == [
            e["reason"] for e in ref.dead_letters()
        ]
        # the freed slots are re-let, last released first, and start empty
        for k, room in enumerate(reversed(gone)):
            d = Y.Doc(gc=False)
            d.client_id = 900 + k
            d.get_text("text").insert(0, f"next tenant {k} " * (2 + k))
            for p in (prov, ref):
                assert p.doc_id(f"new{k}") == slots[room]
                assert p.text(f"new{k}") == ""
                p.receive_update(f"new{k}", Y.encode_state_as_update(d))
                p.flush()
            assert prov.text(f"new{k}") == d.get_text("text").to_string()
            assert Y.merge_updates(
                [prov.engine.encode_state_as_update(slots[room])]
            ) == Y.merge_updates([Y.encode_state_as_update(d)])
        for name in ("_right", "_deleted", "_starts"):
            np.testing.assert_array_equal(
                np.asarray(getattr(prov.engine, name)),
                np.asarray(getattr(ref.engine, name)),
            )
