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
    """Sync step 1 + 2 across many docs in single kernel dispatches
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

    def test_state_vectors_batched_matches_per_doc(self):
        docs, eng = self._make_engine(6)
        svs = eng.state_vectors_batched(list(range(6)))
        for i in range(6):
            assert svs[i] == eng.state_vector(i)

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
