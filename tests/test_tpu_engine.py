"""Convergence tests: the TPU batch engine vs the CPU reference core.

The oracle (mirroring tests/testHelper.js compare(), reference
tests/testHelper.js:274-313): after applying the same updates, the device
engine must produce the same document text, the same state vector, and the
same element order as the CPU core.
"""

import random

import numpy as np
import pytest

import yjs_tpu as Y
from yjs_tpu.ops import BatchEngine


def cpu_rows_in_order(doc: Y.Doc, name: str = "text"):
    """(client, clock, length, deleted) per item in list order, split to the
    same granularity the engine reports (runs may differ; flatten to unit
    granularity for comparison)."""
    out = []
    item = doc.get_text(name)._start
    while item is not None:
        for off in range(item.length):
            out.append((item.id.client, item.id.clock + off, item.deleted))
        item = item.right
    return out


def engine_rows_unit(eng: BatchEngine, i: int, name: str = "text"):
    out = []
    for client, clock, length, deleted in eng.rows_in_order(i, name):
        for off in range(length):
            out.append((client, clock + off, deleted))
    return out


def make_doc(client_id: int) -> Y.Doc:
    d = Y.Doc(gc=False)
    d.client_id = client_id
    return d


def assert_engine_matches(eng, doc: Y.Doc, idx=0, name="text"):
    assert eng.text(idx, name) == doc.get_text(name).to_string()
    assert eng.state_vector(idx) == {
        c: v for c, v in Y.get_state_vector(doc.store).items() if v > 0
    }
    assert engine_rows_unit(eng, idx, name) == cpu_rows_in_order(doc, name)


def replay_into_engine(updates, n_docs=1, v2=False):
    eng = BatchEngine(n_docs)
    for i in range(n_docs):
        for u in updates:
            eng.queue_update(i, u, v2=v2)
    eng.flush()
    return eng


def collect_updates(doc: Y.Doc):
    """Record incremental update blobs from a doc."""
    updates = []
    doc.on("update", lambda u, origin, d: updates.append(u))
    return updates


class TestAppendOnly:
    def test_single_client_appends(self):
        doc = make_doc(1)
        updates = collect_updates(doc)
        t = doc.get_text("text")
        for i in range(50):
            t.insert(len(t.to_string()), f"w{i} ")
        eng = replay_into_engine(updates)
        assert_engine_matches(eng, doc)

    def test_full_state_update(self):
        doc = make_doc(1)
        t = doc.get_text("text")
        t.insert(0, "hello world")
        t.insert(5, ", brave")
        eng = replay_into_engine([Y.encode_state_as_update(doc)])
        assert_engine_matches(eng, doc)


class TestConcurrent:
    def test_two_clients_interleaved(self):
        a, b = make_doc(1), make_doc(2)
        ua, ub = collect_updates(a), collect_updates(b)
        a.get_text("text").insert(0, "aaa")
        b.get_text("text").insert(0, "bbb")
        # cross-sync (updates are idempotent+commutative: deliver everything)
        for u in list(ub):
            Y.apply_update(a, u)
        for u in list(ua):
            Y.apply_update(b, u)
        a.get_text("text").insert(3, "XYZ")
        b.get_text("text").insert(1, "qq")
        for u in list(ub):
            Y.apply_update(a, u)
        for u in list(ua):
            Y.apply_update(b, u)
        assert a.get_text("text").to_string() == b.get_text("text").to_string()
        eng = replay_into_engine(ua + ub)
        assert_engine_matches(eng, a)

    def test_concurrent_same_position(self):
        docs = [make_doc(i + 1) for i in range(4)]
        upds = [collect_updates(d) for d in docs]
        for i, d in enumerate(docs):
            d.get_text("text").insert(0, f"<{i}>")
        all_updates = [u for us in upds for u in us]
        for d in docs:
            for u in all_updates:
                Y.apply_update(d, u)
        for d in docs[1:]:
            assert d.get_text("text").to_string() == docs[0].get_text("text").to_string()
        eng = replay_into_engine(all_updates)
        assert_engine_matches(eng, docs[0])

    def test_deletes(self):
        a, b = make_doc(1), make_doc(2)
        ua, ub = collect_updates(a), collect_updates(b)
        a.get_text("text").insert(0, "abcdefgh")
        for u in list(ua):
            Y.apply_update(b, u)
        a.get_text("text").delete(2, 3)
        b.get_text("text").insert(4, "ZZ")
        for u in list(ub):
            Y.apply_update(a, u)
        for u in list(ua):
            Y.apply_update(b, u)
        assert a.get_text("text").to_string() == b.get_text("text").to_string()
        eng = replay_into_engine(ua + ub)
        assert_engine_matches(eng, a)

    def test_out_of_order_delivery_buffers_pending(self):
        doc = make_doc(7)
        updates = collect_updates(doc)
        t = doc.get_text("text")
        t.insert(0, "one ")
        t.insert(4, "two ")
        t.insert(8, "three")
        eng = BatchEngine(1)
        # deliver newest first: must park in pending, then resolve
        eng.queue_update(0, updates[2])
        eng.flush()
        assert eng.has_pending(0)
        eng.queue_update(0, updates[0])
        eng.queue_update(0, updates[1])
        eng.flush()
        assert not eng.has_pending(0)
        assert_engine_matches(eng, doc)


class TestRandomizedConvergence:
    @pytest.mark.parametrize("seed", range(8))
    def test_fuzz_text_edits(self, seed):
        gen = random.Random(seed)
        n_clients = gen.randint(2, 4)
        docs = [make_doc(i + 1) for i in range(n_clients)]
        upds = [collect_updates(d) for d in docs]
        sent: list[int] = [0] * n_clients  # per-doc cursor into peers
        for _ in range(40):
            i = gen.randrange(n_clients)
            d = docs[i]
            t = d.get_text("text")
            ln = len(t.to_string())
            op = gen.random()
            if op < 0.65 or ln == 0:
                pos = gen.randint(0, ln)
                t.insert(pos, gen.choice(["a", "bb", "ccc", "x", "🙂"]))
            else:
                pos = gen.randrange(ln)
                t.delete(pos, min(gen.randint(1, 3), ln - pos))
            if gen.random() < 0.3:
                # deliver a random peer's pending updates to a random doc
                src = gen.randrange(n_clients)
                dst = gen.randrange(n_clients)
                for u in upds[src]:
                    Y.apply_update(docs[dst], u)
        # final full sync
        all_updates = [u for us in upds for u in us]
        gen.shuffle(all_updates)
        for d in docs:
            for u in all_updates:
                Y.apply_update(d, u)
        for d in docs[1:]:
            assert d.get_text("text").to_string() == docs[0].get_text("text").to_string()
        eng = replay_into_engine(all_updates)
        assert not eng.has_pending(0)
        assert_engine_matches(eng, docs[0])

    def test_v2_encoding(self):
        doc = make_doc(3)
        t = doc.get_text("text")
        t.insert(0, "hello")
        t.insert(2, "XX")
        t.delete(1, 3)
        eng = BatchEngine(1)
        eng.queue_update(0, Y.encode_state_as_update_v2(doc), v2=True)
        eng.flush()
        assert_engine_matches(eng, doc)


class TestBatch:
    def test_many_docs_one_flush(self):
        n = 16
        docs = [make_doc(100 + i) for i in range(n)]
        eng = BatchEngine(n)
        for i, d in enumerate(docs):
            t = d.get_text("text")
            t.insert(0, f"doc-{i}:")
            t.insert(len(t.to_string()), "payload" * (i % 3 + 1))
            t.delete(0, 2)
            eng.queue_update(i, Y.encode_state_as_update(d))
        eng.flush()
        for i, d in enumerate(docs):
            assert eng.text(i) == d.get_text("text").to_string()
            assert_engine_matches(eng, d, idx=i)

    def test_incremental_flushes(self):
        doc = make_doc(5)
        updates = collect_updates(doc)
        t = doc.get_text("text")
        eng = BatchEngine(1)
        for step in range(6):
            t.insert(len(t.to_string()) // 2, f"[{step}]")
            if step % 2 == 1:
                t.delete(0, 1)
            for u in updates:
                eng.queue_update(0, u)
            updates.clear()
            eng.flush()
            assert_engine_matches(eng, doc)


class TestFallback:
    def test_map_and_multiroot_stay_on_device(self):
        doc = make_doc(9)
        doc.get_map("m").set("k", 1)
        doc.get_text("text").insert(0, "hi")
        doc.get_text("notes").insert(0, "n0")
        eng = BatchEngine(1)
        eng.queue_update(0, Y.encode_state_as_update(doc))
        eng.flush()
        assert 0 not in eng.fallback
        assert eng.text(0) == "hi"
        assert eng.text(0, "notes") == "n0"
        assert eng.map_json(0, "m") == {"k": 1}

    def test_subdoc_demotes_to_cpu(self):
        doc = make_doc(9)
        doc.get_map("m").set("sub", Y.Doc(guid="child"))  # ContentDoc
        doc.get_text("text").insert(0, "hi")
        eng = BatchEngine(1)
        eng.queue_update(0, Y.encode_state_as_update(doc))
        eng.flush()
        assert 0 in eng.fallback
        assert eng.demotions[0]["reason"] == "subdocument (content ref 9)"
        assert eng.text(0) == "hi"

    def test_mixed_demotions_inside_chunked_flush(self, monkeypatch):
        """Docs demoting mid-chunk (subdoc updates) must not disturb the
        rest of the batched flush: per-doc rc routing in prepare_many."""
        from yjs_tpu.ops.native_mirror import native_plan_available

        if not native_plan_available():
            pytest.skip("chunked batched flush requires the native planner")
        monkeypatch.setenv("YTPU_FLUSH_CHUNK", "8")
        n = 20
        eng = BatchEngine(n)
        docs = [make_doc(200 + i) for i in range(n)]
        for i, d in enumerate(docs):
            d.get_text("text").insert(0, f"doc{i} body")
            if i % 7 == 3:  # 3, 10, 17 -> one demotion per chunk
                d.get_map("m").set("sub", Y.Doc(guid=f"child{i}"))
            eng.queue_update(i, Y.encode_state_as_update(d))
        eng.flush()
        demoted = {i for i in range(n) if i % 7 == 3}
        assert set(eng.fallback) == demoted
        assert len(eng.demotions) == len(demoted)
        for i in range(n):
            if i in demoted:
                assert eng.text(i) == docs[i].get_text("text").to_string(), i
            else:
                assert_engine_matches(eng, docs[i], i)
        # native docs keep flowing through later chunked flushes
        for i, d in enumerate(docs):
            d.get_text("text").insert(0, "more ")
            eng.queue_update(i, Y.encode_state_as_update(d))
        eng.flush()
        assert set(eng.fallback) == demoted  # no new demotions
        for i in range(n):
            if i in demoted:
                assert eng.text(i) == docs[i].get_text("text").to_string(), i
            else:
                assert_engine_matches(eng, docs[i], i)


class TestNestedTypes:
    """Nested shared types integrate on device as parent-row-keyed segments
    (reference ContentType.js); only subdocuments fall back."""

    def test_nested_map_array_text_stay_on_device(self):
        a = make_doc(5)
        m = a.get_map("root")
        inner = Y.YMap()
        m.set("inner", inner)
        inner.set("k", 42)
        arr = a.get_array("arr")
        nt = Y.YText()
        arr.insert(0, ["plain", nt])
        nt.insert(0, "nested text")
        nt.insert(6, "🙂")
        eng = BatchEngine(1)
        eng.queue_update(0, Y.encode_state_as_update(a))
        eng.flush()
        assert not eng.fallback
        assert eng.map_json(0, "root") == a.get_map("root").to_json()
        assert eng.to_json(0, "arr") == a.get_array("arr").to_json()
        # the mirror's wire export reconstructs the nested state
        d = Y.Doc(gc=False)
        Y.apply_update(d, eng.encode_state_as_update(0))
        assert d.get_map("root").to_json() == a.get_map("root").to_json()
        assert d.get_array("arr").to_json() == a.get_array("arr").to_json()

    def test_parent_arrives_after_children(self):
        # children reference the type item causally: delivering them first
        # must park them in pending, not corrupt state
        a = make_doc(6)
        sv0 = Y.encode_state_vector(a)
        nt = Y.YText()
        a.get_map("root").set("t", nt)
        u_parent = Y.encode_state_as_update(a, sv0)
        sv1 = Y.encode_state_vector(a)
        nt.insert(0, "abc")
        u_children = Y.encode_state_as_update(a, sv1)
        eng = BatchEngine(1)
        eng.queue_update(0, u_children)
        eng.flush()
        assert eng.has_pending(0)
        eng.queue_update(0, u_parent)
        eng.flush()
        assert not eng.has_pending(0)
        assert eng.map_json(0, "root") == {"t": "abc"}

    def test_deleting_type_deletes_subtree(self):
        a = make_doc(7)
        arr = a.get_array("arr")
        nested = Y.YArray()
        arr.insert(0, [nested, "tail"])
        nested.insert(0, [1, 2, 3])
        eng = BatchEngine(1)
        eng.queue_update(0, Y.encode_state_as_update(a))
        eng.flush()
        assert eng.to_json(0, "arr") == [[1, 2, 3], "tail"]
        sv = Y.encode_state_vector(a)
        arr.delete(0, 1)  # deletes the nested type + its subtree
        eng.queue_update(0, Y.encode_state_as_update(a, sv))
        eng.flush()
        assert eng.to_json(0, "arr") == a.get_array("arr").to_json() == ["tail"]
        d = Y.Doc(gc=False)
        Y.apply_update(d, eng.encode_state_as_update(0))
        assert d.get_array("arr").to_json() == ["tail"]

    def test_gc_compaction_preserves_nested_parent_rows(self):
        # a deleted nested type row must survive GC compaction un-merged:
        # its children's wire parent id is that row's identity
        a = make_doc(8)
        arr = a.get_array("arr")
        arr.insert(0, ["s0", "s1", "s2"])
        nested = Y.YMap()
        arr.insert(3, [nested])
        nested.set("k", 1)
        arr.insert(4, ["t0", "t1", "t2"])
        eng = BatchEngine(1, gc=True, compact_min_rows=4)
        eng.queue_update(0, Y.encode_state_as_update(a))
        eng.flush()
        sv = Y.encode_state_vector(a)
        arr.delete(0, 7)  # everything, nested type included
        eng.queue_update(0, Y.encode_state_as_update(a, sv))
        eng.flush()
        # append until compaction triggers with the tombstoned type inside
        t = a.get_text("text")
        for i in range(12):
            sv = Y.encode_state_vector(a)
            t.insert(len(t.to_string()), f"w{i} ")
            eng.queue_update(0, Y.encode_state_as_update(a, sv))
            eng.flush()
        assert eng.last_compaction, "compaction should have run"
        # exports still work and round-trip
        assert eng.to_json(0, "arr") == a.get_array("arr").to_json() == []
        d = Y.Doc(gc=False)
        Y.apply_update(d, eng.encode_state_as_update(0))
        assert d.get_array("arr").to_json() == []
        assert d.get_text("text").to_string() == t.to_string()

    @pytest.mark.parametrize("seed", range(4))
    def test_fuzz_nested_ops(self, seed):
        gen = random.Random(5000 + seed)
        n_clients = 3
        docs = [make_doc(i + 1) for i in range(n_clients)]
        upds = [collect_updates(d) for d in docs]
        # everyone starts from a shared nested skeleton
        nt = Y.YText()
        na = Y.YArray()
        docs[0].get_map("root").set("text", nt)
        docs[0].get_map("root").set("list", na)
        for d in docs[1:]:
            Y.apply_update(d, Y.encode_state_as_update(docs[0]))
        for _ in range(40):
            i = gen.randrange(n_clients)
            d = docs[i]
            op = gen.random()
            root = d.get_map("root")
            if op < 0.35:
                t = root.get("text")
                if t is not None:
                    ln = len(t.to_string())
                    if gen.random() < 0.7 or ln == 0:
                        t.insert(gen.randint(0, ln), gen.choice(["x", "yz "]))
                    else:
                        pos = gen.randrange(ln)
                        t.delete(pos, min(gen.randint(1, 2), ln - pos))
            elif op < 0.6:
                arr = root.get("list")
                if arr is not None:
                    if gen.random() < 0.7 or len(arr.to_json()) == 0:
                        arr.insert(
                            gen.randint(0, len(arr.to_json())),
                            [gen.randrange(100)],
                        )
                    else:
                        arr.delete(gen.randrange(len(arr.to_json())), 1)
            elif op < 0.8:
                root.set(gen.choice("abc"), gen.randrange(100))
            else:
                inner = Y.YMap()
                root.set(gen.choice("mn"), inner)
            if gen.random() < 0.3:
                src, dst = gen.randrange(n_clients), gen.randrange(n_clients)
                for u in upds[src]:
                    Y.apply_update(docs[dst], u)
        all_updates = [u for us in upds for u in us]
        gen.shuffle(all_updates)
        for d in docs:
            for u in all_updates:
                Y.apply_update(d, u)
        eng = replay_into_engine(all_updates)
        assert not eng.fallback, eng.demotions
        ref = docs[0]
        for other in docs[1:]:
            assert other.get_map("root").to_json() == ref.get_map("root").to_json()
        assert eng.map_json(0, "root") == ref.get_map("root").to_json()
        # wire export round-trips the full nested state
        d2 = Y.Doc(gc=False)
        Y.apply_update(d2, eng.encode_state_as_update(0))
        assert d2.get_map("root").to_json() == ref.get_map("root").to_json()

    def test_concurrent_nested_edits_converge(self):
        a, b = make_doc(1), make_doc(2)
        nt = Y.YText()
        a.get_map("root").set("doc", nt)
        Y.apply_update(b, Y.encode_state_as_update(a))
        # concurrent edits in the nested text
        a.get_map("root").get("doc").insert(0, "AA")
        b.get_map("root").get("doc").insert(0, "BB")
        ua, ub = Y.encode_state_as_update(a), Y.encode_state_as_update(b)
        Y.apply_update(a, ub)
        Y.apply_update(b, ua)
        assert (
            a.get_map("root").to_json() == b.get_map("root").to_json()
        )
        eng = BatchEngine(1)
        eng.queue_update(0, ub)
        eng.queue_update(0, ua)
        eng.flush()
        assert not eng.fallback
        assert eng.map_json(0, "root") == a.get_map("root").to_json()


class TestUpdateLogCompaction:
    def test_log_bounded_and_demotion_replays_snapshot(self):
        """After >64 pending-free flushes the demotion-replay log collapses
        to one columnar export; a later demotion must still rebuild the full
        doc from it (engine._update_log compaction)."""
        doc = make_doc(31)
        t = doc.get_text("text")
        eng = BatchEngine(1)
        sv = None
        for step in range(70):
            t.insert(len(t.to_string()), f"w{step} ")
            u = Y.encode_state_as_update(doc, sv)
            sv = Y.encode_state_vector(doc)
            eng.queue_update(0, u)
            eng.flush()
        # compacted at the 65th flush to [snapshot], then the tail appended
        assert len(eng._update_log[0]) <= 6
        assert_engine_matches(eng, doc)
        # demotion after compaction replays the snapshot + tail correctly
        doc.get_map("m").set("sub", Y.Doc(guid="kid"))  # unsupported -> demote
        t.insert(0, "head ")
        eng.queue_update(0, Y.encode_state_as_update(doc, sv))
        eng.flush()
        assert 0 in eng.fallback
        assert eng.text(0) == t.to_string()


class TestMapConvergence:
    """Device-path YMap LWW (ported MAP_MODS fuzz, reference
    tests/y-map.tests.js:438-481): random sets/deletes from several clients
    under random delivery must converge to the CPU core's winners."""

    @pytest.mark.parametrize("seed", range(6))
    def test_fuzz_map_ops(self, seed):
        gen = random.Random(1000 + seed)
        n_clients = gen.randint(2, 4)
        docs = [make_doc(i + 1) for i in range(n_clients)]
        upds = [collect_updates(d) for d in docs]
        keys = ["a", "b", "c", "d"]
        values = [0, 1, "s", 3.5, None, True, [1, 2], {"x": 1}]
        for _ in range(35):
            i = gen.randrange(n_clients)
            m = docs[i].get_map("map")
            if gen.random() < 0.8:
                m.set(gen.choice(keys), gen.choice(values))
            else:
                m.delete(gen.choice(keys))
            if gen.random() < 0.3:
                src, dst = gen.randrange(n_clients), gen.randrange(n_clients)
                for u in upds[src]:
                    Y.apply_update(docs[dst], u)
        all_updates = [u for us in upds for u in us]
        gen.shuffle(all_updates)
        for d in docs:
            for u in all_updates:
                Y.apply_update(d, u)
        for d in docs[1:]:
            assert d.get_map("map").to_json() == docs[0].get_map("map").to_json()
        eng = replay_into_engine(all_updates)
        assert not eng.has_pending(0)
        assert eng.map_json(0, "map") == docs[0].get_map("map").to_json()

    @pytest.mark.parametrize("seed", range(4))
    def test_fuzz_mixed_text_map_multiroot(self, seed):
        gen = random.Random(2000 + seed)
        n_clients = 3
        docs = [make_doc(i + 1) for i in range(n_clients)]
        upds = [collect_updates(d) for d in docs]
        for _ in range(30):
            i = gen.randrange(n_clients)
            d = docs[i]
            op = gen.random()
            if op < 0.4:
                t = d.get_text(gen.choice(["text", "notes"]))
                ln = len(t.to_string())
                if gen.random() < 0.7 or ln == 0:
                    t.insert(gen.randint(0, ln), gen.choice(["x", "yy", "zz "]))
                else:
                    pos = gen.randrange(ln)
                    t.delete(pos, min(gen.randint(1, 2), ln - pos))
            elif op < 0.8:
                d.get_map("map").set(gen.choice("abc"), gen.randrange(100))
            else:
                d.get_map("map").delete(gen.choice("abc"))
            if gen.random() < 0.25:
                src, dst = gen.randrange(n_clients), gen.randrange(n_clients)
                for u in upds[src]:
                    Y.apply_update(docs[dst], u)
        all_updates = [u for us in upds for u in us]
        gen.shuffle(all_updates)
        for d in docs:
            for u in all_updates:
                Y.apply_update(d, u)
        eng = replay_into_engine(all_updates)
        ref = docs[0]
        for name in ("text", "notes"):
            assert eng.text(0, name) == ref.get_text(name).to_string()
            assert_engine_matches(eng, ref, name=name)
        assert eng.map_json(0, "map") == ref.get_map("map").to_json()


class TestChainStitching:
    """Typing chains across clients: every run's origin is another run's
    tail, and a concurrent insert mid-chain breaks it — the planner's
    links must leave the device with the CPU core's order either way."""

    def test_sequential_typing_converges(self):
        # alternating clients typing at their own cursors, fully synced:
        # every run's origin is a prior run's tail
        a, b = make_doc(1), make_doc(2)
        for i in range(30):
            d, o = (a, b) if i % 2 == 0 else (b, a)
            t = d.get_text("text")
            t.insert(len(t.to_string()), f"w{i} ")
            Y.apply_update(o, Y.encode_state_as_update(d, Y.encode_state_vector(o)))
        eng = replay_into_engine([Y.encode_state_as_update(a)])
        assert_engine_matches(eng, a)

    def test_concurrent_insert_breaks_chain_but_converges(self):
        # two clients insert concurrently at the same position mid-chain:
        # the planner's YATA walk orders them, not the chain's shortcut
        a, b = make_doc(1), make_doc(2)
        a.get_text("text").insert(0, "base ")
        Y.apply_update(b, Y.encode_state_as_update(a))
        # concurrent: both extend + insert at position 2
        a.get_text("text").insert(5, "AA ")
        a.get_text("text").insert(8, "A2 ")
        b.get_text("text").insert(5, "BB ")
        b.get_text("text").insert(2, "X")
        ua, ub = Y.encode_state_as_update(a), Y.encode_state_as_update(b)
        for d, u in ((a, ub), (b, ua)):
            Y.apply_update(d, u)
        assert a.get_text("text").to_string() == b.get_text("text").to_string()
        eng = replay_into_engine([ua, ub])
        assert_engine_matches(eng, a)


class TestCompaction:
    """Run-merge + GC keep the device table bounded (the
    engine-side analogue of reference Transaction.js:165-238,299-332)."""

    def _long_append_trace(self, eng, doc, n_flushes, per_flush=20):
        t = doc.get_text("text")
        sv = None
        for _ in range(n_flushes):
            for _ in range(per_flush):
                t.insert(len(t.to_string()), "w ")
            u = Y.encode_state_as_update(doc, sv)
            sv = Y.encode_state_vector(doc)
            eng.queue_update(0, u)
            eng.flush()

    def test_append_trace_rows_bounded(self):
        doc = make_doc(41)
        eng = BatchEngine(1, compact_min_rows=64)
        self._long_append_trace(eng, doc, 80)  # 1600 inserts, 80 flushes
        m = eng.mirrors[0]
        # contiguous same-client typing collapses to a handful of runs
        assert m.n_rows < 100, m.n_rows
        assert eng.last_compaction is not None
        assert_engine_matches(eng, doc)

    def test_delete_heavy_trace_with_gc(self):
        doc = make_doc(42)
        eng = BatchEngine(1, gc=True, compact_min_rows=64)
        t = doc.get_text("text")
        sv = None
        for step in range(40):
            for _ in range(15):
                t.insert(len(t.to_string()), "xy")
            t.delete(0, len(t.to_string()) - 4)  # tombstone almost everything
            u = Y.encode_state_as_update(doc, sv)
            sv = Y.encode_state_vector(doc)
            eng.queue_update(0, u)
            eng.flush()
        m = eng.mirrors[0]
        assert m.n_rows < 120, m.n_rows
        # gc dropped tombstone payloads: deleted rows became ContentDeleted
        # (wire ref 1; backend-neutral — the native mirror realizes lazily)
        n_tombstone = sum(1 for ref in m.row_content_ref if ref == 1)
        assert n_tombstone > 0
        assert eng.text(0) == t.to_string()

    def test_convergence_after_compaction(self):
        """Edits arriving after a compaction must still integrate and sync
        correctly (origins point inside merged runs -> re-split)."""
        doc = make_doc(43)
        eng = BatchEngine(1, compact_min_rows=64)
        self._long_append_trace(eng, doc, 30)
        # a second client edits concurrently against the synced state
        remote = make_doc(900)
        Y.apply_update(remote, Y.encode_state_as_update(doc))
        remote.get_text("text").insert(5, "[mid]")
        remote.get_text("text").delete(20, 6)
        u = Y.encode_state_as_update(remote, Y.encode_state_vector(doc))
        Y.apply_update(doc, u)
        eng.queue_update(0, u)
        eng.flush()
        assert_engine_matches(eng, doc)
        # and the mirror's wire export round-trips into a fresh CPU doc
        fresh = Y.Doc(gc=False)
        Y.apply_update(fresh, eng.encode_state_as_update(0))
        assert fresh.get_text("text").to_string() == doc.get_text("text").to_string()


class TestCompactionScale:
    def test_batch_compaction_no_readback(self):
        """Compacting a whole batch of fragmented docs converges and
        shrinks rows — decided purely from mirror state (the device
        gather that bounded r3's 100k-doc scaling is gone; this test
        drives the rebuild_compacted_self path for every doc at once)."""
        import yjs_tpu as Y

        n_docs = 256
        eng = BatchEngine(n_docs, compact_min_rows=8)
        docs = [Y.Doc(gc=False) for _ in range(n_docs)]
        svs = [None] * n_docs
        # several rounds of tiny appends -> heavily fragmented run tables
        for rnd in range(10):
            for i, d in enumerate(docs):
                t = d.get_text("text")
                t.insert(len(t.to_string()), f"r{rnd}d{i % 7},")
                u = Y.encode_state_as_update(d, svs[i])
                svs[i] = Y.encode_state_vector(d)
                eng.queue_update(i, u)
            eng.flush()
        assert eng.last_compaction, "batch compaction should have fired"
        compacted_docs = {c["doc"] for c in eng.last_compaction}
        assert len(compacted_docs) > n_docs // 2
        assert all(
            c["rows_after"] <= c["rows_before"] for c in eng.last_compaction
        )
        for i in (0, 7, 100, n_docs - 1):
            assert eng.text(i) == docs[i].get_text("text").to_string()
        # post-compaction traffic still integrates correctly
        for i, d in enumerate(docs):
            t = d.get_text("text")
            t.insert(0, "HEAD:")
            u = Y.encode_state_as_update(d, svs[i])
            svs[i] = Y.encode_state_vector(d)
            eng.queue_update(i, u)
        eng.flush()
        for i in (0, 55, n_docs - 1):
            assert eng.text(i) == docs[i].get_text("text").to_string()


def _stage_full_width(eng):
    """The staging as it was before a block followed its rooms, kept as
    the reference: every block ``cap + 1`` and ``seg_cap + 1`` wide."""

    def full(todo, rebuild, n_rows, n_segs):
        k = len(todo)
        new_right = np.full((k, eng._cap + 1), -1, np.int32)
        new_deleted = np.zeros((k, eng._cap + 1), bool)
        new_starts = np.full((k, eng._seg_cap + 1), -1, np.int32)
        for j, i in enumerate(todo):
            r, d, h = rebuild(i)
            new_right[j, : len(r)] = r
            new_deleted[j, : len(d)] = d
            new_starts[j, : len(h)] = h
        eng._dispatch(
            "rows", eng._put_r(np.asarray(todo, np.int32)),
            eng._put_r(new_right), eng._put_r(new_deleted),
            eng._put_r(new_starts),
        )

    eng._scatter_rebuilt = full


def _engine_pair(n, mesh):
    """Two engines alike, on one device or on the tests' four-device
    CPU mesh: the one under test and the one a reference is put into."""
    if mesh:
        from yjs_tpu.parallel import doc_mesh

        try:
            mesh = doc_mesh(4, backend="cpu")
        except RuntimeError as e:  # YTPU_TEST_PLATFORM=tpu: one chip
            pytest.skip(f"no CPU mesh beside this backend: {e}")
    # no compaction but the ones the case asks for
    kw = dict(gc=True, compact_min_rows=1 << 30, mesh=mesh or None)
    return BatchEngine(n, **kw), BatchEngine(n, **kw)


class TestStagedWidth:
    """A compaction or hydration stages a block as wide as its rooms
    need (``_scatter_rebuilt``), and must leave the device tables, over
    their whole ``cap + 1``, as full-width staging leaves them."""

    N = 16
    WIDE = 3  # the room that sets the table width
    SMALL = [*range(WIDE), *range(WIDE + 1, N)]
    LONG = {WIDE: 700}  # rooms prepended a character at a time: no row merges
    CAP = 1024

    def _engines(self, mesh):
        eng, ref = _engine_pair(self.N, mesh)
        _stage_full_width(ref)
        return eng, ref

    def _fragment(self, engines):
        """One wide room (700 rows, cap 1024) and fifteen small ones of
        some hundred rows that a rebuild merges into a few: typed at the
        end a keystroke an update, erased again from the front."""
        docs = [make_doc(500 + i) for i in range(self.N)]
        typed: list[list[bytes]] = [[] for _ in docs]
        for d, out in zip(docs, typed):
            d.on("update", lambda u, _origin, _doc, out=out: out.append(u))
        for rnd in range(14):
            for i, d in enumerate(docs):
                t = d.get_text("text")
                if i not in self.LONG:
                    for ch in f"r{rnd:02d}d{i % 5}, "[: 6 + i % 3]:
                        t.insert(len(t.to_string()), ch)
                    if rnd % 3 == 2:
                        t.delete(0, 4 + i % 3)
                elif rnd == 0:
                    for _ in range(self.LONG[i]):
                        t.insert(0, "x")
                for e in engines:
                    for u in typed[i]:
                        e.queue_update(i, u)
                typed[i].clear()
            for e in engines:
                e.flush()
        return docs

    @staticmethod
    def _tables(eng):
        return [np.asarray(t) for t in (eng._right, eng._deleted, eng._starts)]

    def _assert_same_tables(self, eng, ref):
        assert eng._cap == ref._cap == self.CAP
        for got, want in zip(self._tables(eng), self._tables(ref)):
            assert got.shape == want.shape
            np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("mesh", [False, True], ids=["one_device", "cpu_mesh"])
    @pytest.mark.parametrize("case", ["narrow", "one_wide", "hydrate"])
    def test_narrow_staging_leaves_full_width_tables(self, case, mesh):
        eng, ref = self._engines(mesh)
        docs = self._fragment((eng, ref))
        todo = self.SMALL if case == "narrow" else list(range(self.N))
        for e in (eng, ref):
            stats = e.compact_docs(todo)
            assert [s["doc"] for s in stats] == todo
        small = [s for s in stats if s["doc"] != self.WIDE]
        # a stale tail to blank: the rooms shrank to under half their rows
        assert all(
            s["rows_after"] < 32 and 64 < s["rows_before"] <= 128
            for s in small
        )
        if case == "hydrate":
            # park four rooms, blank their slots, bring them back into
            # each other's: the next flush scatters them through the
            # same staging
            moved = [1, 6, 9, 14]
            for e in (eng, ref):
                parked = [e.export_doc_columns(i) for i in moved]
                for i in moved:
                    e.reset_doc(i)
                for i, m in zip(moved, reversed(parked)):
                    e.hydrate_doc_columns(i, m)
                e.flush()
            docs[1], docs[14] = docs[14], docs[1]
            docs[6], docs[9] = docs[9], docs[6]
        self._assert_same_tables(eng, ref)
        right, deleted, _starts = self._tables(eng)
        for s in small:
            # every cell behind a rebuilt room is at fill
            assert (right[s["doc"], s["rows_after"]:] == -1).all()
            assert not deleted[s["doc"], s["rows_after"]:].any()
        for i in (0, 1, self.WIDE, 9, self.N - 1):
            assert eng.text(i) == docs[i].get_text("text").to_string()
        if case == "hydrate":
            # the flush reports the fifteen small rooms' block, the wide
            # room's own and the four hydrated rooms'
            m = eng.last_flush_metrics
            assert m["rows_staged_bytes"] == (
                15 * (128 * 5 + 8 * 4) + (1024 * 5 + 8 * 4)
                + 4 * (64 * 5 + 8 * 4)
            )
            assert m["rows_staged_blocks"] == 3

    def test_staged_and_held_bytes_read_what_the_shapes_say(self):
        eng, _ref = self._engines(False)
        self._fragment((eng,))
        eng.flush()
        assert eng.last_flush_metrics["rows_staged_bytes"] == 0
        assert eng.last_flush_metrics["rows_held_bytes"] == 0
        stats = eng.compact_docs(self.SMALL)
        wide = eng.compact_docs([self.WIDE])
        eng.flush()  # the next flush reports what was staged since the last
        m = eng.last_flush_metrics
        k = len(self.SMALL)
        # 15 rooms that held 65 to 128 rows and one list head: 128 and
        # 8 wide; the wide room alone: 1024 (the bucket of its 700 rows)
        assert m["rows_staged_bytes"] == (
            k * (128 * 5 + 8 * 4) + (1024 * 5 + 8 * 4)
        )
        rows = sum(s["rows_after"] for s in stats + wide)
        heads = sum(eng.mirrors[i].n_segs for i in range(self.N))
        assert m["rows_held_bytes"] == rows * 5 + heads * 4
        reg = eng.obs.registry
        assert reg.get("ytpu_flush_rows_staged_bytes_total").value == (
            m["rows_staged_bytes"]
        )
        assert reg.get("ytpu_flush_rows_held_bytes_total").value == (
            m["rows_held_bytes"]
        )
        assert m["rows_staged_blocks"] == 2
        assert reg.get("ytpu_flush_rows_staged_blocks_total").value == 2
        eng.flush()
        assert eng.last_flush_metrics["rows_staged_bytes"] == 0
        assert eng.last_flush_metrics["rows_staged_blocks"] == 0


def _stage_one_block(eng):
    """The staging as it was before rooms were staged in width classes,
    kept as the reference: one block for all of ``todo``, as wide as its
    widest room."""
    from yjs_tpu.ops.engine import _bucket

    def one_block(todo, rebuild, n_rows, n_segs):
        k = len(todo)
        w = min(_bucket(max(n_rows)), eng._cap + 1)
        ws = min(_bucket(max(n_segs), 8), eng._seg_cap + 1)
        new_right = np.full((k, w), -1, np.int32)
        new_deleted = np.zeros((k, w), bool)
        new_starts = np.full((k, ws), -1, np.int32)
        for j, i in enumerate(todo):
            r, d, h = rebuild(i)
            new_right[j, : len(r)] = r
            new_deleted[j, : len(d)] = d
            new_starts[j, : len(h)] = h
        eng._dispatch(
            "rows", eng._put_r(np.asarray(todo, np.int32)),
            eng._put_r(new_right), eng._put_r(new_deleted),
            eng._put_r(new_starts),
        )

    eng._scatter_rebuilt = one_block


class TestWidthClasses:
    """A ``todo`` of short, a few times longer and twenty times longer
    rooms is staged in width classes (a class: the rooms of its widest
    room's ``_bucket(rows)`` and of half of it), one block a class, and
    must leave the three device tables, over their whole ``cap + 1``, as
    the single block as wide as the widest room left them."""

    N = TestStagedWidth.N
    WIDE = TestStagedWidth.WIDE
    MID = 10
    NEAR = 12  # an octave under MID: shares MID's block
    LONG = {WIDE: 1500, MID: 300, NEAR: 200}
    CAP = 2048
    _fragment = TestStagedWidth._fragment
    _tables = staticmethod(TestStagedWidth._tables)
    _assert_same_tables = TestStagedWidth._assert_same_tables

    def _engines(self, mesh):
        eng, ref = _engine_pair(self.N, mesh)
        _stage_one_block(ref)
        return eng, ref

    @pytest.mark.parametrize("mesh", [False, True], ids=["one_device", "cpu_mesh"])
    @pytest.mark.parametrize("case", ["compact", "hydrate"])
    def test_width_classes_leave_the_tables_of_one_block(self, case, mesh):
        eng, ref = self._engines(mesh)
        docs = self._fragment((eng, ref))
        todo = list(range(self.N))
        before = {i: eng.mirrors[i].n_rows for i in todo}
        assert before[self.WIDE] == 1500 and before[self.MID] == 300
        assert before[self.NEAR] == 200
        assert all(
            64 < n <= 128 for i, n in before.items() if i not in self.LONG
        )
        for e in (eng, ref):
            stats = e.compact_docs(todo)
            # the classes are staged narrowest first; the stats stay in
            # the order asked for
            assert [s["doc"] for s in stats] == todo
            assert [s["rows_before"] for s in stats] == list(before.values())
        if case == "hydrate":
            # park a room of each width, blank the slots, bring each back
            # into another's: one pending hydration a class
            moved = [1, self.MID, self.WIDE, 14]
            for e in (eng, ref):
                parked = [e.export_doc_columns(i) for i in moved]
                for i in moved:
                    e.reset_doc(i)
                for i, m in zip(moved, reversed(parked)):
                    e.hydrate_doc_columns(i, m)
                e.flush()
            docs[1], docs[14] = docs[14], docs[1]
            docs[self.MID], docs[self.WIDE] = docs[self.WIDE], docs[self.MID]
        self._assert_same_tables(eng, ref)
        right, deleted, _starts = self._tables(eng)
        for i in todo:
            n = eng.mirrors[i].n_rows
            assert (right[i, n:] == -1).all() and not deleted[i, n:].any()
        for i in (0, 1, self.WIDE, self.MID, 14, self.N - 1):
            assert eng.text(i) == docs[i].get_text("text").to_string()
        m = eng.last_flush_metrics
        if case == "hydrate":
            # the compaction's three blocks (13 rooms 128 wide, the two
            # of 300 and 200 rows 512 wide, one 2048) and the
            # hydrations' three (two rooms 64 wide)
            assert m["rows_staged_blocks"] == 6
            assert m["rows_staged_bytes"] == sum(
                k * (w * 5 + 8 * 4)
                for k, w in ((13, 128), (2, 512), (1, 2048), (2, 64), (1, 512), (1, 2048))
            )
        else:
            eng.flush()
            m = eng.last_flush_metrics
            assert m["rows_staged_blocks"] == 3
            assert m["rows_staged_bytes"] == (
                13 * (128 * 5 + 8 * 4) + 2 * (512 * 5 + 8 * 4) + (2048 * 5 + 8 * 4)
            )

    def test_one_class_is_one_block(self):
        """Rooms of one width class (here an octave apart: 200 rows
        beside about a hundred): exactly the block it was, one
        ``scatter_rows`` of ``len(todo) x w``."""
        eng, _ref = self._engines(False)
        self._fragment((eng,))
        eng.flush()
        small = [i for i in range(self.N) if i not in (self.WIDE, self.MID)]
        calls = []
        dispatch = eng._dispatch
        eng._dispatch = lambda kind, *a, **kw: (
            calls.append((kind, *(x.shape for x in a))), dispatch(kind, *a, **kw)
        )
        eng.compact_docs(small)
        assert calls == [("rows", (14,), (14, 256), (14, 256), (14, 8))]
        eng._dispatch = dispatch
        eng.flush()
        assert eng.last_flush_metrics["rows_staged_blocks"] == 1


def _reset_by_table_copies(eng):
    """``reset_doc``'s device half as it was before a release wrote rows,
    kept as the plain reference: three ``.at[doc].set`` outside ``jit``,
    each a new whole table."""

    def reset(doc):
        tables = eng._right, eng._deleted, eng._starts
        eng._right = None  # the host half alone: no table, no blanking
        BatchEngine.reset_doc(eng, doc)
        eng._right = tables[0].at[doc].set(-1)
        eng._deleted = tables[1].at[doc].set(False)
        eng._starts = tables[2].at[doc].set(-1)

    eng.reset_doc = reset


class TestReleaseBlanking:
    """``reset_doc`` blanks a slot's rows by one donated program
    (``kernels.blank_rows``) and must leave the device tables, over
    their whole ``cap + 1``, as the whole-table copies left them."""

    N = TestStagedWidth.N
    WIDE = TestStagedWidth.WIDE
    # rooms of different widths, one at least in each block of four
    # slots (a chip's share on the tests' four-device mesh)
    GONE = [1, WIDE, 6, 9, 14, 15]

    def _engines(self, mesh):
        eng, ref = _engine_pair(self.N, mesh)
        _reset_by_table_copies(ref)
        return eng, ref

    def _grown(self, mesh):
        eng, ref = self._engines(mesh)
        docs = TestStagedWidth()._fragment((eng, ref))
        for e in (eng, ref):
            # something in the scratch column to blank as well
            e._right = e._right.at[:, -1].set(7)
        return eng, ref, docs

    @staticmethod
    def _pointers(eng):
        return [
            [s.data.unsafe_buffer_pointer() for s in t.addressable_shards]
            for t in (eng._right, eng._deleted, eng._starts)
        ]

    @pytest.mark.parametrize("mesh", [False, True], ids=["one_device", "cpu_mesh"])
    def test_reset_leaves_the_tables_of_whole_table_copies(self, mesh):
        eng, ref, docs = self._grown(mesh)
        kept = TestStagedWidth._tables(eng)
        widths = {eng.mirrors[i].n_rows for i in self.GONE}
        assert len(widths) >= 3 and max(widths) >= 700
        for e in (eng, ref):
            for i in self.GONE:
                e.reset_doc(i)
        got, want = TestStagedWidth._tables(eng), TestStagedWidth._tables(ref)
        for g, w, k, fill in zip(got, want, kept, (-1, False, -1)):
            assert g.shape == w.shape == k.shape
            np.testing.assert_array_equal(g, w)
            assert (g[self.GONE] == fill).all()  # scratch column included
            stay = [i for i in range(self.N) if i not in self.GONE]
            np.testing.assert_array_equal(g[stay], k[stay])
        for i in (0, 5, 8, 12):
            assert eng.text(i) == docs[i].get_text("text").to_string()

    @pytest.mark.parametrize("mesh", [False, True], ids=["one_device", "cpu_mesh"])
    def test_resident_tables_are_donated_and_no_second_table_made(self, mesh):
        from yjs_tpu.ops import kernels

        eng, _ref, _docs = self._grown(mesh)
        eng.reset_doc(0)  # the program is met
        programs = kernels.blank_rows.__wrapped__._cache_size()
        for i in self.GONE:
            old = (eng._right, eng._deleted, eng._starts)
            where = self._pointers(eng)
            eng.reset_doc(i)
            assert all(t.is_deleted() for t in old)
            # written where the old tables lay, on every device
            assert self._pointers(eng) == where
        # the slot is an argument, not a constant: one program for all
        assert kernels.blank_rows.__wrapped__._cache_size() == programs
        eng.flush()
        row = (eng._cap + 1) * 5 + (eng._seg_cap + 1) * 4
        blanked = (1 + len(self.GONE)) * row
        assert eng.last_flush_metrics["release_blanked_bytes"] == blanked
        assert eng.obs.registry.get(
            "ytpu_release_blanked_bytes_total"
        ).value == blanked
        eng.flush()
        assert eng.last_flush_metrics["release_blanked_bytes"] == 0

    @pytest.mark.parametrize("slot", [-1, TestStagedWidth.N])
    @pytest.mark.parametrize("mesh", [False, True], ids=["one_device", "cpu_mesh"])
    def test_a_slot_out_of_range_blanks_nothing(self, mesh, slot):
        """The host list wraps a negative index and the device write
        clamps its start: ``reset_doc`` refuses before either picks a
        row, and host and device stay as they were."""
        eng, _ref, docs = self._grown(mesh)
        kept = TestStagedWidth._tables(eng)
        mirrors = list(eng.mirrors)
        with pytest.raises(IndexError, match="no slot"):
            eng.reset_doc(slot)
        assert all(a is b for a, b in zip(eng.mirrors, mirrors))
        for g, k in zip(TestStagedWidth._tables(eng), kept):
            np.testing.assert_array_equal(g, k)
        assert eng.text(self.N - 1) == docs[self.N - 1].get_text("text").to_string()

    @pytest.mark.parametrize("mesh", [False, True], ids=["one_device", "cpu_mesh"])
    def test_next_tenant_is_byte_identical_with_the_cpu_doc(self, mesh):
        eng, ref, _docs = self._grown(mesh)
        tenants = {}
        for i in self.GONE:
            d = make_doc(9000 + i)
            t = d.get_text("text")
            t.insert(0, f"tenant {i} " * (3 + i))
            t.delete(2, 5)
            tenants[i] = d
        for e in (eng, ref):
            for i in self.GONE:
                e.reset_doc(i)
            for i, d in tenants.items():
                e.queue_update(i, Y.encode_state_as_update(d))
            e.flush()
        for i, d in tenants.items():
            assert_engine_matches(eng, d, idx=i)
            assert Y.merge_updates([eng.encode_state_as_update(i)]) == (
                Y.merge_updates([Y.encode_state_as_update(d)])
            )
        for g, w in zip(
            TestStagedWidth._tables(eng), TestStagedWidth._tables(ref)
        ):
            np.testing.assert_array_equal(g, w)
        if mesh:
            # a room that left and came back lies on its own chip's
            # block: slot i is row i % 4 of device i // 4's shard
            per = self.N // 4
            right = np.asarray(eng._right)
            shards = {
                s.index[0].start // per: np.asarray(s.data)
                for s in eng._right.addressable_shards
            }
            assert sorted(shards) == [0, 1, 2, 3]
            for i in self.GONE:
                assert (right[i] != -1).any()
                np.testing.assert_array_equal(
                    shards[i // per][i % per], right[i]
                )


def test_a_mesh_reuses_a_program_whose_lanes_cover_the_chunk():
    """On a mesh a lane width is the widest shard's sum: the same rooms
    re-let into other slots wander over a bucket's edge.  A program the
    mesh already has that covers the chunk at no more than a quarter
    more lanes is used before another is issued; one device keeps a
    bulk load's exact key, and covers a served flush's (PR 40)."""
    from yjs_tpu.parallel import doc_mesh

    def grown(eng, slot, client, n):
        """A room of ``n`` rows whose dense links ride the lanes: it
        holds its first row when the others arrive, each typed right
        behind that one, so the plan rewrites every link the room has.
        (Loaded whole into an empty slot it would go up as a row block
        and meet no lane key: tests/test_row_load.py.)"""
        d = make_doc(client)
        t = d.get_text("text")
        t.insert(0, "x")
        eng.queue_update(slot, Y.encode_state_as_update(d))
        eng.flush()
        sv = Y.encode_state_vector(d)
        for _ in range(n - 1):
            t.insert(1, "x")
        eng.queue_update(slot, Y.encode_state_as_update(d, sv))
        eng.flush()
        return d

    eng = BatchEngine(8, mesh=doc_mesh(4, backend="cpu"))
    grown(eng, 0, 1, 200)
    (key,) = eng._sharded_apply
    assert key[0] == 208  # 200 dense link writes, bucketed
    # the room comes back a little smaller and on another chip's block
    eng.reset_doc(0)
    second = grown(eng, 5, 2, 180)
    assert set(eng._sharded_apply) == {key}
    assert_engine_matches(eng, second, idx=5)
    assert eng._covering_key((192, 64, 8, 64)) == key
    # too narrow for the padding to be worth it, or wider: its own
    for other in ((64, 64, 8, 64), (224, 64, 8, 64), (208, 64, 8, 128)):
        assert eng._covering_key(other) == other
    third = grown(eng, 2, 3, 40)
    assert set(eng._sharded_apply) == {key, (64, 64, 8, 64)}
    assert_engine_matches(eng, third, idx=2)
    one = BatchEngine(8)
    grown(one, 0, 1, 200)
    assert one._mesh_keys == {(256, 64, 8, 64)}  # a new width: a power of two
    # a served flush's key wanders in four widths at once: an issued key
    # that covers it at 512 lanes more, or a quarter, is issued again
    for served in ((192, 64, 8, 64), (64, 64, 8, 64), (112, 64, 8, 64)):
        assert one._covering_key(served) == (256, 64, 8, 64)
    # not covered: its own, each width the next power of two
    assert one._covering_key((208, 64, 9, 64)) == (256, 64, 16, 64)
    assert one._covering_key((272, 72, 8, 64)) == (512, 128, 8, 64)
    assert one._covering_key((260, 64, 8, 64)) == (512, 128, 8, 64)
    # a bulk merge's links and deletes give the same widths every time
    # and keep them; its list heads, a Poisson count of some dozens, are
    # given six times their root of room (PR 46), and an issued key
    # covers at a quarter more lanes
    bulk = (8192, 64, 30, 64)
    assert one._covering_key(bulk) == (8192, 64, 60, 64)
    for merge in (bulk, (8192, 64, 36, 64), (8000, 64, 8, 64)):
        assert one._covering_key(merge) == (8192, 64, 60, 64)
    # wider in a width, or too narrow for the padding: a key of its own
    assert one._covering_key((9216, 64, 30, 64)) == (9216, 64, 60, 64)
    assert one._covering_key((8192, 64, 64, 64)) == (8192, 64, 112, 64)
    assert one._covering_key((6144, 4608, 8, 64)) == (6144, 4608, 20, 64)


class TestChunkedFlushStress:
    """Adversarial coverage of the chunked batched flush (r4): capacity
    growth BETWEEN chunks mid-flush, duplicated/out-of-order delivery,
    and causal gaps parked/resumed across chunk boundaries."""

    def test_uneven_growth_across_chunks(self, monkeypatch):
        from yjs_tpu.ops.native_mirror import native_plan_available

        if not native_plan_available():
            pytest.skip("chunked batched flush requires the native planner")
        monkeypatch.setenv("YTPU_FLUSH_CHUNK", "8")
        rng = random.Random(42)
        n = 48
        eng = BatchEngine(n, compact_min_rows=16)
        docs = [make_doc(100 + i) for i in range(n)]
        for rnd in range(5):
            batches = []
            for i, d in enumerate(docs):
                t = d.get_text("text")
                size = rng.choice([1, 3, 200])  # uneven chunk-local caps
                pos = rng.randint(0, len(t.to_string()))
                t.insert(pos, "x" * size + f"[{rnd}.{i}]")
                if rng.random() < 0.4 and len(t.to_string()) > 10:
                    t.delete(rng.randint(0, 5), 5)
                batches.append(Y.encode_state_as_update(d))
            order = list(range(n))
            rng.shuffle(order)
            for i in order:
                eng.queue_update(i, batches[i])
                if rng.random() < 0.2:
                    eng.queue_update(i, batches[i])  # duplicate delivery
            eng.flush()
            assert not eng.fallback, eng.demotions  # fast path every round
        for i in range(n):
            assert_engine_matches(eng, docs[i], i)

    def test_causal_gaps_park_and_resume(self, monkeypatch):
        from yjs_tpu.ops.native_mirror import native_plan_available

        if not native_plan_available():
            pytest.skip("chunked batched flush requires the native planner")
        monkeypatch.setenv("YTPU_FLUSH_CHUNK", "4")
        rng = random.Random(7)
        n = 24
        eng = BatchEngine(n)
        docs = [make_doc(100 + i) for i in range(n)]
        peers = [make_doc(500 + i) for i in range(n)]
        svs = [None] * n
        held = [[] for _ in range(n)]
        for rnd in range(8):
            for i in range(n):
                d = docs[i]
                t = d.get_text("text")
                t.insert(rng.randint(0, len(t.to_string())), f"a{rnd}")
                u = Y.encode_state_as_update(d, svs[i])
                svs[i] = Y.encode_state_vector(d)
                if rng.random() < 0.4:
                    held[i].append(u)  # causal gap until released below
                else:
                    eng.queue_update(i, u)
                    for h in reversed(held[i]):
                        eng.queue_update(i, h)
                    held[i].clear()
                if rng.random() < 0.3:
                    p = peers[i]
                    Y.apply_update(p, Y.encode_state_as_update(d))
                    p.get_text("text").insert(0, f"P{rnd}.")
                    pu = Y.encode_state_as_update(
                        p, Y.encode_state_vector(d)
                    )
                    Y.apply_update(d, pu)
                    svs[i] = Y.encode_state_vector(d)
                    eng.queue_update(i, pu)
            eng.flush()
            assert not eng.fallback, eng.demotions
        for i in range(n):
            for h in held[i]:
                eng.queue_update(i, h)
        eng.flush()
        assert not eng.fallback, eng.demotions
        for i in range(n):
            assert_engine_matches(eng, docs[i], i)
        assert eng.last_flush_metrics["n_pending_docs"] == 0


class TestLaneBucketing:
    """_bucket_lanes: mantissa-quantized lane widths
    cap padding waste at 12.5% while keeping compiled shapes bounded."""

    def test_properties(self):
        from yjs_tpu.ops.engine import _bucket_lanes

        assert _bucket_lanes(0) == 64 and _bucket_lanes(64) == 64
        prev = 0
        seen_per_octave: dict[int, set] = {}
        for n in range(1, 200000, 7):
            b = _bucket_lanes(n)
            assert b >= n and b >= 64
            assert b >= prev or n <= 64  # monotone
            prev = b
            if n > 64:
                assert b / n <= 1.125 + 1e-9, (n, b)
            assert _bucket_lanes(b) == b  # idempotent (stable shapes)
            seen_per_octave.setdefault(b.bit_length(), set()).add(b)
        # bounded distinct shapes: at most 2**bits per power-of-two octave
        for octave, vals in seen_per_octave.items():
            assert len(vals) <= 8 + 1, (octave, sorted(vals))

    def test_flush_occupancy_and_shape_stability(self, rng):
        """Multi-doc flush occupancy >= 0.92, and flushes whose lane
        demand differs by <12.5% reuse the SAME padded widths (= the
        dispatch hits the jit cache by construction).

        Specific to the NATIVE lane packing: the Python-planner fallback
        takes the non-batched pack path."""
        import os as _os

        import pytest as _pytest

        if _os.environ.get("YTPU_NO_NATIVE_PLAN"):
            _pytest.skip("native lane packing only")
        import yjs_tpu as Y
        from yjs_tpu.ops import BatchEngine

        def mk_updates(n_docs, ops, seed0):
            # two-client conflict texture: realistic fragmentation so the
            # lane demand is real work, not floor padding.  Each room is
            # a pair: its first keystroke, flushed by itself, and the
            # whole room behind it, so that the room holds a row when
            # the rest arrives and rides the lanes (loaded whole into an
            # empty slot it goes up as a row block and packs no lane:
            # tests/test_row_load.py)
            outs = []
            for k in range(n_docs):
                gen = random.Random(seed0 + k)
                a = Y.Doc(gc=False)
                a.client_id = 1000 + 2 * k
                b = Y.Doc(gc=False)
                b.client_id = 1001 + 2 * k

                def sync(a=a, b=b):
                    ua = Y.encode_state_as_update(a, Y.encode_state_vector(b))
                    ub = Y.encode_state_as_update(b, Y.encode_state_vector(a))
                    Y.apply_update(b, ua)
                    Y.apply_update(a, ub)

                a.get_text("text").insert(0, "ab")
                first = Y.encode_state_as_update(a)
                sync()
                for i in range(ops + gen.randint(0, ops // 20)):
                    d = a if gen.random() < 0.5 else b
                    t = d.get_text("text")
                    ln = len(t.to_string())
                    if gen.random() < 0.75 or ln == 0:
                        t.insert(gen.randint(0, ln), gen.choice(["ab", "c "]))
                    else:
                        pos = gen.randrange(ln)
                        t.delete(pos, min(gen.randint(1, 3), ln - pos))
                    if gen.random() < 0.2:
                        sync()
                sync()
                outs.append((first, Y.encode_state_as_update(a)))
            return outs

        def load(eng, rooms):
            for part in (0, 1):
                for i, pair in enumerate(rooms):
                    eng.queue_update(i, pair[part])
                eng.flush()

        eng = BatchEngine(32)
        load(eng, mk_updates(32, 120, 5000))
        assert eng.last_flush_metrics["rooms_row_loaded"] == 0
        occ = eng.last_flush_metrics["schedule_occupancy"]
        # >=0.90 at this 32-doc scale (the fixed 64/64/8/64 minimum-width
        # floors are ~5% of demand here)
        assert occ >= 0.90, occ
        # second engine, ~5% different demand -> identical lane widths
        import yjs_tpu.ops.engine as engine_mod

        widths = []
        orig = engine_mod.pack_apply_lanes

        def spy(work, doc_ids, b_loc, n_shards, w, *a, **k):
            widths.append(w)
            return orig(work, doc_ids, b_loc, n_shards, w, *a, **k)

        engine_mod.pack_apply_lanes = spy
        try:
            for run, seed0 in enumerate(range(6000, 6600, 100)):
                e1 = BatchEngine(32)
                ops = 120 + (run % 3) * 4  # ±~5% demand wobble per run
                load(e1, mk_updates(32, ops, seed0))
        finally:
            engine_mod.pack_apply_lanes = orig
        assert len(widths) >= 6
        # bucketing must COLLAPSE the wobble onto few padded shapes (each
        # repeat = a jit-cache hit); exact widths would give one distinct
        # tuple per run
        assert len(set(widths)) <= len(widths) // 2, widths
