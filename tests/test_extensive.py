"""Opt-in extensive fuzz — the deep-history analogue of the reference's
CI-extensive run (`npm test -- --production --repitition-time 10000`,
reference package.json:15-16; randomized instances scale 6 → 100 000
iterations in reference tests/y-map.tests.js:499-606).

Skipped unless YTPU_FUZZ_ITERS is set, e.g.:

    YTPU_FUZZ_ITERS=10000 JAX_PLATFORMS=cpu python -m pytest \
        tests/test_extensive.py -q

Covers all three layers: the CPU reference core
(ported op tables under the disconnect/reconnect connector), the batch
engine, and the sharded engine on the virtual 8-device mesh.  Recorded
runs live in tests/EXTENSIVE_RUNS.md.
"""

import os
import random

import pytest

import yjs_tpu as Y
from yjs_tpu.ops import BatchEngine

from helpers import apply_random_tests
from test_yarray import ARRAY_MODS
from test_ymap import MAP_MODS
from test_ytext import TEXT_MODS

ITERS = int(os.environ.get("YTPU_FUZZ_ITERS", "0"))

pytestmark = pytest.mark.skipif(
    ITERS <= 0, reason="set YTPU_FUZZ_ITERS>=1 for the extensive fuzz run"
)


# -- r5 op-table extensions: undo + snapshot ops mixed into the fuzz ---------
# (the deep fuzz must also drive the undo and snapshot machinery, not
# only plain edits)


def _undo_mod_for(type_getter, attr):
    """Random undo/redo against a per-user UndoManager scoped to one root
    type.  Undo emits ordinary updates, so the convergence oracle is
    unchanged; what this adds is redone-chain + deleted-struct traffic in
    every random delivery order."""

    def _mod(user, gen):
        um = getattr(user, attr, None)
        if um is None:
            um = Y.UndoManager(type_getter(user), capture_timeout=0)
            setattr(user, attr, um)
        if gen.random() < 0.6 and um.undo_stack:
            um.undo()
        elif um.redo_stack:
            um.redo()

    return _mod


def _snapshot_mod(user, gen):
    """Random snapshot capture + codec roundtrip; restore parity is
    checked on non-gc docs (the engine fuzz below covers restore on its
    gc=False docs every run)."""
    snap = Y.snapshot(user)
    enc = Y.encode_snapshot(snap)
    assert Y.equal_snapshots(Y.decode_snapshot(enc), snap)
    if not user.gc:
        d2 = Y.create_doc_from_snapshot(user, snap)
        assert d2.get_text("text").to_string() == user.get_text("text").to_string()


EXT_ARRAY_MODS = ARRAY_MODS + [
    _undo_mod_for(lambda u: u.get_array("array"), "_fuzz_undo_array"),
    _snapshot_mod,
]
EXT_MAP_MODS = MAP_MODS + [
    _undo_mod_for(lambda u: u.get_map("map"), "_fuzz_undo_map"),
    _snapshot_mod,
]
EXT_TEXT_MODS = TEXT_MODS + [
    _undo_mod_for(lambda u: u.get_text("text"), "_fuzz_undo_text"),
    _snapshot_mod,
]


def _compare_content(users):
    """Content-level convergence oracle for undo-mixed runs: ``redone``
    pointers are replica-local (reference Item.js:555-579 mergeWith needs
    ``redone === null``), so the undoing replica merges runs differently
    than its peers and struct-store IDENTITY legitimately diverges; the
    rendered content and the pending queues must still agree exactly."""
    for u in users:
        u.connect()
    while users[0].tc.flush_all_messages():
        pass
    ref = users[0]
    for u in users[1:]:
        assert u.get_array("array").to_json() == ref.get_array("array").to_json()
        assert u.get_map("map").to_json() == ref.get_map("map").to_json()
        assert (
            u.get("xml", Y.YXmlElement).to_string()
            == ref.get("xml", Y.YXmlElement).to_string()
        )
        assert u.get_text("text").to_delta() == ref.get_text("text").to_delta()
    for u in users:
        assert len(u.store.pending_delete_readers) == 0
        assert len(u.store.pending_stack) == 0
        assert len(u.store.pending_clients_struct_refs) == 0


# -- CPU reference core under the random-delivery connector -----------------
# plain tables keep the full struct-store-identity oracle; the *_mixed
# variants drive the same tables with undo/snapshot ops folded in under
# the content-level oracle (see _compare_content for why)


def test_extensive_array(rng):
    apply_random_tests(rng, ARRAY_MODS, ITERS)


def test_extensive_map(rng):
    apply_random_tests(rng, MAP_MODS, ITERS)


def test_extensive_text(rng):
    apply_random_tests(rng, TEXT_MODS, ITERS)


def test_extensive_array_mixed(rng):
    apply_random_tests(rng, EXT_ARRAY_MODS, ITERS, compare_fn=_compare_content)


def test_extensive_map_mixed(rng):
    apply_random_tests(rng, EXT_MAP_MODS, ITERS, compare_fn=_compare_content)


def test_extensive_text_mixed(rng):
    apply_random_tests(rng, EXT_TEXT_MODS, ITERS, compare_fn=_compare_content)


# -- batch engine / sharded batch engine -------------------------------------


def _engine_fuzz(gen: random.Random, n_ops: int, mesh=None) -> None:
    """Deep mixed text+map+multiroot trace with randomized delivery into the
    engine (incremental flushes, so splits/pending paths see deep histories),
    checked against the CPU core oracle at the end.

    PR 40: format ops on a root text and XML ops (elements, their texts
    with marks, attributes, node deletion) under a root fragment ride the
    same streams; the engine's own formatting clean-ups (what it
    broadcasts after a flush, ``_format_cleanup``) go back to the docs
    as a peer's would, everything goes round until nobody has anything
    new to say, and the rooms are then held to the docs' deltas and XML
    strings as well.

    r5: updates fan out to FOUR engine rooms (docs 0..3, each receiving an
    independent random prefix), and YTPU_FLUSH_CHUNK=2 forces every flush
    through the chunked plan/transfer-overlap path; random engine
    snapshots assert SV-vs-mirror equality mid-run, and per-client
    UndoManagers add redone-chain traffic to the delivered updates."""
    n_clients = 4
    docs = []
    for i in range(n_clients):
        d = Y.Doc(gc=False)
        d.client_id = i + 1
        docs.append(d)
    upds = [[] for _ in range(n_clients)]
    for i, d in enumerate(docs):
        d.on("update", lambda u, origin, _d, i=i: upds[i].append(u))
    undo_mgrs = [
        Y.UndoManager(d.get_text("text"), capture_timeout=0) for d in docs
    ]

    n_rooms = n_clients  # one engine room per client stream
    eng = BatchEngine(8 if mesh is not None else n_rooms, mesh=mesh)
    cleaned: list = []  # what the engine broadcast: its clean-ups among it
    eng.on_update(lambda _room, u: cleaned.append(u))

    def xml_op(d):
        frag = d.get_xml_fragment("xml")
        els = [e for e in frag.to_array() if isinstance(e, Y.YXmlElement)]
        r = gen.random()
        if not els or r < 0.15:
            el = Y.YXmlElement(gen.choice(["paragraph", "heading"]))
            t = Y.YXmlText()
            t.insert(0, gen.choice(["some words ", "more of them "]))
            el.insert(0, [t])
            frag.insert(gen.randint(0, len(els)), [el])
            return
        el = gen.choice(els)
        kids = el.to_array()
        t = kids[0] if kids else None
        if r < 0.2 and len(els) > 3:
            frag.delete(frag.to_array().index(el), 1)
        elif r < 0.35:
            el.set_attribute(gen.choice(["level", "align"]), gen.randrange(4))
        elif t is None:
            return
        elif r < 0.65:
            t.insert(gen.randint(0, t.length), gen.choice(["ab", "c ", "xyz"]))
        elif r < 0.8 and t.length:
            pos = gen.randrange(t.length)
            t.delete(pos, min(gen.randint(1, 3), t.length - pos))
        elif t.length:
            pos = gen.randrange(t.length)
            t.format(
                pos, min(gen.randint(1, 6), t.length - pos),
                {gen.choice(["strong", "em"]): gen.choice([{}, None, {}])},
            )
    # prefix of upds[i] already queued to engine room i
    delivered = [0] * n_clients
    flush_every = max(40, n_ops // 200)

    def deliver_some():
        i = gen.randrange(n_clients)
        take = gen.randint(1, max(1, len(upds[i]) - delivered[i]))
        for u in upds[i][delivered[i] : delivered[i] + take]:
            eng.queue_update(i, u)
        delivered[i] = min(len(upds[i]), delivered[i] + take)

    for step in range(n_ops):
        i = gen.randrange(n_clients)
        d = docs[i]
        op = gen.random()
        if op < 0.08:
            xml_op(d)
        elif op < 0.12:
            # a root text of its own: "text" is the undo managers' scope
            # (an undone format and a peer's clean-up of it are another
            # matter), and a format that lands inside a surrogate pair
            # splits it in the doc that asked and in no other
            t = d.get_text("rich")
            if t.length < 8 or gen.random() < 0.4:
                t.insert(gen.randint(0, t.length), gen.choice(["ab ", "cde", "f"]))
            else:
                pos = gen.randrange(t.length)
                t.format(
                    pos, min(gen.randint(1, 5), t.length - pos),
                    {"bold": gen.choice([True, None]), "c": gen.choice(["r", None])},
                )
        elif op < 0.5:
            t = d.get_text(gen.choice(["text", "notes"]))
            ln = len(t.to_string())
            if gen.random() < 0.65 or ln == 0:
                t.insert(gen.randint(0, ln), gen.choice(["x", "yy", "zz ", "🙂"]))
            else:
                pos = gen.randrange(ln)
                t.delete(pos, min(gen.randint(1, 3), ln - pos))
        elif op < 0.75:
            d.get_map("map").set(gen.choice("abcde"), gen.randrange(1000))
        elif op < 0.85:
            d.get_map("map").delete(gen.choice("abcde"))
        elif op < 0.95:  # nested shared types on the device path
            key = gen.choice("nm")
            cur = d.get_map("map").get(key)
            if cur is None or not hasattr(cur, "insert"):
                d.get_map("map").set(key, Y.YText())
            else:
                cur.insert(len(cur.to_string()), gen.choice(["n", "est "]))
        else:
            arr = d.get_map("map").get("arr")
            if arr is None or not hasattr(arr, "to_json"):
                d.get_map("map").set("arr", Y.YArray())
            else:
                arr.insert(0, [gen.randrange(50)])
        if gen.random() < 0.04:  # undo/redo traffic into the streams
            um = undo_mgrs[i]
            if gen.random() < 0.6 and um.undo_stack:
                um.undo()
            elif um.redo_stack:
                um.redo()
        if gen.random() < 0.3:  # random partial cross-client sync
            src, dst = gen.randrange(n_clients), gen.randrange(n_clients)
            for u in upds[src]:
                Y.apply_update(docs[dst], u)
        if gen.random() < 0.2:
            deliver_some()
        if step and step % flush_every == 0:
            eng.flush()
            if gen.random() < 0.1:
                # engine snapshot mid-run: SV must equal the mirror's
                room = gen.randrange(n_rooms)
                snap = eng.snapshot(room)
                assert {
                    c: v for c, v in snap.sv.items() if v > 0
                } == eng.state_vector(room)

    # quiesce: everyone sees everything, every engine room included; a
    # doc that hears a remote transaction may clean its formatting, and
    # so may the engine: round again until nobody says anything new
    told = 0
    for _ in range(20):
        all_updates = [u for us in upds for u in us] + cleaned
        if len(all_updates) == told:
            break
        told = len(all_updates)
        gen.shuffle(all_updates)
        for d in docs:
            for u in all_updates:
                Y.apply_update(d, u)
        for room in range(n_rooms):
            for u in all_updates:
                eng.queue_update(room, u)
        eng.flush()
    else:
        raise AssertionError("the clean-ups never went quiet")

    ref = docs[0]
    for other in docs[1:]:
        for name in ("text", "notes"):
            assert other.get_text(name).to_string() == ref.get_text(name).to_string()
        assert other.get_map("map").to_json() == ref.get_map("map").to_json()
        assert other.get_text("rich").to_delta() == ref.get_text("rich").to_delta()
        assert (
            other.get_xml_fragment("xml").to_string()
            == ref.get_xml_fragment("xml").to_string()
        )
    assert "<" in ref.get_xml_fragment("xml").to_string()
    for room in range(n_rooms):
        for name in ("text", "notes"):
            assert eng.text(room, name) == ref.get_text(name).to_string()
        assert eng.to_delta(room, "rich") == ref.get_text("rich").to_delta()
        assert eng.xml_string(room, "xml") == ref.get_xml_fragment("xml").to_string()
        assert eng.map_json(room, "map") == ref.get_map("map").to_json()
        assert eng.state_vector(room) == {
            c: v for c, v in Y.get_state_vector(ref.store).items() if v > 0
        }
        assert not eng.has_pending(room)
    # engine snapshot restore parity on the quiesced state
    snap = eng.snapshot(0)
    restored = eng.create_doc_from_snapshot(0, snap)
    assert restored.get_text("text").to_string() == ref.get_text("text").to_string()
    assert not eng.fallback, f"unexpected demotions: {eng.demotions}"


def test_extensive_engine(rng, monkeypatch):
    # chunk of 2 over 4 rooms: every flush exercises the chunked
    # plan/transfer-overlap path (capacity growth across chunks included)
    monkeypatch.setenv("YTPU_FLUSH_CHUNK", "2")
    _engine_fuzz(rng, ITERS)


def test_extensive_engine_sharded(rng, monkeypatch):
    import jax

    if len(jax.devices("cpu")) < 8:
        pytest.skip("needs 8 virtual cpu devices")
    from yjs_tpu.parallel import doc_mesh

    monkeypatch.setenv("YTPU_FLUSH_CHUNK", "2")
    _engine_fuzz(rng, ITERS, mesh=doc_mesh(8, backend="cpu"))
