"""ProseMirror-shaped rooms through ``TpuProvider`` against a ``Y.Doc``.

Seeded sessions of two and three writers on y-prosemirror-shaped
documents (a ``Y.XmlFragment`` of ``Y.XmlElement`` and ``Y.XmlText`` with
marks and node attributes; ``scripts/gen_prosemirror_fixtures.py``'s
``Binding``): typing, backspaces, block splits, marks over overlapping
ranges at once, attribute sets racing on one key, a marked word erased,
updates delivered late and out of order.  Every update a writer's
document emits (its own formatting clean-up after hearing a peer among
them) goes through ``TpuProvider(backend="device")``, on the native
planner and on the Python ``DocMirror``, and after every flush the room
is held to a ``Y.Doc`` fed the same bytes, a flush a transaction: state
vector, canonical encoded state, the XML string from the host mirror and
from the device's rows, and what the room's listeners were sent.

This is where the clean-up question is settled: a ``Y.Doc`` that takes a
remote transaction which brought a format item, or deleted one, cleans
the texts the transaction changed (``YText._callObserver``) and deletes
format items no client deleted; ``BatchEngine._format_cleanup`` deletes
the same ones in the same flush and broadcasts them, and
``test_the_replay_deletes_what_no_client_did`` shows the traffic that
needs it.
"""

import random
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
for p in (ROOT, ROOT / "scripts"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import yjs_tpu as Y  # noqa: E402
from yjs_tpu.core import create_delete_set_from_struct_store, transact  # noqa: E402
from yjs_tpu.ops.columns import DocMirror  # noqa: E402
from yjs_tpu.ops.native_mirror import NativeMirror, native_plan_available  # noqa: E402
from yjs_tpu.provider import TpuProvider  # noqa: E402

from gen_prosemirror_fixtures import FRAGMENT, Binding  # noqa: E402

GUID = "room"
PLANNERS = ["native", "python"]


def planner(monkeypatch, which):
    if which == "python":
        monkeypatch.setenv("YTPU_NO_NATIVE_PLAN", "1")
    elif not native_plan_available():
        pytest.skip("no native plan core")


def canonical(update: bytes) -> bytes:
    return Y.merge_updates([update])


def delete_set(doc) -> dict:
    ds = create_delete_set_from_struct_store(doc.store)
    return {c: [(d.clock, d.len) for d in v] for c, v in ds.clients.items()}


class Room:
    """One room on a provider, the ``Y.Doc`` it is held to, a listener."""

    def __init__(self, which):
        self.prov = TpuProvider(n_docs=4, backend="device")
        self.oracle = Y.Doc(gc=False)
        self.heard: list[bytes] = []
        self.prov.on_update(lambda guid, u: self.heard.append(u))
        self.listener = Y.Doc(gc=False)
        self.which = which
        self.cleaned = 0
        self._applied = 0  # broadcasts the listener has taken

    def flush(self, updates):
        """One flush of ``updates``; the oracle takes them in one
        transaction, as the engine does."""
        for u in updates:
            assert self.prov.receive_update(GUID, u)
        first = len(self.heard)
        self.prov.flush()
        transact(
            self.oracle,
            lambda _t: [Y.apply_update(self.oracle, u) for u in updates],
            None, False,
        )
        m = self.prov.engine.last_flush_metrics
        self.cleaned += m["format_cleanup_deleted"]
        self.check()
        return self.heard[first:]

    def check(self):
        prov, eng, oracle = self.prov, self.prov.engine, self.oracle
        mirror = eng.mirrors[prov.doc_id(GUID)]
        assert type(mirror) is (NativeMirror if self.which == "native" else DocMirror)
        assert not eng.fallback and not eng.demotions
        sv = Y.decode_state_vector(Y.encode_state_vector(oracle))
        assert prov.state_vector(GUID) == sv
        assert canonical(prov.encode_state_as_update(GUID)) == canonical(
            Y.encode_state_as_update(oracle)
        )
        want = oracle.get_xml_fragment(FRAGMENT).to_string()
        for device in (False, True):
            eng.export_from_device = device
            assert prov.xml_string(GUID, FRAGMENT) == want, device
        for u in self.heard[self._applied:]:
            Y.apply_update(self.listener, u)
        self._applied = len(self.heard)
        if not mirror.has_pending():
            assert canonical(Y.encode_state_as_update(self.listener)) == canonical(
                Y.encode_state_as_update(oracle)
            )


class Writer:
    """A y-prosemirror client: a ``Y.Doc`` that collects garbage, its
    binding, and what it has to send."""

    def __init__(self, client: int, gc: bool = True):
        self.doc = Y.Doc(gc=gc)
        self.b = Binding(self.doc, client)
        self.outbox: list[bytes] = []
        self.doc.on(
            "update",
            lambda u, origin, _d: origin == "remote" or self.outbox.append(u),
        )

    def hear(self, update: bytes) -> None:
        Y.apply_update(self.doc, update, "remote")

    def settle(self, rng) -> None:
        """The cursor where the document still has it."""
        b = self.b
        blocks = b.blocks()
        if b.block is None or not any(b.block is k for k in blocks):
            b.jump(rng.choice(blocks), None)
        b.jump(b.block, min(b.index, len(b.chars())))


def outline(w: Writer) -> None:
    b = w.b
    b.insert_block(None, "heading", {"level": 1}, [("The title", {})])
    b.insert_block(b.block, "paragraph", None, [
        ("plain words and ", {}), ("bold ones", {"strong": {}}),
        (" then a link", {"link": {"href": "https://example.org/1"}}),
    ])
    top = b.block
    b.insert_list(top, "bullet_list", [
        [("first item", {})], [("second ", {}), ("item", {"em": {}})],
        [[("nested", {})], [("deep one", {})], [("deep two", {})]],
    ])
    b.insert_block(top, "paragraph", {"textAlign": "left"}, [("last words here", {})])
    b.insert_block(b.block, "code_block", None, [("x = 1", {})])


def act(w: Writer, rng) -> None:
    """One ProseMirror transaction of a writer."""
    b = w.b
    w.settle(rng)
    r = rng.random()
    if r < 0.15:
        blocks = b.blocks()
        b.jump(rng.choice(blocks), None)
        b.jump(b.block, rng.randint(0, len(b.chars())))
        r = rng.random()
    if r < 0.45:
        b.type(" " if rng.random() < 0.2 else rng.choice("abcdefgh"))
    elif r < 0.65:
        if not b.erase():
            b.type("z")
    elif r < 0.73:
        if b.block.node_name == "code_block":
            b.type("\n")
        else:
            b.enter()
    elif r < 0.9:
        if not b.toggle(rng.choice(("strong", "em"))):
            b.type("w")
    elif b.block.node_name == "heading":
        b.set_attr("level", rng.randint(1, 6))
    elif b.block.node_name == "paragraph":
        b.set_attr("textAlign", rng.choice(("left", "center", "right")))
    else:
        b.type("q")


@pytest.mark.parametrize("which", PLANNERS)
@pytest.mark.parametrize("n_writers,seed", [(2, 11), (2, 12), (3, 13), (3, 14)])
def test_a_session_is_held_to_a_ydoc_after_every_flush(
    monkeypatch, which, n_writers, seed
):
    planner(monkeypatch, which)
    rng = random.Random(f"pm-parity:{seed}")
    room = Room(which)
    writers = [Writer(500 + k) for k in range(n_writers)]
    outline(writers[0])
    boot = writers[0].outbox[:]
    writers[0].outbox.clear()
    for u in room.flush(boot):
        for w in writers[1:]:
            w.hear(u)
    in_flight: list[tuple[int, bytes]] = []  # (round it arrives, update)
    for rnd in range(70):
        for w in writers:
            if rng.random() < 0.85:
                act(w, rng)
            for u in w.outbox:
                # most arrive at once, some late and so out of order
                late = 0 if rng.random() < 0.7 else rng.randint(1, 3)
                in_flight.append((rnd + late, u))
            w.outbox.clear()
        due = [u for at, u in in_flight if at <= rnd]
        in_flight = [(at, u) for at, u in in_flight if at > rnd]
        rng.shuffle(due)
        if not due:
            continue
        for u in room.flush(due):
            for w in writers:
                w.hear(u)
    # everything lands; the writers' own clean-ups go round until quiet
    for _ in range(12):
        due = [u for _at, u in in_flight] + [
            u for w in writers for u in w.outbox
        ]
        in_flight = []
        for w in writers:
            w.outbox.clear()
        if not due:
            break
        for u in room.flush(due):
            for w in writers:
                w.hear(u)
    else:
        raise AssertionError("the writers never went quiet")
    want = room.oracle.get_xml_fragment(FRAGMENT).to_string()
    assert len(want) > 200
    for w in writers:
        assert w.doc.get_xml_fragment(FRAGMENT).to_string() == want
    m = room.prov.engine.last_flush_metrics
    assert m["seg_cap"] >= m["n_segs_max"] > 8


def paragraph(text: str, gc: bool = True):
    """A writer whose document is one paragraph."""
    w = Writer(1, gc)
    w.b.insert_block(None, "paragraph", None, [(text, {})])
    return w


def follower(update: bytes, client: int) -> Writer:
    w = Writer(client)
    w.hear(update)
    w.b.jump(w.b.blocks()[0], None)
    return w


@pytest.mark.parametrize("which", PLANNERS)
def test_marks_over_overlapping_ranges_at_once(monkeypatch, which):
    """Two writers bold overlapping ranges from one state: the replay
    cleans the doubled format items, and so does the provider."""
    planner(monkeypatch, which)
    room = Room(which)
    a = paragraph("hello wide world of words")
    base = a.outbox.pop()
    room.flush([base])
    b = follower(base, 2)
    a.b.text().format(0, 16, {"strong": {}})
    b.b.text().format(6, 14, {"strong": {}})
    room.flush([a.outbox.pop()])
    room.flush([b.outbox.pop()])
    assert room.cleaned >= 1
    # (the first closing item ends both ranges: Yjs's own outcome)
    assert room.prov.xml_string(GUID, FRAGMENT) == (
        "<paragraph><strong>hello </strong><strong>wide world</strong>"
        " of words</paragraph>"
    )


@pytest.mark.parametrize("which", PLANNERS)
def test_attribute_sets_racing_on_one_key(monkeypatch, which):
    planner(monkeypatch, which)
    room = Room(which)
    a = Writer(1)
    a.b.insert_block(None, "heading", {"level": 1}, [("title", {})])
    base = a.outbox.pop()
    room.flush([base])
    b = follower(base, 2)
    a.b.set_attr("level", 2)
    b.b.set_attr("level", 3)
    # both in one flush, the higher client id's first
    room.flush([b.outbox.pop(), a.outbox.pop()])
    m = room.prov.engine.last_flush_metrics
    assert m["rows_attr"] == 2 and m["lww_overwritten"] >= 1
    assert room.prov.xml_string(GUID, FRAGMENT) == '<heading level="3">title</heading>'


@pytest.mark.parametrize("which", PLANNERS)
def test_the_replay_deletes_what_no_client_did(monkeypatch, which):
    """A marked word erased one character at a time by a writer that
    keeps its history (``gc: false``, as y-prosemirror's versions need):
    its own ``cleanupFormattingGap`` stops at the character it erased
    before and leaves both format items where the word was; the next
    remote transaction that brings a format item makes a ``Y.Doc`` clean
    the text, which deletes them: two deletions no client sent."""
    planner(monkeypatch, which)
    room = Room(which)
    w = paragraph("xx ab yy and more", gc=False)
    room.flush([w.outbox.pop()])
    t = w.b.text()
    t.format(3, 2, {"strong": {}})
    room.flush([w.outbox.pop()])
    t.delete(4, 1)
    room.flush([w.outbox.pop()])
    t.delete(3, 1)
    room.flush([w.outbox.pop()])
    sent = delete_set(w.doc)
    assert delete_set(room.oracle) == sent and room.cleaned == 0
    t.format(6, 3, {"em": {}})
    heard = room.flush([w.outbox.pop()])
    cleaned = delete_set(room.oracle)
    assert sum(n for ranges in cleaned.values() for _c, n in ranges) == (
        sum(n for ranges in sent.values() for _c, n in ranges) + 2
    )
    assert room.cleaned == 2
    # the flush that integrated the mark broadcast the clean-up too
    assert len(heard) == 2 and heard[1][0] == 0  # no struct, a delete set
    m = room.prov.engine.last_flush_metrics
    assert (m["rows_format"], m["format_cleanup_texts"]) == (2, 1)


@pytest.mark.parametrize("which", PLANNERS)
def test_a_whole_document_in_one_update_is_not_cleaned(monkeypatch, which):
    """A text that the transaction itself created is not in the
    transaction's changed types: a room that arrives as one update (a
    cold start) is left as it was sent."""
    planner(monkeypatch, which)
    room = Room(which)
    w = paragraph("xx ab yy", gc=False)
    t = w.b.text()
    t.format(3, 2, {"strong": {}})
    t.delete(4, 1)
    t.delete(3, 1)
    room.flush([Y.encode_state_as_update(w.doc)])
    assert room.cleaned == 0
    assert room.prov.engine.last_flush_metrics["rows_format"] == 2


def test_the_cores_clean_up_is_the_python_walks(monkeypatch):
    """``ymx_format_cleanup`` over the core's own rows against
    ``engine._cleanup_room`` over the mirror's columns, for every room a
    session's flushes look at: the same format items, the same texts."""
    planner(monkeypatch, "native")
    from yjs_tpu.ops import engine as E

    gates, checked = {}, []
    look = E.BatchEngine._format_cleanup

    def noting(self, metrics):
        for doc, _rows, plan in self._cleanup_gate:
            gates[id(self.mirrors[doc])] = plan
        return look(self, metrics)

    core = NativeMirror.format_cleanup

    def both(self, rows_before):
        got = core(self, rows_before)
        want = E._cleanup_room(
            self, rows_before, self.make_plan(gates[id(self)])
        )
        assert got is not None
        assert (sorted(got[0]), got[1]) == (sorted(want[0]), want[1])
        checked.append(len(got[0]))
        return got

    monkeypatch.setattr(E.BatchEngine, "_format_cleanup", noting)
    monkeypatch.setattr(NativeMirror, "format_cleanup", both)
    rng = random.Random("pm-parity:core")
    room = Room("native")
    writers = [Writer(600), Writer(601, gc=False)]
    outline(writers[0])
    for u in room.flush(writers[0].outbox[:]):
        writers[1].hear(u)
    writers[0].outbox.clear()
    for _ in range(120):
        due = []
        for w in writers:
            act(w, rng)
            due += w.outbox
            w.outbox.clear()
        for u in room.flush(due):
            for w in writers:
                w.hear(u)
    assert len(checked) > 25 and sum(checked) > 0
