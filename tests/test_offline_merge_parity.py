"""Bulk merges into rooms that hold rows: what ``offline-merge`` sends,
at a small size.  Two or three sessions come back to one room, each with
ONE update of a whole offline history (crdt-benchmarks B2.2-B2.4 on a
``Y.Text``, three writers of random inserts on a ``Y.Array``), written
by the benchmark's plain client (``benchmarks/plain_offline.py``).  Both
planners, the updates in one flush and in consecutive flushes, in every
arrival order: every room must equal a CPU ``Y.Doc`` fed the same
updates, byte for byte in its canonical encoded state, from the host
mirror and from the device's rows, and the rooms of all orders must
equal each other.  The counters and spans PR 46 added say which write
path a link took.
"""

import collections
import itertools
import random
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:  # the benchmark's plain client and oracle
    sys.path.insert(0, str(ROOT))

import yjs_tpu as Y
from yjs_tpu.ops.native_mirror import native_plan_available
from yjs_tpu.provider import TpuProvider

from benchmarks import oracle  # noqa: E402
from benchmarks.plain_client import PlainText  # noqa: E402
from benchmarks.plain_offline import Writer, work_offline  # noqa: E402

OPERATIONS = 70
WRITERS = {"b2.2": 2, "b2.3": 2, "b2.4": 2, "array": 3}


def _provider(monkeypatch, planner, n=8):
    if planner == "python":
        monkeypatch.setenv("YTPU_NO_NATIVE_PLAN", "1")
    elif not native_plan_available():
        pytest.skip("native plan core unavailable")
    return TpuProvider(n_docs=n, backend="device")


def text_base():
    """A small text with tombstones and two authors, one update."""
    doc = Y.Doc(gc=False)
    doc.client_id = 11
    text = doc.get_text("text")
    text.insert(0, "the quick brown fox jumps over the lazy dog")
    text.delete(4, 6)
    text.insert(10, " red")
    other = Y.Doc(gc=False)
    other.client_id = 12
    Y.apply_update(other, Y.encode_state_as_update(doc))
    other.get_text("text").insert(3, "!?")
    other.get_text("text").delete(20, 3)
    update = Y.encode_state_as_update(other)
    plain = PlainText.of_items(oracle.items_of(other))
    return update, list(plain.ids), bytes(plain.dead)


def array_base():
    writer = Writer(13, [], b"", "array", "array")
    work_offline(writer, "array", 40, random.Random("base"))
    return (writer.update(), *writer.sequence())


def histories(shape, seed=0):
    """The room's base and its writers' updates, all from one state."""
    kind = "array" if shape == "array" else "text"
    base, ids, dead = array_base() if kind == "array" else text_base()
    updates = []
    for k in range(WRITERS[shape]):
        writer = Writer(3_000_000 + 10 * seed + k, ids, dead, kind, kind)
        work_offline(
            writer, shape, OPERATIONS, random.Random(f"{shape}:{seed}:{k}")
        )
        updates.append(writer.update())
    return kind, base, updates


def held(doc, kind):
    if kind == "array":
        return doc.get_array("array").to_json()
    return doc.get_text("text").to_string()


def read(prov, guid, kind):
    return prov.to_json(guid, "array") if kind == "array" else prov.text(guid)


@pytest.mark.parametrize("mode", ["one_flush", "consecutive"])
@pytest.mark.parametrize("planner", ["native", "python"])
@pytest.mark.parametrize("shape", sorted(WRITERS))
def test_every_arrival_order_gives_the_y_doc(monkeypatch, shape, planner, mode):
    kind, base, updates = histories(shape)
    orders = list(itertools.permutations(range(len(updates))))
    prov = _provider(monkeypatch, planner, n=len(orders))
    eng = prov.engine
    rooms = {f"room-{k}": order for k, order in enumerate(orders)}
    for guid in rooms:
        assert prov.receive_update(guid, base)
    prov.flush()
    m = eng.last_flush_metrics
    assert m["row_links"] == m["n_sched_entries"] > 0 and m["lane_links"] == 0
    if mode == "one_flush":
        for guid, order in rooms.items():
            for k in order:
                assert prov.receive_update(guid, updates[k])
        prov.flush()
        flushes = [eng.last_flush_metrics]
    else:
        flushes = []
        for at in range(len(updates)):
            for guid, order in rooms.items():
                assert prov.receive_update(guid, updates[order[at]])
            prov.flush()
            flushes.append(eng.last_flush_metrics)
    for m in flushes:
        # rooms that hold rows take the element lanes, all of them
        assert m["rooms_row_loaded"] == 0 and m["row_links"] == 0
        assert m["lane_links"] == m["n_sched_entries"] > 0
        assert m["lanes_dispatched"] >= m["lane_links"]
    # siblings in one gap: only the native walk counts its steps (a lone
    # first writer meets none)
    steps = sum(m["conflict_steps"] for m in flushes)
    assert (steps > 0) == (planner == "native")
    assert not eng.fallback and not eng.demotions and not eng.rollbacks
    states = set()
    for guid, order in rooms.items():
        doc = Y.Doc(gc=False)
        for u in [base] + [updates[k] for k in order]:
            Y.apply_update(doc, u)
        want = Y.merge_updates([Y.encode_state_as_update(doc)])
        assert Y.merge_updates([prov.encode_state_as_update(guid)]) == want
        assert prov.state_vector(guid) == Y.decode_state_vector(
            Y.encode_state_vector(doc)
        )
        for device in (False, True):
            eng.export_from_device = device
            assert read(prov, guid, kind) == held(doc, kind), (guid, device)
        eng.export_from_device = False
        states.add(want)
    assert len(states) == 1  # every order, one document
    assert oracle.device_rows_differ(prov, list(rooms)) == 0


@pytest.mark.parametrize("planner", ["native", "python"])
def test_one_at_a_time_gives_the_same_document_as_one_flush(
    monkeypatch, planner
):
    """A room fed its writers' updates a flush each, compacted or not in
    between, and one fed them in one flush serve the same state."""
    prov = _provider(monkeypatch, planner, n=8)
    cases = {shape: histories(shape, seed=1) for shape in WRITERS}
    for shape, (_kind, base, _updates) in cases.items():
        for way in ("once", "each"):
            assert prov.receive_update(f"{shape}-{way}", base)
    prov.flush()
    for shape, (_kind, _base, updates) in cases.items():
        for u in updates:
            assert prov.receive_update(f"{shape}-once", u)
    prov.flush()
    for at in range(3):
        for shape, (_kind, _base, updates) in cases.items():
            if at < len(updates):
                assert prov.receive_update(f"{shape}-each", updates[at])
        prov.flush()
    for shape, (kind, _base, _updates) in cases.items():
        once, each = (
            Y.merge_updates([prov.encode_state_as_update(f"{shape}-{way}")])
            for way in ("once", "each")
        )
        assert once == each
        prov.engine.export_from_device = True
        assert read(prov, f"{shape}-once", kind) == read(
            prov, f"{shape}-each", kind
        )
        prov.engine.export_from_device = False
    guids = [f"{shape}-{way}" for shape in cases for way in ("once", "each")]
    assert oracle.device_rows_differ(prov, guids) == 0


@pytest.mark.parametrize("planner", ["native", "python"])
def test_the_pack_spans_open_once_a_chunk_by_write_path(monkeypatch, planner):
    """``ytpu.pack.rows`` for the chunks that load a room whole,
    ``ytpu.pack.lanes`` for those whose rooms held rows, each inside its
    chunk's ``ytpu.pack`` (``obs.trace.PACK_SPANS``)."""
    from yjs_tpu.obs.trace import PACK_SPANS

    monkeypatch.setenv("YTPU_FLUSH_CHUNK", "2")
    prov = _provider(monkeypatch, planner, n=4)
    kind, base, updates = histories("b2.3", seed=2)

    def spans():
        ring = [e for e in prov.engine.obs.tracer.trace_events() if e["ph"] == "X"]
        count = collections.Counter(e["name"] for e in ring)
        for e in ring:
            if e["name"] in PACK_SPANS:
                assert any(
                    p["name"] == PACK_SPANS[e["name"]] and p["ts"] <= e["ts"]
                    and e["ts"] + e["dur"] <= p["ts"] + p["dur"] for p in ring
                ), e["name"]
        return count

    for k in range(4):
        assert prov.receive_update(f"room-{k}", base)
    prov.flush()  # four rooms from empty, two chunks: rows only
    got = spans()
    assert (got["ytpu.pack"], got["ytpu.pack.rows"], got["ytpu.pack.lanes"]) == (
        2, 2, 0
    )
    for k in range(3):  # three rooms that hold rows, two chunks: lanes only
        assert prov.receive_update(f"room-{k}", updates[0])
    prov.flush()
    got = spans()
    assert (got["ytpu.pack"], got["ytpu.pack.rows"], got["ytpu.pack.lanes"]) == (
        4, 2, 2
    )
    # a chunk with a room of each kind opens both
    prov.release_doc("room-2")
    assert prov.receive_update("room-2", base)
    assert prov.receive_update("room-3", updates[1])
    prov.flush()
    got = spans()
    assert (got["ytpu.pack"], got["ytpu.pack.rows"], got["ytpu.pack.lanes"]) == (
        5, 3, 3
    )
    m = prov.engine.last_flush_metrics
    assert m["lane_links"] > 0 and m["row_links"] > 0
    assert m["lane_links"] + m["row_links"] == m["n_sched_entries"]


def test_the_new_counters_are_in_the_schema():
    from yjs_tpu.obs import FLUSH_METRICS_SCHEMA

    assert {
        "lane_links", "row_links", "lanes_dispatched", "conflict_steps",
    } <= set(FLUSH_METRICS_SCHEMA)


def test_conflict_steps_count_the_siblings_walked(monkeypatch):
    """Three writers append at the same place: the second's struct walks
    past the first's, the third's past both (client ids ascending, so
    each new struct goes behind its siblings)."""
    prov = _provider(monkeypatch, "native", n=2)
    base = Y.Doc(gc=False)
    base.client_id = 5
    base.get_text("text").insert(0, "ab")
    update = Y.encode_state_as_update(base)
    assert prov.receive_update("room", update)
    prov.flush()
    tails = []
    for client in (21, 22, 23):
        doc = Y.Doc(gc=False)
        doc.client_id = client
        Y.apply_update(doc, update)
        doc.get_text("text").insert(2, "x")
        tails.append(Y.encode_state_as_update(
            doc, Y.encode_state_vector(base)
        ))
    steps = []
    for u in tails:
        assert prov.receive_update("room", u)
        prov.flush()
        steps.append(prov.engine.last_flush_metrics["conflict_steps"])
    assert steps == [0, 1, 2]
    assert prov.text("room") == "abxxx"
