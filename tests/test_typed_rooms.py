"""Rooms whose roots are not the provider's one ``root_name``: the read
accessors take the root's name; a flush that gains segments widens the
table of list heads and no other; what a flush integrated is counted by
kind, on both planners."""

import numpy as np
import pytest

import yjs_tpu as Y
from yjs_tpu.obs import FLUSH_METRICS_SCHEMA
from yjs_tpu.ops.engine import BatchEngine
from yjs_tpu.provider import TpuProvider

PLANNERS = ["native", "python"]


def planner(monkeypatch, which):
    if which == "python":
        monkeypatch.setenv("YTPU_NO_NATIVE_PLAN", "1")


def typed_doc(client=1):
    """A document with one root of every kind."""
    d = Y.Doc(gc=False)
    d.client_id = client
    d.get_text("text").insert(0, "plain")
    notes = d.get_text("notes")
    notes.insert(0, "bold and not")
    notes.format(0, 4, {"strong": {}})
    frag = d.get_xml_fragment("prosemirror")
    p = Y.YXmlElement("paragraph")
    p.set_attribute("textAlign", "center")
    t = Y.YXmlText()
    t.insert(0, "hello ")
    t.insert(6, "world", {"em": {}})
    p.insert(0, [t])
    frag.insert(0, [p])
    d.get_map("meta").set("title", "a title")
    d.get_map("meta").set("tags", Y.YArray())
    d.get_map("meta").get("tags").insert(0, [1, 2])
    d.get_array("list").insert(0, ["a", {"b": 1}])
    return d


@pytest.mark.parametrize("which", PLANNERS)
def test_the_read_accessors_take_the_roots_name(monkeypatch, which):
    planner(monkeypatch, which)
    d = typed_doc()
    prov = TpuProvider(n_docs=4, backend="device")
    assert prov.receive_update("room", Y.encode_state_as_update(d))
    prov.flush()
    assert not prov.engine.fallback
    # the default is the provider's one root name, as before
    assert prov.text("room") == "plain" == prov.text("room", "text")
    assert prov.to_delta("room") == [{"insert": "plain"}]
    assert prov.xml_string("room") == "plain"
    # by name: the roots the provider was not built around
    assert prov.text("room", "notes") == "bold and not"
    assert prov.to_delta("room", name="notes") == d.get_text("notes").to_delta()
    want = d.get_xml_fragment("prosemirror").to_string()
    assert want == '<paragraph textAlign="center">hello <em>world</em></paragraph>'
    for device in (False, True):
        prov.engine.export_from_device = device
        assert prov.xml_string("room", "prosemirror") == want
        assert prov.map_json("room", "meta") == d.get_map("meta").to_json()
        assert prov.to_json("room", "list") == d.get_array("list").to_json()
    assert prov.xml_string("room", "no-such-root") == ""
    assert prov.map_json("room", "no-such-root") == {}
    # a snapshot view by name too
    snap = prov.snapshot("room")
    assert prov.to_delta("room", snapshot=snap, name="notes") == (
        d.get_text("notes").to_delta()
    )


def paragraphs(d, n, start=0):
    frag = d.get_xml_fragment("prosemirror")
    for k in range(n):
        p = Y.YXmlElement("paragraph")
        t = Y.YXmlText()
        t.insert(0, f"paragraph {start + k}")
        p.insert(0, [t])
        frag.insert(len(frag), [p])


@pytest.mark.parametrize("which", PLANNERS)
def test_a_flush_that_gains_segments_widens_the_list_heads_alone(
    monkeypatch, which
):
    """Six rooms; one gains paragraphs until its segments cross a power
    of two: ``_starts`` is reallocated, ``_right`` and ``_deleted`` keep
    their buffers' shape, ``realloc_bytes`` is the new ``_starts``'
    bytes, and every room still reads right from the device."""
    planner(monkeypatch, which)
    eng = BatchEngine(6, root_name="prosemirror")
    docs = []
    for i in range(6):
        d = Y.Doc(gc=False)
        d.client_id = i + 1
        paragraphs(d, 3)
        docs.append(d)
        eng.queue_update(i, Y.encode_state_as_update(d))
    eng.flush()
    cap, seg_cap = eng._cap, eng._seg_cap
    assert seg_cap == 8 and eng.last_flush_metrics["n_segs_max"] == 7
    assert eng.last_flush_metrics["seg_cap"] == 8
    sent = []
    docs[2].on("update", lambda u, *_: sent.append(u))
    paragraphs(docs[2], 1, start=3)  # 9 segments: over the edge
    eng.queue_update(2, sent.pop())
    eng.flush()
    m = eng.last_flush_metrics
    assert (eng._cap, eng._seg_cap) == (cap, 16) and m["n_segs_max"] == 9
    assert eng._right.shape == eng._deleted.shape == (6, cap + 1)
    assert eng._starts.shape == (6, 17)
    assert m["realloc_bytes"] == eng._starts.nbytes == 6 * 17 * 4
    assert m["flush_donated"] == 0 and m["segs_created"] == 2
    # the next flush grows nothing
    paragraphs(docs[2], 1, start=4)
    eng.queue_update(2, sent.pop())
    eng.flush()
    assert eng.last_flush_metrics["realloc_bytes"] == 0
    assert eng.last_flush_metrics["flush_donated"] == 1
    eng.export_from_device = True
    for i, d in enumerate(docs):
        assert eng.xml_string(i) == d.get_xml_fragment("prosemirror").to_string()
    heads = np.asarray(eng._starts)
    for i in range(6):
        mirror = eng.mirrors[i]
        assert heads[i, : mirror.n_segs].tolist() == list(mirror.head_of_seg)
    # rows that grow past their bucket still widen the row tables alone
    big = Y.Doc(gc=False)
    big.client_id = 99
    for k in range(cap + 10):
        big.get_text("prosemirror-notes").insert(0, "x")
    eng.queue_update(5, Y.encode_state_as_update(big))
    eng.flush()
    assert eng._cap > cap and eng._seg_cap == 16
    assert eng.last_flush_metrics["realloc_bytes"] == (
        eng._right.nbytes + eng._deleted.nbytes
    )


@pytest.mark.parametrize("which", PLANNERS)
def test_a_flush_counts_what_it_integrated_by_kind(monkeypatch, which):
    planner(monkeypatch, which)
    kinds = {
        "rows_planned", "rows_nested", "rows_format", "rows_attr", "rows_type",
        "segs_created", "lww_overwritten", "format_deleted",
        "format_cleanup_deleted", "format_cleanup_texts", "n_segs_max", "seg_cap",
    }
    assert kinds <= set(FLUSH_METRICS_SCHEMA)
    eng = BatchEngine(2, root_name="prosemirror")
    d = Y.Doc(gc=False)
    d.client_id = 7
    sent = []
    d.on("update", lambda u, *_: sent.append(u))
    frag = d.get_xml_fragment("prosemirror")
    h = Y.YXmlElement("heading")
    h.set_attribute("level", 1)
    t = Y.YXmlText()
    t.insert(0, "a title here")
    h.insert(0, [t])
    frag.insert(0, [h])

    def flush():
        for u in sent:
            eng.queue_update(0, u)
        sent.clear()
        eng.flush()
        return eng.last_flush_metrics

    m = flush()
    # element, text, string, attribute: the element alone has a root's name
    assert (m["rows_planned"], m["rows_nested"], m["rows_type"], m["rows_attr"]) == (
        4, 3, 2, 1
    )
    assert (m["segs_created"], m["n_segs_max"]) == (4, 4)
    h.set_attribute("level", 2)
    m = flush()
    assert (m["rows_planned"], m["rows_attr"], m["lww_overwritten"]) == (1, 1, 1)
    assert m["segs_created"] == 0
    t.format(2, 5, {"strong": {}})
    m = flush()
    assert (m["rows_format"], m["format_deleted"]) == (2, 0)
    assert m["format_cleanup_texts"] == 1 and m["format_cleanup_deleted"] == 0
    t.format(2, 5, {"strong": None})
    m = flush()
    assert m["format_deleted"] >= 1
    assert eng.xml_string(0) == '<heading level="2">a title here</heading>'
    reg = eng.obs.registry
    by_kind = reg.get("ytpu_flush_rows_by_kind_total")
    assert by_kind.labels(kind="attr").value == 2
    assert by_kind.labels(kind="type").value == 2
    assert reg.get("ytpu_flush_lww_overwritten_total").value == 1
    assert reg.get("ytpu_flush_segments_created_total").value == 4
    assert reg.get("ytpu_engine_segment_capacity").value == eng._seg_cap
    # a flush with nothing to plan reports zeros and the capacity
    eng.flush()
    m = eng.last_flush_metrics
    assert m["rows_planned"] == 0 and m["seg_cap"] == eng._seg_cap


def test_a_served_compaction_is_one_staged_shape():
    """One device, a few short rooms that doubled: one block of
    ``_SERVED_ROOMS x _SERVED_WIDTH`` whatever the rooms' number and
    length (one ``scatter_rows`` program for a served process), its
    spare rows dropped; the device holds what the host does."""
    from yjs_tpu.ops import engine as E

    eng = BatchEngine(6)
    eng.compact_min_rows = 8
    long = Y.Doc(gc=False)
    long.client_id = 50
    for _ in range(E._SERVED_WIDTH + 8):
        long.get_text("text").insert(0, "x")
    eng.queue_update(5, Y.encode_state_as_update(long))
    eng.flush()
    assert eng._cap >= E._SERVED_WIDTH
    docs, sent = [], []
    for i in range(3):
        d = Y.Doc(gc=False)
        d.client_id = i + 1
        d.on("update", lambda u, _o, _d, i=i: sent.append((i, u)))
        docs.append(d)
    shapes = []
    for rnd in range(40):
        for i, d in enumerate(docs[: 1 + rnd % 3]):
            t = d.get_text("text")
            t.insert(len(t.to_string()) // 2, "ab")
            # and a tail of two keystrokes, an update each: rows that
            # merge, or a room that has doubled is asked and left alone
            for key in "yz":
                t.insert(len(t.to_string()), key)
            if rnd % 4 == 3:
                t.delete(0, 1)
        for i, u in sent:
            eng.queue_update(i, u)
        sent.clear()
        before = eng.last_compaction
        eng.flush()
        m = eng.last_flush_metrics
        if eng.last_compaction is not before:
            if any(c["doc"] == 5 for c in eng.last_compaction):
                continue  # the long room's own: as wide as its rows
            shapes.append((len(eng.last_compaction), m["rows_staged_bytes"]))
            assert m["rows_staged_blocks"] == 1
    block = E._SERVED_ROOMS * (
        E._SERVED_WIDTH * 5 + (eng._seg_cap + 1) * 4
    )
    assert len(shapes) >= 4 and {n for n, _b in shapes} >= {1, 2}
    assert {b for _n, b in shapes} == {block}
    eng.export_from_device = True
    for i, d in enumerate(docs):
        assert eng.text(i) == d.get_text("text").to_string()
    right, heads = np.asarray(eng._right), np.asarray(eng._starts)
    for i in range(6):
        mirror = eng.mirrors[i]
        n = mirror.n_rows
        assert right[i, :n].tolist() == [int(x) for x in mirror.list_next[:n]]
        assert (right[i, n : eng._cap] == -1).all()
        assert heads[i, : mirror.n_segs].tolist() == list(mirror.head_of_seg)
    assert eng.text(5) == long.get_text("text").to_string()
