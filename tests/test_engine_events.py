"""Engine-path typed events vs the CPU doc's YEvent on the same traffic
(observe for engine-hosted docs, reference
YEvent.js:85-187, AbstractType.js:360-389)."""

import pytest

import yjs_tpu as Y
from yjs_tpu.ids import find_root_type_key
from yjs_tpu.ops import BatchEngine
from yjs_tpu.types.events import YEvent, get_path_to


def cpu_events_for(doc, update):
    """Apply one update to a CPU doc, capturing YEvent-shaped dicts."""
    captured = []

    def after_transaction(transaction, d):
        for typ in transaction.changed:
            root = typ
            while root._item is not None:
                root = root._item.parent
            ev = YEvent(typ, transaction)
            ch = ev.changes
            if not ch["delta"] and not ch["keys"]:
                continue
            captured.append({
                "path": [find_root_type_key(root)] + get_path_to(root, typ),
                "delta": ch["delta"],
                "keys": ch["keys"],
            })
    doc.on("afterTransaction", after_transaction)
    Y.apply_update(doc, update)
    doc.off("afterTransaction", after_transaction)
    return captured


def _old_repr(v):
    # nested shared types compare by kind: the engine's oldValue is an
    # unbound type shell (the mirror holds nested content in its own
    # segments), the CPU's is the live instance
    if hasattr(v, "to_json") and not isinstance(v, (str, bytes)):
        return type(v).__name__
    return repr(v)


def norm(events):
    """Order-independent comparable form."""
    def freeze(ev):
        return (
            tuple(ev["path"]),
            tuple(
                tuple(sorted(op.items(), key=lambda kv: kv[0]))
                if not any(isinstance(v, list) for v in op.values())
                else (("insert", tuple(op["insert"])),)
                for op in ev["delta"]
            ),
            tuple(sorted(
                (k, v["action"], _old_repr(v["oldValue"]))
                for k, v in ev["keys"].items()
            )),
        )
    return sorted(freeze(e) for e in events)


def session_updates(rng, n_rounds=40, nested=False):
    a = Y.Doc(gc=False); a.client_id = 11
    b = Y.Doc(gc=False); b.client_id = 22
    updates = []
    for _ in range(n_rounds):
        for d in (a, b):
            sv = Y.encode_state_vector(d)
            t = d.get_text("text")
            m = d.get_map("meta")
            arr = d.get_array("list")
            op = rng.random()
            if op < 0.4 or len(t) == 0:
                t.insert(rng.randint(0, len(t)), rng.choice(
                    ["hey ", "ho ", "let's ", "go "]))
            elif op < 0.55:
                pos = rng.randrange(len(t))
                t.delete(pos, min(rng.randint(1, 4), len(t) - pos))
            elif op < 0.7:
                m.set(rng.choice("xyz"), rng.randint(0, 9))
            elif op < 0.8 and m.get(rng.choice("xyz")) is not None:
                k = rng.choice("xyz")
                if m.get(k) is not None:
                    m.delete(k)
            elif op < 0.9:
                arr.insert(rng.randint(0, len(arr)), [rng.randint(0, 99)])
            elif nested:
                nm = Y.YMap()
                m.set("nested", nm)
                nm.set("deep", rng.randint(0, 9))
            updates.append(Y.encode_state_as_update(d, sv))
        if rng.random() < 0.5:
            ua = Y.encode_state_as_update(a, Y.encode_state_vector(b))
            ub = Y.encode_state_as_update(b, Y.encode_state_vector(a))
            Y.apply_update(b, ua)
            Y.apply_update(a, ub)
    return updates


@pytest.mark.parametrize("nested", [False, True])
def test_engine_events_match_cpu(rng, nested):
    updates = session_updates(rng, nested=nested)
    cpu = Y.Doc(gc=False)
    eng = BatchEngine(1)
    got: list = []
    eng.observe(0, lambda doc, evs: got.extend(evs))
    for u in updates:
        expect = cpu_events_for(cpu, u)
        got.clear()
        eng.queue_update(0, u)
        eng.flush()
        assert norm(got) == norm(expect), f"events diverged on update"


def test_provider_observe_path_filter(rng):
    from yjs_tpu.provider import TpuProvider

    p = TpuProvider(2)
    text_evs, all_evs = [], []
    p.observe("room", ["text"], lambda g, ev: text_evs.append(ev))
    p.observe("room", [], lambda g, ev: all_evs.append(ev))
    d = Y.Doc(gc=False)
    d.client_id = 5
    d.get_text("text").insert(0, "hi")
    d.get_map("meta").set("k", 1)
    p.receive_update("room", Y.encode_state_as_update(d))
    p.flush()
    assert any(ev["path"] == ["text"] for ev in text_evs)
    assert all(ev["path"][0] == "text" for ev in text_evs)
    assert {tuple(ev["path"]) for ev in all_evs} >= {("text",), ("meta",)}
    delta = next(ev for ev in text_evs if ev["path"] == ["text"])["delta"]
    assert delta == [{"insert": ["h", "i"]}]


def test_events_after_demotion(rng):
    """Demoted docs keep delivering the same event shape via the CPU core."""
    eng = BatchEngine(1)
    got: list = []
    eng.observe(0, lambda doc, evs: got.extend(evs))
    d = Y.Doc(gc=False)
    d.client_id = 7
    d.get_text("text").insert(0, "ab")
    eng.queue_update(0, Y.encode_state_as_update(d))
    eng.flush()
    assert got and got[0]["path"] == ["text"]
    got.clear()
    # subdoc traffic demotes the doc; the demoting flush's own changes
    # still deliver (the CPU bridge attaches at the pre-flush boundary of
    # the replay), and events keep flowing afterwards
    sub = Y.Doc()
    d.get_map("m").set("sub", sub)
    eng.queue_update(0, Y.encode_state_as_update(d, None))
    eng.flush()
    assert 0 in eng.fallback
    assert any(
        ev["path"] == ["m"] and "sub" in ev["keys"] for ev in got
    ), got
    got.clear()
    sv = Y.encode_state_vector(d)
    d.get_text("text").insert(2, "cd")
    eng.queue_update(0, Y.encode_state_as_update(d, sv))
    eng.flush()
    assert any(
        ev["path"] == ["text"] and {"retain": 2} in ev["delta"]
        for ev in got
    )


def test_engine_to_delta_matches_cpu(rng):
    """Mirror-served attributed delta vs the CPU doc (reference YText.toDelta YText.js:936-1030)."""
    a = Y.Doc(gc=False); a.client_id = 31
    b = Y.Doc(gc=False); b.client_id = 32
    updates = []
    for _ in range(120):
        for d in (a, b):
            sv = Y.encode_state_vector(d)
            t = d.get_text("text")
            op = rng.random()
            if op < 0.4 or len(t) == 0:
                t.insert(rng.randint(0, len(t)), rng.choice(
                    ["plain ", "words "]))
            elif op < 0.6 and len(t) > 2:
                pos = rng.randrange(len(t) - 1)
                t.format(pos, rng.randint(1, min(4, len(t) - pos)), rng.choice([
                    {"bold": True}, {"italic": True}, {"bold": None},
                    {"color": "red"},
                ]))
            elif op < 0.75:
                pos = rng.randrange(len(t))
                t.delete(pos, min(rng.randint(1, 4), len(t) - pos))
            elif op < 0.85:
                t.insert_embed(rng.randint(0, len(t)), {"img": "x.png"})
            else:
                t.insert(rng.randint(0, len(t)), "styled",
                         rng.choice([{"bold": True}, {"em": True}]))
            updates.append(Y.encode_state_as_update(d, sv))
        if rng.random() < 0.5:
            ua = Y.encode_state_as_update(a, Y.encode_state_vector(b))
            ub = Y.encode_state_as_update(b, Y.encode_state_vector(a))
            Y.apply_update(b, ua)
            Y.apply_update(a, ub)
    ua = Y.encode_state_as_update(a, Y.encode_state_vector(b))
    Y.apply_update(b, ua)
    updates.append(ua)

    cpu = Y.Doc(gc=False)
    eng = BatchEngine(1)
    for j, u in enumerate(updates):
        Y.apply_update(cpu, u)
        eng.queue_update(0, u)
        if j % 7 == 6:
            eng.flush()
            assert eng.to_delta(0) == cpu.get_text("text").to_delta()
    eng.flush()
    assert eng.to_delta(0) == cpu.get_text("text").to_delta()
    assert eng.to_delta(0)  # non-trivial traffic produced ops


def test_engine_xml_string_matches_cpu(rng):
    """Engine-served XML serialization vs the CPU doc (reference
    YXmlFragment/YXmlElement/YXmlText toString)."""
    a = Y.Doc(gc=False); a.client_id = 41
    b = Y.Doc(gc=False); b.client_id = 42
    updates = []
    tags = ["div", "p", "span"]
    for _ in range(60):
        for d in (a, b):
            sv = Y.encode_state_vector(d)
            frag = d.get_xml_fragment("xml")
            op = rng.random()
            if op < 0.35 or len(frag) == 0:
                el = Y.YXmlElement(rng.choice(tags))
                frag.insert(rng.randint(0, len(frag)), [el])
            elif op < 0.55:
                el = frag.get(rng.randrange(len(frag)))
                if isinstance(el, Y.YXmlElement):
                    el.set_attribute(rng.choice("ab"), str(rng.randint(0, 9)))
                    if rng.random() < 0.4:
                        child = Y.YXmlText()
                        el.insert(0, [child])
            elif op < 0.7:
                el = frag.get(rng.randrange(len(frag)))
                if isinstance(el, Y.YXmlElement) and len(el) > 0:
                    sub = el.get(0)
                    if isinstance(sub, Y.YXmlText):
                        sub.insert(0, rng.choice(["hi ", "yo "]))
                        if rng.random() < 0.5 and len(sub) > 1:
                            sub.format(0, 2, {"b": {"w": "1"}})
            elif op < 0.85:
                pos = rng.randrange(len(frag))
                frag.delete(pos, 1)
            else:
                t = Y.YXmlText()
                frag.insert(rng.randint(0, len(frag)), [t])
            updates.append(Y.encode_state_as_update(d, sv))
        if rng.random() < 0.5:
            ua = Y.encode_state_as_update(a, Y.encode_state_vector(b))
            ub = Y.encode_state_as_update(b, Y.encode_state_vector(a))
            Y.apply_update(b, ua)
            Y.apply_update(a, ub)
    ua = Y.encode_state_as_update(a, Y.encode_state_vector(b))
    Y.apply_update(b, ua)
    updates.append(ua)

    cpu = Y.Doc(gc=False)
    eng = BatchEngine(1, root_name="xml")
    for j, u in enumerate(updates):
        Y.apply_update(cpu, u)
        eng.queue_update(0, u)
        if j % 9 == 8:
            eng.flush()
            assert eng.xml_string(0) == cpu.get_xml_fragment("xml").to_string()
    eng.flush()
    expect = cpu.get_xml_fragment("xml").to_string()
    assert eng.xml_string(0) == expect
    assert expect  # non-trivial traffic


# ---------------------------------------------------------------------------
# Event-path INDEX parity.  getPathTo (YEvent.js:207-228)
# counts undeleted ITEMS before the nested type — a count that depends on
# run-merge state, which differs between the CPU store (merges eagerly at
# cleanup) and the mirror (merges only at compaction).  These sessions put
# nested types inside ARRAYS behind char-by-char typed prefixes (one update
# per keystroke = maximally merge-sensitive) and behind deletions, for all
# three list kinds: array, xml children, and nested array-in-array.
# ---------------------------------------------------------------------------


def _nested_list_session(rng, n_rounds=30):
    a = Y.Doc(gc=False); a.client_id = 31
    b = Y.Doc(gc=False); b.client_id = 42
    updates = []
    nested_keys = []
    for rnd in range(n_rounds):
        for d in (a, b):
            sv = Y.encode_state_vector(d)
            arr = d.get_array("list")
            xml = d.get("xml", Y.YXmlElement)
            op = rng.random()
            if op < 0.35:
                # char-by-char prefix typing: each keystroke is its own
                # update, so the mirror holds N rows where the CPU store
                # holds one merged item
                arr.insert(rng.randint(0, len(arr)), [rng.choice("abcdef")])
            elif op < 0.5:
                nm = Y.YMap()
                arr.insert(rng.randint(0, len(arr)), [nm])
                nm.set("born", rnd)
            elif op < 0.6 and len(arr):
                pos = rng.randrange(len(arr))
                arr.delete(pos, 1)
            elif op < 0.75:
                # edit a nested map that lives at some array index: the
                # event path is ["list", <item-count index>]
                for i in range(len(arr)):
                    v = arr.get(i)
                    if hasattr(v, "set"):
                        v.set(rng.choice("pq"), rnd)
                        break
                else:
                    arr.insert(0, [rng.randint(0, 9)])
            elif op < 0.85:
                t = Y.YXmlText()
                xml.insert(rng.randint(0, xml.length), [t])
                t.insert(0, rng.choice(["hi", "yo"]))
            else:
                # edit an existing xml text child -> path ["xml", index]
                n = xml._first_child() if hasattr(xml, "_first_child") else None
                edited = False
                for i in range(xml.length):
                    c = xml.get(i)
                    if isinstance(c, Y.YXmlText):
                        c.insert(len(c.to_string()), "!")
                        edited = True
                        break
                if not edited:
                    xml.insert(0, [Y.YXmlText()])
            updates.append(Y.encode_state_as_update(d, sv))
        if rng.random() < 0.5:
            ua = Y.encode_state_as_update(a, Y.encode_state_vector(b))
            ub = Y.encode_state_as_update(b, Y.encode_state_vector(a))
            Y.apply_update(b, ua)
            Y.apply_update(a, ub)
    del nested_keys
    return updates


def _norm_types(events):
    """norm() with nested-type delta inserts compared by KIND: the engine
    materializes unbound shells for nested types while the CPU yields the
    live instances, so identity can never match (same convention as
    _old_repr for map values)."""
    out = []
    for ev in events:
        ev = dict(ev)
        delta = []
        for op in ev.get("delta", []):
            if isinstance(op.get("insert"), list):
                op = dict(op)
                op["insert"] = [
                    type(v).__name__
                    if hasattr(v, "to_json") and not isinstance(v, (str, bytes))
                    else v
                    for v in op["insert"]
                ]
            delta.append(op)
        ev["delta"] = delta
        out.append(ev)
    return norm(out)


def test_event_path_parity_nested_lists(rng):
    """CPU-vs-engine path equality for nested types in arrays/xml under
    merge-sensitive traffic (the r4 documented divergence, now fixed by
    counting CPU-merged-item runs in ops/events._path_of)."""
    updates = _nested_list_session(rng)
    cpu = Y.Doc(gc=False)
    eng = BatchEngine(1)
    got: list = []
    eng.observe(0, lambda doc, evs: got.extend(evs))
    for u in updates:
        expect = cpu_events_for(cpu, u)
        got.clear()
        eng.queue_update(0, u)
        eng.flush()
        assert _norm_types(got) == _norm_types(expect), "event paths diverged"


def test_event_path_parity_after_compaction(rng):
    """Same parity with a 4-row compaction threshold: compacted mirrors
    merge rows themselves, so the run-grouping must stay consistent."""
    updates = _nested_list_session(rng, n_rounds=20)
    cpu = Y.Doc(gc=False)
    eng = BatchEngine(1, gc=False, compact_min_rows=4)
    got: list = []
    eng.observe(0, lambda doc, evs: got.extend(evs))
    for u in updates:
        expect = cpu_events_for(cpu, u)
        got.clear()
        eng.queue_update(0, u)
        eng.flush()
        assert _norm_types(got) == _norm_types(expect), "event paths diverged post-compaction"
