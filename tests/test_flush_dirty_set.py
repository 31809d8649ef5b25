"""A flush looks only at the rooms that took an update (``BatchEngine.
_dirty_docs``, ``_compact_look``): held, at every flush of a seeded run,
to the two walks over every slot that it replaces, which the tests keep
as the reference."""

import random

import pytest

import yjs_tpu as Y
from yjs_tpu.ops import BatchEngine
from yjs_tpu.ops.native_mirror import NativeMirror, native_plan_available

GARBAGE = b"\x01\xff\xff\xff"


def _engine(monkeypatch, planner, n, mesh=False, **kw):
    if planner == "python":
        monkeypatch.setenv("YTPU_NO_NATIVE_PLAN", "1")
    elif not native_plan_available():
        pytest.skip("native plan core unavailable")
    if mesh:
        from yjs_tpu.parallel import doc_mesh

        try:
            kw["mesh"] = doc_mesh(4, backend="cpu")
        except RuntimeError as e:  # YTPU_TEST_PLATFORM=tpu: one chip
            pytest.skip(f"no CPU mesh beside this backend: {e}")
    return BatchEngine(n, **kw)


def walk_of_every_slot(eng):
    """The plan phase's walk as it was: every slot, in slot order."""
    native = native_plan_available() and any(
        isinstance(m, NativeMirror) for m in eng.mirrors
    )
    out = []
    for i, m in enumerate(eng.mirrors):
        if i in eng.fallback:
            continue
        if native:
            if isinstance(m, NativeMirror) and (m._incoming or m._had_pending):
                out.append(i)
        elif m._incoming or m.has_pending():
            out.append(i)
    return out


def scan_of_every_slot(eng):
    """``_maybe_compact``'s scan as it was: ``n_rows`` of every slot."""
    return [
        i
        for i, m in enumerate(eng.mirrors)
        if i not in eng.fallback
        and m.n_rows >= max(eng.compact_min_rows, 2 * eng._rows_at_compact[i])
    ]


class Rooms:
    """Plain clients and a CPU ``Y.Doc`` a slot; what is sent to the
    engine is applied to the slot's ``Y.Doc`` too."""

    def __init__(self, eng, rng):
        self.eng, self.rng = eng, rng
        self.n = eng.n_docs
        self.next_client = 1
        self.client = {}
        self.oracle = {}
        self.late = {}  # slot -> the update whose successor went ahead
        for i in range(self.n):
            self.let(i)

    def let(self, i):
        d = Y.Doc(gc=False)
        d.client_id = self.next_client
        self.next_client += 1
        self.client[i], self.oracle[i] = d, Y.Doc(gc=False)
        self.late.pop(i, None)

    def keystroke(self, i, prepend=False):
        d = self.client[i]
        sv = Y.encode_state_vector(d)
        t = d.get_text("text")
        if len(t) and not prepend and self.rng.random() < 0.3:
            t.delete(self.rng.randrange(len(t)), 1)
        else:
            at = 0 if prepend else self.rng.randint(0, len(t))
            t.insert(at, self.rng.choice("abcdefgh "))
        return Y.encode_state_as_update(d, sv)

    def send(self, i, u):
        Y.apply_update(self.oracle[i], u)
        assert self.eng.queue_update(i, u)

    def type(self, i, n=1, prepend=False):
        for _ in range(n):
            self.send(i, self.keystroke(i, prepend))

    def out_of_order(self, i):
        """Two keystrokes, the second sent first: its structs park."""
        if i in self.late:
            return
        self.late[i] = self.keystroke(i)
        self.send(i, self.keystroke(i))

    def arrive(self, i):
        self.send(i, self.late.pop(i))

    def check(self, fed=()):
        """Every room but ``fed`` (those a listener typed into while the
        flush ran: the next flush's) against its ``Y.Doc``."""
        eng = self.eng
        for i in range(self.n):
            if i in fed:
                assert eng.mirrors[i]._incoming
                continue
            ref = self.oracle[i]
            assert eng.text(i) == ref.get_text("text").to_string(), i
            want = Y.decode_state_vector(Y.encode_state_vector(ref))
            assert eng.state_vector(i) == want, i


CASES = [
    ("native", 8, False), ("native", 512, False),
    ("python", 8, False), ("python", 512, False),
    ("native", 8, True), ("python", 512, True),
]


@pytest.mark.parametrize(
    "planner,min_rows,mesh", CASES,
    ids=[f"{p}-{r}-{'mesh4' if m else 'one'}" for p, r, m in CASES],
)
def test_the_sets_are_the_walks_at_every_flush(
    monkeypatch, planner, min_rows, mesh
):
    n = 64
    eng = _engine(
        monkeypatch, planner, n, mesh=mesh, gc=True, compact_min_rows=min_rows,
    )
    rng = random.Random(f"dirty:{planner}:{min_rows}:{mesh}")
    rooms = Rooms(eng, rng)
    echo = {}  # room heard -> room its listener types into, once a flush
    fed = set()

    def listener(doc, _update):
        to = echo.pop(doc, None)
        if to is not None and to not in eng.fallback:
            rooms.type(to)
            fed.add(to)

    eng.on_update(listener)
    hydrated, compacted, skipped, engaged = set(), 0, 0, set()
    for rnd in range(28):
        live = [i for i in range(n) if i not in eng.fallback]
        for i in rng.sample(live, rng.randint(0, 9)):
            rooms.type(i, rng.randint(1, 3))
        if rnd in (2, 3):
            rooms.type(5, 300, prepend=True)  # no two rows merge: it doubles
            # typed in anywhere, three keystrokes in ten backspaces: it
            # doubles too, and has deleted content for ``gc`` to drop
            rooms.type(6, 300)
        if rnd % 3 == 0:
            rooms.out_of_order(rng.choice(live))
        for i in [i for i in rooms.late if rng.random() < 0.3]:
            rooms.arrive(i)
        if rnd % 5 == 1:
            a, b = rng.sample(live, 2)
            rooms.type(a)
            echo[a] = b
        if rnd in (9, 17):
            bad = rng.choice([i for i in live if i != 5])
            rooms.late.pop(bad, None)
            eng.queue_update(bad, GARBAGE)  # rolled back to the CPU core
        # the sets against the walks over every slot, before the flush
        want = walk_of_every_slot(eng)
        assert set(want) <= eng._dirty_docs
        assert eng._dirty_docs - set(want) <= hydrated
        scan = scan_of_every_slot(eng)
        assert [i for i in sorted(eng._compact_look) if i in scan] == scan
        look = len(eng._compact_look)
        # of the rooms that have doubled, those a rebuild would change
        # (tests/test_compact_skip.py holds the answer to the rebuild)
        rebuilt = [
            i for i in scan
            if not isinstance(eng.mirrors[i], NativeMirror)
            or NativeMirror.compact_changes_many([eng.mirrors[i]], eng.gc)[0]
        ]
        eng.last_compaction = None
        eng.flush()
        m = eng.last_flush_metrics
        assert m["rooms_dirty"] == len(want) + len(hydrated - set(want))
        assert m["rooms_compact_looked"] == look
        assert m["rooms_compact_skipped"] == len(scan) - len(rebuilt)
        if rebuilt:
            assert [s["doc"] for s in eng.last_compaction] == rebuilt
            compacted += len(rebuilt)
        else:
            assert eng.last_compaction is None
        skipped += len(scan) - len(rebuilt)
        engaged.add(m["rooms_dirty"])
        hydrated.clear()
        # what a flush leaves: rooms a listener fed, rooms that park structs
        assert eng._dirty_docs == set(walk_of_every_slot(eng))
        rooms.check(fed)
        if fed:
            # the rooms fed inside a flush are the next one's: flush them
            # now, so that what follows meets rooms with nothing queued
            assert fed <= eng._dirty_docs
            fed.clear()
            eng.flush()
            rooms.check()
        # between flushes: a slot re-let, a room moved, a forced pass
        if rnd % 4 == 2:
            i = rng.choice(live)
            eng.reset_doc(i)
            rooms.let(i)
            assert i not in eng._dirty_docs and i not in eng._compact_look
        if rnd % 6 == 3:
            free = rng.choice(range(n))
            eng.reset_doc(free)
            rooms.let(free)
            parked = [i for i in rooms.late if i != free]
            src = parked[0] if parked else rng.choice(
                [i for i in live if i not in (free, 5)]
            )
            if src in eng.fallback or eng.mirrors[src]._incoming:
                continue
            mirror, log = eng.export_doc_columns(src), eng._update_log[src]
            eng.reset_doc(src)
            eng.hydrate_doc_columns(free, mirror)
            eng._update_log[free] = log  # as the tier manager does
            for d in (rooms.client, rooms.oracle, rooms.late):
                if src in d:
                    d[free] = d.pop(src)
            rooms.let(src)
            hydrated.add(free)
        if rnd % 7 == 4:
            eng.compact_docs(rng.sample(range(n), 6))
    assert eng.fallback and compacted
    # a native room with nothing to merge is asked and left (room 5, where
    # the seed sends it no backspace before it has doubled); a Python
    # mirror is never asked
    if planner == "python":
        assert not skipped
    elif min_rows == 8:
        assert skipped
    assert max(engaged) < n  # never every slot


def test_a_flush_costs_what_its_rooms_cost(monkeypatch):
    """4096 slots, three rooms typed into: the flush visits three slots
    and reads three ``n_rows``, whatever the number of slots."""
    eng = _engine(monkeypatch, "native", 4096, compact_min_rows=1 << 30)
    rooms = Rooms(eng, random.Random(5))
    typed = [7, 1900, 4095]
    for i in typed:
        rooms.type(i, 2)
    eng.flush()
    assert eng.last_flush_metrics["rooms_dirty"] == 3
    assert eng.last_flush_metrics["rooms_compact_looked"] == 0
    read = []
    real = NativeMirror.n_rows.fget

    def counted(mirror):
        read.append(id(mirror))
        return real(mirror)

    monkeypatch.setattr(NativeMirror, "n_rows", property(counted))
    for i in reversed(typed):
        rooms.type(i)
    eng.flush()
    m = eng.last_flush_metrics
    assert (m["rooms_dirty"], m["rooms_compact_looked"]) == (3, 3)
    assert sorted(read) == sorted(id(eng.mirrors[i]) for i in typed)
    read.clear()
    eng.flush()  # nothing queued: nothing visited, nothing read
    m = eng.last_flush_metrics
    assert (m["rooms_dirty"], m["rooms_compact_looked"]) == (0, 3)
    assert len(read) == 3
    eng.flush()
    assert eng.last_flush_metrics["rooms_compact_looked"] == 0
    monkeypatch.undo()
    for i in typed:
        assert eng.text(i) == rooms.oracle[i].get_text("text").to_string()


def _saved_room(client, n):
    """One encoded state of ``n`` rows: ``n`` characters prepended in one
    transaction (no two merge), as a server writes a room for a restart."""
    d = Y.Doc(gc=False)
    d.client_id = client
    t = d.get_text("text")
    d.transact(lambda _txn: [t.insert(0, "abcdefghij"[k % 10]) for k in range(n)])
    return d


def test_a_cold_start_compacts_nothing(monkeypatch):
    """512 rooms loaded whole, then a keystroke in each: the look reads
    512 rooms that have doubled (from nothing), asks them and rebuilds
    none (``rooms_compact_skipped`` 512, nothing staged, ``last_compaction``
    the object it was); a room that is then typed in until it has doubled
    again has runs to merge and is rebuilt (skipped 0)."""
    eng = _engine(monkeypatch, "native", 512, compact_min_rows=32)
    reg = eng.obs.registry
    docs = [_saved_room(100 + k, 40 + k) for k in range(4)]
    saved = [Y.encode_state_as_update(d) for d in docs]
    for i in range(512):
        assert eng.queue_update(i, saved[i % 4])
    eng.flush()
    m = eng.last_flush_metrics
    assert (m["rooms_compact_looked"], m["rooms_compact_skipped"]) == (0, 0)
    keystroke = []
    for d in docs:
        sv = Y.encode_state_vector(d)
        d.get_text("text").insert(len(d.get_text("text")), "!")
        keystroke.append(Y.encode_state_as_update(d, sv))
    before = eng.last_compaction
    for i in range(512):
        assert eng.queue_update(i, keystroke[i % 4])
    eng.flush()  # the first keystroke's flush: the one that compacted
    m = eng.last_flush_metrics
    assert (m["rooms_compact_looked"], m["rooms_compact_skipped"]) == (512, 512)
    assert m["rows_staged_blocks"] == m["rows_staged_bytes"] == 0
    assert eng.last_compaction is before
    assert eng._rows_at_compact == [40 + i % 4 for i in range(512)]
    assert reg.get("ytpu_flush_rooms_compact_skipped_total").value == 512
    assert reg.get("ytpu_flush_rooms_compact_looked_total").value == 512
    # room 5 is typed in, a keystroke an update, until it has doubled
    d = docs[1]
    t = d.get_text("text")
    for _ in range(45):
        sv = Y.encode_state_vector(d)
        t.insert(len(t), "x")
        assert eng.queue_update(5, Y.encode_state_as_update(d, sv))
    eng.flush()
    m = eng.last_flush_metrics
    assert (m["rooms_compact_looked"], m["rooms_compact_skipped"]) == (512, 0)
    eng.flush()  # this look reads room 5 at 87 rows, twice 41 and more
    m = eng.last_flush_metrics
    assert (m["rooms_compact_looked"], m["rooms_compact_skipped"]) == (1, 0)
    assert eng.last_compaction == [
        {"doc": 5, "rows_before": 87, "rows_after": 42}
    ]
    assert reg.get("ytpu_flush_rooms_compact_skipped_total").value == 512
    assert eng.text(5) == t.to_string()
    assert eng.text(6) == docs[2].get_text("text").to_string()


@pytest.mark.parametrize("planner", ["native", "python"])
def test_a_flush_that_raises_keeps_its_rooms(monkeypatch, planner):
    """Strict mode: an update that raises in the plan phase leaves every
    room of the flush in the set, and the retry integrates them."""
    monkeypatch.setenv("YTPU_RESILIENCE_DISABLED", "1")
    eng = _engine(monkeypatch, planner, 16)
    rooms = Rooms(eng, random.Random(11))
    queued = [2, 3, 8, 13]
    for i in queued:
        rooms.type(i, 3)
    eng.queue_update(2, GARBAGE)  # the lowest slot: nothing was taken yet
    with pytest.raises(Exception):
        eng.flush()
    assert eng._dirty_docs == set(queued)
    eng.reset_doc(2)  # the operator drops the poisoned room
    rooms.let(2)
    rooms.type(3)
    eng.flush()
    assert eng.last_flush_metrics["rooms_dirty"] == 3
    assert not eng._dirty_docs
    # the host's rooms: the native core had merged the other rooms'
    # updates when the flush raised and their plans went with it, so in
    # strict mode the device's rows of those rooms lag, as they did
    # before the set
    eng.export_from_device = False
    rooms.check()


def test_work_and_chunks_come_out_in_slot_order(monkeypatch):
    """600 rooms queued in a shuffled order: ``work`` is the list the
    walk over every slot built, so the chunks of 256 hold the same rooms
    and the next flush of the same rooms meets no new ``apply_plan2``
    lane key (nothing compiles)."""
    import jax

    eng = _engine(monkeypatch, "native", 1024, compact_min_rows=1 << 30)
    rng = random.Random(3)
    rooms = Rooms(eng, rng)
    hot = rng.sample(range(1024), 600)
    seen = {"work": [], "chunks": [], "keys": []}
    flush_bulk, plan_chunk, dispatch = (
        eng._flush_bulk, eng._plan_chunk_native, eng._dispatch,
    )

    def spy_bulk(items, *a, **kw):
        seen["work"].append([i for i, _m in items])
        return flush_bulk(items, *a, **kw)

    def spy_chunk(chunk, *a):
        seen["chunks"].append([i for i, _m in chunk])
        return plan_chunk(chunk, *a)

    def spy_dispatch(kind, *a, **kw):
        if kind == "lanes":
            seen["keys"].append(a[1])
        return dispatch(kind, *a, **kw)

    monkeypatch.setattr(eng, "_flush_bulk", spy_bulk)
    monkeypatch.setattr(eng, "_plan_chunk_native", spy_chunk)
    monkeypatch.setattr(eng, "_dispatch", spy_dispatch)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_kw: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None
    )
    laps = []
    for _lap in range(3):  # the first meets empty rooms: other lanes
        rng.shuffle(hot)
        for i in hot:
            rooms.type(i, prepend=True)
        want = walk_of_every_slot(eng)
        assert want == sorted(hot)
        before = len(compiles)
        eng.flush()
        assert seen["work"].pop() == want
        assert seen["chunks"] == [want[:256], want[256:512], want[512:]]
        laps.append((list(seen["keys"]), len(compiles) - before))
        seen["chunks"].clear()
        seen["keys"].clear()
    assert laps[2][0] == laps[1][0] and len(laps[1][0]) == 3
    assert laps[2][1] == 0
    for i in hot[:32]:
        assert eng.text(i) == rooms.oracle[i].get_text("text").to_string()
