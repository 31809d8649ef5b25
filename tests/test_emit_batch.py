"""The flush's broadcast updates from ONE native call
(``ymx_encode_steps_many`` / ``native_mirror.encode_steps_many``) against
the per-room ``encode_step_update`` every room took before: the same
bytes, room by room, in the same order, and a room the native writer
refuses still gets its update the old way."""

import random
import threading

import numpy as np
import pytest

import yjs_tpu as Y
from yjs_tpu.native import load
from yjs_tpu.ops import BatchEngine
from yjs_tpu.ops import engine as engine_mod
from yjs_tpu.ops.columns import DocMirror
from yjs_tpu.ops.native_mirror import (
    NativeMirror,
    NativePlan,
    encode_diffs_many,
    encode_steps_many,
    native_plan_available,
)

pytestmark = pytest.mark.skipif(
    not native_plan_available(), reason="native plan core unavailable"
)

KINDS = ("distinct", "storm", "map", "nested", "deleting", "astral")


def session(kind: str, seed: int, n_ops: int = 60, v2: bool = False):
    """Per-operation updates of one room's editing session."""
    from yjs_tpu.coding import use_v1_encoding, use_v2_encoding

    rng = random.Random(f"{kind}:{seed}")
    n_clients = 4 if kind == "storm" else 2
    docs = []
    for c in range(n_clients):
        d = Y.Doc(gc=False)
        d.client_id = 1000 * (seed + 1) + c
        docs.append(d)
    words = ["alpha ", "be", "gamma", "δδ ", "é"]
    if kind == "astral":
        words += ["x\U0001F600y", "\U0001F680\U0001F680"]
    updates = []
    if v2:
        use_v2_encoding()
    try:
        for step in range(n_ops):
            d = docs[step % n_clients]
            sv = Y.encode_state_vector(d)
            t = d.get_text("text")
            if kind == "map":
                d.get_map("meta").set(rng.choice("abc"), rng.randint(0, 99))
                if rng.random() < 0.3:
                    d.get_array("list").insert(0, [rng.randint(0, 9), "s", None])
            elif kind == "nested" and rng.random() < 0.5:
                if rng.random() < 0.5 and len(t) > 2:
                    t.format(rng.randrange(len(t) - 1), 2, {"bold": True})
                else:
                    nested = Y.YMap()
                    d.get_map("meta").set("nested", nested)
                    nested.set("k", rng.randint(0, 9))
            elif kind == "deleting" and len(t) > 3 and rng.random() < 0.6:
                pos = rng.randrange(len(t) - 1)
                t.delete(pos, min(rng.randint(1, 4), len(t) - pos))
            else:
                t.insert(rng.randint(0, len(t)), rng.choice(words))
            updates.append(Y.encode_state_as_update(d, sv))
            # a storm's clients type from one state and meet rarely
            if rng.random() < (0.1 if kind == "storm" else 0.4):
                for a in docs:
                    for b in docs:
                        if a is not b:
                            Y.apply_update(
                                b,
                                Y.encode_state_as_update(
                                    a, Y.encode_state_vector(b)
                                ),
                            )
    finally:
        if v2:
            use_v1_encoding()
    return updates


def flushes(updates, flush_every):
    """The session cut into flushes: 0 = one whole-room load."""
    if not flush_every:
        return [updates]
    return [
        updates[j : j + flush_every]
        for j in range(0, len(updates), flush_every)
    ]


@pytest.mark.parametrize("flush_every", [0, 1, 7], ids=["load", "key", "7"])
@pytest.mark.parametrize("kind", KINDS)
def test_batch_bytes_equal_per_room(kind, flush_every):
    """Rooms of one kind, three seeds, every flush: the batch entry's
    bytes are ``encode_step_update``'s in all three delete-set modes (the
    plan's own ranges read in the core, the same ranges handed over, the
    room's whole delete set against ``encode_diff_update``)."""
    rooms = [
        (NativeMirror("text"), flushes(session(kind, s), flush_every))
        for s in range(3)
    ]
    n_flushes = max(len(f) for _m, f in rooms)
    n_updates = 0
    for j in range(n_flushes):
        work, pre_svs, want, plans = [], {}, [], []
        for i, (m, fl) in enumerate(rooms):
            if j >= len(fl):
                continue
            for u in fl[j]:
                m.ingest(u)
            pre_svs[i] = m.state_vector()
            if not flush_every:
                assert pre_svs[i] == {}
            plan = m.prepare_step()
            plans.append(plan)
            want.append(m.encode_step_update(pre_svs[i], plan))
            work.append((i, m, int(plan.counts[15])))
        got, rcs = encode_steps_many(work, pre_svs)
        assert rcs.tolist() == [0] * len(work)
        assert got == want
        n_updates += sum(u is not None for u in got)
        # explicit triples; and the derived form is a sync step 2's
        got2, rcs2 = encode_steps_many(
            [(i, m, p.applied_ds) for (i, m, _s), p in zip(work, plans)],
            pre_svs,
        )
        assert (got2, rcs2.tolist()) == (want, [0] * len(work))
        got3, rcs3 = encode_steps_many(
            [(i, m, None) for i, m, _s in work], pre_svs
        )
        assert rcs3.tolist() == [0] * len(work)
        for (i, m, _s), u in zip(work, got3):
            assert (u or b"\x00\x00") == m.encode_diff_update(pre_svs[i])
    assert n_updates >= len(rooms)


def test_no_novelty_flush_gives_none():
    """A flush that changed nothing visible (the same update again) maps
    to None, as ``encode_step_update`` does, with return code 0."""
    m = NativeMirror("text")
    ups = session("distinct", 0, 10)
    for u in ups:
        m.ingest(u)
    m.prepare_step()
    pre = m.state_vector()
    m.ingest(ups[-1])
    plan = m.prepare_step()
    assert m.encode_step_update(pre, plan) is None
    got, rcs = encode_steps_many([(0, m, int(plan.counts[15]))], {0: pre})
    assert got == [None] and rcs.tolist() == [0]


@pytest.mark.parametrize("how", ["prepare", "adopt"])
def test_stale_plan_is_refused_not_encoded(how):
    """A plan whose mirror has planned again (or adopted a snapshot)
    since is refused with -8: its delete set is gone from the core."""
    ups = session("deleting", 1, 40)
    m = NativeMirror("text")
    for u in ups[:30]:
        m.ingest(u)
    pre = m.state_vector()
    plan = m.prepare_step()
    seq = int(plan.counts[15])
    assert seq == m._plan_seq
    fresh, rcs = encode_steps_many([(0, m, seq)], {0: pre})
    assert rcs.tolist() == [0] and fresh[0] is not None
    if how == "prepare":
        for u in ups[30:]:
            m.ingest(u)
        m.prepare_step()
    else:
        src = NativeMirror("text")
        for u in ups:
            src.ingest(u)
        p2 = src.prepare_step()
        from types import SimpleNamespace

        counts = m.adopt_cached(SimpleNamespace(
            h=src._h, counts=p2.counts, pins=src._py_bufs,
            frontier_after=src.plan_frontier,
        ))
        # the clone's plan carries the clone's number, not the source's
        assert int(counts[15]) == m._plan_seq
    assert m._plan_seq != seq
    got, rcs = encode_steps_many([(0, m, seq)], {0: pre})
    assert got == [None] and rcs.tolist() == [-8]
    with pytest.raises(RuntimeError, match="stale NativePlan"):
        plan.applied_ds


def test_v2_framed_room_is_refused_with_minus_7():
    """V2-framed format/embed/type payloads are the Python writer's: -7
    for that room, the rooms beside it in the same call are encoded."""
    plain = NativeMirror("text")
    for u in session("distinct", 2, 20):
        plain.ingest(u)
    rich = NativeMirror("text")
    for u in session("nested", 2, 40, v2=True):
        rich.ingest(u, True)
    work, pre_svs, want = [], {}, []
    for i, m in enumerate((plain, rich)):
        pre_svs[i] = m.state_vector()
        plan = m.prepare_step()
        want.append(m.encode_step_update(pre_svs[i], plan))
        work.append((i, m, int(plan.counts[15])))
    got, rcs = encode_steps_many(work, pre_svs)
    assert rcs.tolist() == [0, -7]
    assert got == [want[0], None] and want[1] is not None


def test_results_outlive_the_next_call_and_other_threads():
    """The arena is the calling thread's and is overwritten by its next
    call: what a call returned are copies, and two threads encoding at
    once do not see each other's bytes."""
    def room(seed):
        m = NativeMirror("text")
        for u in session("distinct", seed, 80):
            m.ingest(u)
        plan = m.prepare_step()
        return m, int(plan.counts[15]), m.encode_step_update({}, plan)

    rooms = [room(s) for s in range(6)]
    first, _ = encode_steps_many([(0, rooms[0][0], rooms[0][1])], {})
    second, _ = encode_steps_many([(0, rooms[1][0], rooms[1][1])], {})
    assert first == [rooms[0][2]] and second == [rooms[1][2]]
    bad = []

    def worker(mine):
        for _ in range(200):
            for m, seq, want in mine:
                got, rcs = encode_steps_many([(0, m, seq)], {})
                if got != [want] or rcs.tolist() != [0]:
                    bad.append(1)

    threads = [
        threading.Thread(target=worker, args=(rooms[k::2],)) for k in range(2)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
        assert not t.is_alive()
    assert not bad


# -- the engine's emit phase --------------------------------------------------


def _count_native_calls(monkeypatch):
    lib = load()
    calls = []
    real = lib.ymx_encode_steps_many

    def counting(handles, n, *rest):
        calls.append(int(n))
        return real(handles, n, *rest)

    monkeypatch.setattr(lib, "ymx_encode_steps_many", counting)
    return calls


def _heard(eng):
    heard = []
    eng.on_update(lambda doc, u: heard.append((doc, u)))
    return heard


@pytest.mark.parametrize("n_rooms", [1, 5, 24])
def test_flush_of_n_native_rooms_is_one_native_call(monkeypatch, n_rooms):
    calls = _count_native_calls(monkeypatch)
    eng = BatchEngine(n_rooms)
    heard = _heard(eng)
    sessions = [session(KINDS[i % 3], i, 30) for i in range(n_rooms)]
    # a whole-room load, then keystrokes
    for i, ups in enumerate(sessions):
        for u in ups[:20]:
            eng.queue_update(i, u)
    eng.flush()
    m = eng.last_flush_metrics
    assert calls == [n_rooms]
    assert (m["emit_batched"], m["emit_fallback"]) == (n_rooms, 0)
    assert m["emit_bytes"] == sum(len(u) for _d, u in heard)
    assert [d for d, _u in heard] == list(range(n_rooms))
    for j in range(20, 30):
        del heard[:]
        for i, ups in enumerate(sessions):
            eng.queue_update(i, ups[j])
        eng.flush()
        m = eng.last_flush_metrics
        assert (m["emit_batched"], m["emit_fallback"]) == (n_rooms, 0)
        assert m["emit_bytes"] == sum(len(u) for _d, u in heard)
    assert calls == [n_rooms] * 11
    for i, ups in enumerate(sessions):
        ref = Y.Doc(gc=False)
        for u in ups:
            Y.apply_update(ref, u)
        assert eng.text(i) == ref.get_text("text").to_string()


def _refuse_everything(work, pre_svs):
    return [None] * len(work), np.full(len(work), -7, np.int64)


@pytest.mark.parametrize("observe", [False, True], ids=["plain", "observed"])
@pytest.mark.parametrize("flush_every", [0, 1, 5], ids=["load", "key", "5"])
def test_engine_updates_identical_to_per_room_path(
    monkeypatch, flush_every, observe
):
    """What ``on_update`` hears from the batched emit is, update for
    update and in order, what it heard when every room took
    ``encode_step_update`` (the batch entry made to refuse every room),
    V2-framed rooms and observed rooms among them."""
    n = 6
    sessions = [session(KINDS[i], i, 40) for i in range(n)]
    sessions[3] = session("nested", 9, 40, v2=True)  # refused: -7
    heard = {}
    made = {}
    for mode in ("batch", "per-room"):
        if mode == "per-room":
            monkeypatch.setattr(
                engine_mod, "encode_steps_many", _refuse_everything
            )
        eng = BatchEngine(n)
        heard[mode] = _heard(eng)
        events = []
        if observe:
            eng.observe(1, lambda d, ev: events.append((d, len(ev))))
        made[mode] = []
        real_make = NativeMirror.make_plan
        monkeypatch.setattr(
            NativeMirror, "make_plan",
            lambda self, c, log=made[mode]: log.append(1) or real_make(self, c),
        )
        fallbacks = []
        for chunk in zip(*(flushes(s, flush_every) for s in sessions)):
            for i, ups in enumerate(chunk):
                for u in ups:
                    eng.queue_update(i, u, v2=(i == 3))
            eng.flush()
            m = eng.last_flush_metrics
            assert m["emit_batched"] + m["emit_fallback"] == n
            fallbacks.append(m["emit_fallback"])
        monkeypatch.setattr(NativeMirror, "make_plan", real_make)
        if mode == "batch":
            # only the V2-framed room falls back, once its formats come
            assert set(fallbacks) <= {0, 1} and 1 in fallbacks
            assert observe == bool(events)
            # plan objects: observed rooms and the refused room, no others
            assert len(made[mode]) == sum(fallbacks) + (
                len(fallbacks) if observe else 0
            )
        else:
            assert set(fallbacks) == {n}
    assert heard["batch"] == heard["per-room"]
    docs = [d for d, _u in heard["batch"]]
    assert docs == sorted(docs) or flush_every  # one flush: plans order
    assert len(heard["batch"]) >= n


def test_docmirror_and_refused_rooms_fall_back_in_order(monkeypatch):
    """A Python-planner room and a V2-framed room in one flush with native
    rooms: both are counted as fallbacks, and every room's update still
    arrives, in ``plans`` order, equal to what an engine planning every
    room in Python hears for the DocMirror room and to the per-room
    encode for the others."""
    calls = _count_native_calls(monkeypatch)
    sessions = [session("distinct", 0, 30), session("nested", 1, 30, v2=True),
                session("deleting", 2, 30), session("map", 3, 30)]
    eng = BatchEngine(4)
    assert all(isinstance(m, NativeMirror) for m in eng.mirrors)
    eng.mirrors[3] = DocMirror(eng.root_name)
    heard = _heard(eng)
    # the per-doc plan loop is the one that plans a mixed set of mirrors
    monkeypatch.setenv("YTPU_NO_NATIVE_PLAN", "1")
    want = {}
    real = {
        NativeMirror: NativeMirror.encode_step_update,
        DocMirror: DocMirror.encode_step_update,
    }
    for i, ups in enumerate(sessions):
        for u in ups:
            eng.queue_update(i, u, v2=(i == 1))
    # a NativeMirror's fallback ends in its shadow DocMirror's encode
    owner = {id(m): i for i, m in enumerate(eng.mirrors)}
    owner.update(
        (id(m._py), i) for i, m in enumerate(eng.mirrors)
        if isinstance(m, NativeMirror)
    )
    for cls, fn in real.items():
        def spy(self, pre_sv, plan, v2=False, fn=fn):
            u = fn(self, pre_sv, plan, v2=v2)
            want[owner[id(self)]] = u
            return u

        monkeypatch.setattr(cls, "encode_step_update", spy)
    eng.flush()
    m = eng.last_flush_metrics
    assert calls == [3]
    assert (m["emit_batched"], m["emit_fallback"]) == (2, 2)
    assert sorted(want) == [1, 3]  # the only rooms the per-room path saw
    assert [d for d, _u in heard] == [0, 1, 2, 3]
    assert heard[1][1] == want[1] and heard[3][1] == want[3]
    assert m["emit_bytes"] == sum(len(u) for _d, u in heard)
    for i, ups in enumerate(sessions):
        ref, got = Y.Doc(gc=False), Y.Doc(gc=False)
        for u in ups:
            (Y.apply_update_v2 if i == 1 else Y.apply_update)(ref, u)
        Y.apply_update(got, heard[i][1])
        assert Y.decode_state_vector(Y.encode_state_vector(got)) == (
            Y.decode_state_vector(Y.encode_state_vector(ref))
        )
        for d in (got, ref):
            d.get_text("text"), d.get_map("meta"), d.get_array("list")
        assert got.get_text("text").to_delta() == ref.get_text("text").to_delta()
        assert got.get_map("meta").to_json() == ref.get_map("meta").to_json()
        assert got.get_array("list").to_json() == ref.get_array("list").to_json()


def test_stale_plan_raises_from_the_flush(monkeypatch):
    """The engine takes a refused room through ``encode_step_update``,
    whose plan then reads as stale: a flush never broadcasts bytes
    encoded from another plan's delete set."""
    eng = BatchEngine(2)
    _heard(eng)
    ups = session("deleting", 4, 30)
    for u in ups[:20]:
        eng.queue_update(0, u)
        eng.queue_update(1, u)
    real = engine_mod.encode_steps_many

    def replan_first(work, pre_svs):
        _i, m, _seq = work[1]
        for u in ups[20:]:
            m.ingest(u)
        m.prepare_step()
        return real(work, pre_svs)

    monkeypatch.setattr(engine_mod, "encode_steps_many", replan_first)
    with pytest.raises(RuntimeError, match="stale NativePlan"):
        eng.flush()


@pytest.mark.parametrize("planner", ["native", "python"])
def test_emit_counters_by_planner_and_listener(monkeypatch, planner):
    """No listener: nothing is encoded and the three counters read 0.
    The Python planner's rooms all take the per-room path."""
    calls = _count_native_calls(monkeypatch)
    if planner == "python":
        monkeypatch.setenv("YTPU_NO_NATIVE_PLAN", "1")
    eng = BatchEngine(3)
    ups = session("distinct", 5, 20)
    for u in ups[:10]:
        for i in range(3):
            eng.queue_update(i, u)
    eng.flush()
    m = eng.last_flush_metrics
    assert (m["emit_batched"], m["emit_fallback"], m["emit_bytes"]) == (0, 0, 0)
    assert calls == []
    heard = _heard(eng)
    for u in ups[10:]:
        for i in range(3):
            eng.queue_update(i, u)
    eng.flush()
    m = eng.last_flush_metrics
    if planner == "native":
        assert (m["emit_batched"], m["emit_fallback"]) == (3, 0)
        assert calls == [3]
    else:
        assert (m["emit_batched"], m["emit_fallback"]) == (0, 3)
        assert calls == []
    assert m["emit_bytes"] == sum(len(u) for _d, u in heard) > 0
    # an idle flush resets them
    eng.flush()
    m = eng.last_flush_metrics
    assert (m["emit_batched"], m["emit_fallback"], m["emit_bytes"]) == (0, 0, 0)


def test_applied_ds_is_built_on_first_read():
    m = NativeMirror("text")
    for u in session("deleting", 6, 40):
        m.ingest(u)
    plan = m.prepare_step()
    assert isinstance(plan, NativePlan) and plan._applied is None
    ads = plan.applied_ds
    assert ads and all(type(t) is tuple and len(t) == 3 for t in ads)
    assert plan.applied_ds is ads
    assert len(ads) == int(plan.counts[7])


# -- a handshake's answers (mode 2, one state vector a request) ---------------

DIFF_KINDS = ("typed", "storm", "deletes in the gap", "empty")


def _diff_room(kind: str):
    """A loaded NativeMirror and the state vectors of its sessions: one
    that reloads (None) and one that says so ({}), stale ones that hold a
    prefix, a current one, and an offline typist (a client the room has
    never heard of, and more of a known client than the room holds)."""
    m = NativeMirror("text")
    if kind == "empty":
        return m, [None, {}, {41: 7}]
    if kind == "deletes in the gap":
        d = Y.Doc(gc=False)
        d.client_id = 77
        t = d.get_text("text")
        t.insert(0, "a room that is only ever erased from now on")
        ups = [Y.encode_state_as_update(d)]
        for pos in (3, 9, 20):
            sv = Y.encode_state_vector(d)
            t.delete(pos, 4)
            ups.append(Y.encode_state_as_update(d, sv))
    else:
        ups = session("storm" if kind == "storm" else "deleting", 11, 60)
    stale = []
    for j, u in enumerate(ups):
        m.ingest(u)
        m.prepare_step()
        if j in (0, len(ups) // 2, len(ups) - 2):
            stale.append(m.state_vector())
    now = m.state_vector()
    some = next(iter(now))
    typist = dict(now)
    typist[some] += 5
    typist[999_999] = 3
    return m, [None, {}, *stale, now, dict(now), typist]


@pytest.mark.parametrize("kind", DIFF_KINDS)
def test_diffs_many_equal_encode_diff_update(kind):
    """Every answer of the batched call is ``encode_diff_update``'s of the
    same request, byte for byte, the sessions of one room side by side in
    one call and again beside another room's."""
    m, svs = _diff_room(kind)
    other, other_svs = _diff_room("typed")
    requests = [(m, sv) for sv in svs]
    mixed = requests + [(other, sv) for sv in other_svs]
    random.Random(kind).shuffle(mixed)
    for reqs in (requests, mixed):
        want = [mm.encode_diff_update(sv) for mm, sv in reqs]
        got, arena_bytes = encode_diffs_many(reqs)
        assert got == want
        assert arena_bytes == sum(map(len, want))
    answers = dict.fromkeys(
        m.encode_diff_update(sv) for sv in svs
    )
    if kind == "empty":
        # nothing to send is sent: two zero bytes, not None
        assert list(answers) == [b"\x00\x00"]
    elif kind == "deletes in the gap":
        # erasing moves no clock: every session that holds the text is
        # owed the delete set and no struct
        assert len(answers) == 2
        assert all(u[-1] != 0 for u in answers)  # a delete set in each
    else:
        assert len(answers) >= 4


def test_diffs_many_refuses_a_v2_framed_room_alone():
    plain, svs = _diff_room("typed")
    rich = NativeMirror("text")
    for u in session("nested", 2, 40, v2=True):
        rich.ingest(u, True)
    rich.prepare_step()
    assert rich.encode_diff_update(None) is None
    got, arena_bytes = encode_diffs_many(
        [(plain, svs[0]), (rich, None), (plain, svs[-1]), (rich, {})]
    )
    assert got == [
        plain.encode_diff_update(svs[0]), None,
        plain.encode_diff_update(svs[-1]), None,
    ]
    assert arena_bytes == len(got[0]) + len(got[2])
    assert encode_diffs_many([]) == ([], 0)


def _sv_bytes(sv):
    from yjs_tpu.coding import DSEncoderV1
    from yjs_tpu.updates import write_state_vector

    if not sv:
        return None
    e = DSEncoderV1()
    write_state_vector(e, sv)
    return e.to_bytes()


def _handshake_engine():
    """Six rooms: four native ones of the kinds above, one fed V2-framed
    formats (the core refuses it: -7), one served by the CPU core; and a
    tick's requests, several sessions a room, shuffled."""
    eng = BatchEngine(6)
    sessions = [
        session("deleting", 11, 60), session("storm", 11, 60),
        session("map", 3, 30), [],
        session("nested", 2, 40, v2=True), session("distinct", 4, 30),
    ]
    eng._cpu_serve(5)
    for i, ups in enumerate(sessions):
        for u in ups:
            eng.queue_update(i, u, v2=(i == 4))
    eng.flush()
    assert set(eng.fallback) == {5}
    requests = []
    for i in range(6):
        now = eng.state_vector(i)
        half = {c: n // 2 for c, n in now.items() if n // 2}
        requests += [(i, None), (i, now), (i, half), (i, {**now, 999_999: 3})]
    random.Random(6).shuffle(requests)
    return eng, requests


def test_sync_step2_batch_is_one_native_call_and_counts_fallbacks(monkeypatch):
    calls = _count_native_calls(monkeypatch)
    eng, requests = _handshake_engine()
    del calls[:]
    replies = eng.sync_step2_batch(requests)
    m = eng.last_sync_metrics
    # one call over the 20 requests of the five native rooms.  The V2-framed
    # room is refused where the answer selects a row (its reload and its
    # stale session); those two and the fallback doc's four go one by one
    assert calls == [20]
    refused = [
        sv for i, sv in requests
        if i == 4 and eng.mirrors[4].encode_diff_update(sv) is None
    ]
    assert len(refused) == 2
    assert (m["n_requests"], m["encode_batched"], m["encode_fallback"]) == (
        24, 18, 6
    )
    assert m["encode_buffer_bytes"] >= sum(
        len(u) for (i, _sv), u in zip(requests, replies) if i < 4
    )
    for (i, sv), u in zip(requests, replies):
        if i < 4:
            assert u == eng.mirrors[i].encode_diff_update(sv)
        assert u == eng.encode_state_as_update(i, _sv_bytes(sv))
    # with nothing refused and no fallback doc, nothing falls back
    native = [r for r in requests if r[0] < 4]
    assert eng.sync_step2_batch(native) == [
        u for r, u in zip(requests, replies) if r[0] < 4
    ]
    m = eng.last_sync_metrics
    assert (m["encode_batched"], m["encode_fallback"]) == (16, 0)
    assert m["encode_buffer_bytes"] == sum(
        len(u) for r, u in zip(requests, replies) if r[0] < 4
    )


@pytest.mark.parametrize("how", ["v2", "python-mirror"])
def test_other_paths_answer_as_before_and_count_as_fallbacks(monkeypatch, how):
    """``v2=True`` and Python-mirror engines never reach the batched
    call; their answers are the single-request path's."""
    if how == "python-mirror":
        monkeypatch.setenv("YTPU_NO_NATIVE_PLAN", "1")
    eng, requests = _handshake_engine()
    if how == "python-mirror":
        assert not any(isinstance(m, NativeMirror) for m in eng.mirrors)
    calls = _count_native_calls(monkeypatch)
    v2 = how == "v2"
    replies = eng.sync_step2_batch(requests, v2=v2)
    m = eng.last_sync_metrics
    assert calls == []
    assert (m["encode_batched"], m["encode_fallback"]) == (0, len(requests))
    for (i, sv), u in zip(requests, replies):
        assert u == eng.encode_state_as_update(i, _sv_bytes(sv), v2=v2)


def test_room_both_native_encoders_decline_is_answered_from_the_host_mask(
    monkeypatch,
):
    """A room fed V2-framed formats asked for V1 answers: the batched
    call and the room's own ``encode_diff_update`` both decline every
    answer that selects a row, and the mirror's host mask over its
    columns gives it (no device program is dispatched for it): a
    session at that state vector that applies the answer holds what the
    CPU core holds."""
    from yjs_tpu.obs.prof import kernel_profiler
    from yjs_tpu.ops.columns import DocMirror

    ups = session("nested", 2, 40, v2=True)
    core = Y.Doc(gc=False)
    stale = Y.Doc(gc=False)  # a session that left half way
    for j, u in enumerate(ups):
        Y.apply_update_v2(core, u)
        if j < len(ups) // 2:
            Y.apply_update_v2(stale, u)
    eng = BatchEngine(2)
    for u in ups:
        eng.queue_update(0, u, v2=True)
    eng.flush()
    masks = []
    real = DocMirror._diff_mask

    def spy(self, remote_sv):
        masks.append(dict(remote_sv))
        return real(self, remote_sv)

    monkeypatch.setattr(DocMirror, "_diff_mask", spy)
    calls = _count_native_calls(monkeypatch)
    stale_sv = Y.decode_state_vector(Y.encode_state_vector(stale))
    requests = [(0, None), (0, stale_sv), (0, eng.state_vector(0))]
    programs = kernel_profiler().snapshot()
    replies = eng.sync_step2_batch(requests)
    assert kernel_profiler().snapshot() == programs
    m = eng.last_sync_metrics
    # the reload and the stale session select rows: declined twice, then
    # masked on the host; the current session is owed the delete set alone
    assert calls == [3]
    assert masks == [{}, stale_sv]
    assert (m["encode_batched"], m["encode_fallback"]) == (1, 2)
    assert m["encode_buffer_bytes"] >= len(replies[2])
    for (_i, sv), u, peer in zip(requests, replies, (Y.Doc(gc=False), stale)):
        assert eng.mirrors[0].encode_diff_update(sv) is None
        Y.apply_update(peer, u)
        assert Y.encode_state_as_update(peer) == Y.encode_state_as_update(core)
        assert peer.get_text("text").to_delta() == (
            core.get_text("text").to_delta()
        )


def test_encode_states_batched_over_more_rooms_than_a_slice(monkeypatch):
    """A checkpoint's whole rooms go through in slices: each slice's
    answers are copied out before the next reuses the arena, and come
    back in the order asked."""
    from yjs_tpu.ops import native_mirror

    monkeypatch.setattr(native_mirror, "_DIFF_SLICE", 4)
    calls = _count_native_calls(monkeypatch)
    n = 11
    eng = BatchEngine(n)
    sessions = [session(KINDS[i % 3], 20 + i, 25) for i in range(n)]
    for i, ups in enumerate(sessions):
        for u in ups:
            eng.queue_update(i, u)
    eng.flush()
    del calls[:]
    order = list(range(n))
    random.Random(3).shuffle(order)
    states = eng.encode_states_batched(order)
    assert calls == [4, 4, 3]
    m = eng.last_sync_metrics
    assert (m["encode_batched"], m["encode_fallback"]) == (n, 0)
    assert len(set(states)) == n
    for i, u in zip(order, states):
        assert u == eng.mirrors[i].encode_diff_update(None)
        ref, got = Y.Doc(gc=False), Y.Doc(gc=False)
        for s in sessions[i]:
            Y.apply_update(ref, s)
        Y.apply_update(got, u)
        assert got.get_text("text").to_string() == (
            ref.get_text("text").to_string()
        )
        assert Y.decode_state_vector(Y.encode_state_vector(got)) == (
            Y.decode_state_vector(Y.encode_state_vector(ref))
        )
