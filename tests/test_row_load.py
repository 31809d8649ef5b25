"""A room loaded whole into an empty slot is written to the device as a
row of a block (``BatchEngine._stage_row_loads``, ``kernels.
apply_plan2_rows``; ``parallel.mesh.sharded_load_rows`` on a mesh) and
not link by link.  Held here to the element lanes, which the tests keep
as the reference: an engine whose packers are handed every plan with the
fact "the mirror held no row" taken off, so that each room rides
``apply_plan2``'s lanes as it did before, must leave the same three
device tables, cell for cell."""

import copy

import numpy as np
import pytest

import yjs_tpu as Y
from yjs_tpu.ops import BatchEngine, kernels
from yjs_tpu.ops.native_mirror import native_plan_available

NULL = -1


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
    # no compaction: the flushes under test are the only device writes
    kw.setdefault("compact_min_rows", 1 << 30)
    return BatchEngine(n, **kw)


def lanes_only(eng):
    """The reference: ``eng``'s own packers, each given copies of the
    chunk's plans that do not say the mirror came from empty, so every
    room goes through the element lanes (``pack_apply_lanes`` /
    ``_pack_chunk_py``'s bins and ``apply_plan2``) and no block is
    staged.  Nothing in the engine is switched: the lanes packer is
    called with what it was always called with."""
    native, py = eng._pack_chunk_native, eng._pack_chunk_py

    def pack_native(chunk_ok, b_loc, n_shards):
        stripped = []
        for i, m, c in chunk_ok:
            c = c.copy()
            c[14] &= 1
            stripped.append((i, m, c))
        out = native(stripped, b_loc, n_shards)
        assert out[4] == []
        return out

    def pack_py(chunk_ok, b_loc, n_shards):
        stripped = []
        for i, p in chunk_ok:
            p = copy.copy(p)
            p.from_empty = False
            stripped.append((i, p))
        out = py(stripped, b_loc, n_shards)
        assert out[4] == []
        return out

    eng._pack_chunk_native, eng._pack_chunk_py = pack_native, pack_py
    return eng


def spy_dispatch(eng):
    """Every dispatch of ``eng`` as ``(kind, shapes and dtypes | key)``."""
    seen = []
    dispatch = eng._dispatch

    def spy(kind, *a, **kw):
        if kind == "lanes":
            seen.append((kind, a[1]))
        else:
            seen.append((kind, *((x.shape, str(x.dtype)) for x in a)))
        return dispatch(kind, *a, **kw)

    eng._dispatch = spy
    return seen


def prepended(client, n, erase=()):
    """A room of exactly ``n`` rows: ``n`` characters prepended one at a
    time (no two merge), then the characters at ``erase`` deleted
    (tombstones; a single character's row never splits)."""
    d = Y.Doc(gc=False)
    d.client_id = client
    t = d.get_text("text")

    def run(_txn):
        for k in range(n):
            t.insert(0, "abcdefghij"[k % 10])
        for at in sorted(erase, reverse=True):
            t.delete(at, 1)

    d.transact(run)
    return d


def whole(d):
    return Y.encode_state_as_update(d)


def typed_more(d, at, text):
    """``d`` takes ``text`` at ``at``; the update that says so."""
    sv = Y.encode_state_vector(d)
    d.get_text("text").insert(at, text)
    return Y.encode_state_as_update(d, sv)


def tables(eng):
    return [np.asarray(t) for t in (eng._right, eng._deleted, eng._starts)]


def assert_same_tables(eng, ref):
    assert (eng._cap, eng._seg_cap) == (ref._cap, ref._seg_cap)
    for got, want in zip(tables(eng), tables(ref)):
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)  # scratch column included


def pair(monkeypatch, planner, n, mesh=False):
    eng = _engine(monkeypatch, planner, n, mesh)
    ref = lanes_only(_engine(monkeypatch, planner, n, mesh))
    return eng, ref


def feed(engines, slot, update):
    for e in engines:
        assert e.queue_update(slot, update)


def flush(engines):
    for e in engines:
        e.flush()


def loads_of(seen):
    return [d for d in seen if d[0] == "load"]


# a chunk that mixes rooms loaded whole into empty slots with rooms that
# held rows: slots 0..3 are loaded in a first flush and typed into in the
# second; the second flush also loads the case's room and three short
# ones, on every shard of the four-device mesh (16 slots, 4 a shard)
HELD = (0, 5, 10, 15)
SHORT = {2: 3, 7: 40, 9: 130}
BIG = 13


def mixed_chunk(engines, big_rows, big_erase=(), seen=None):
    docs = {i: prepended(100 + i, 20 + 3 * i, erase=(1, 4)) for i in HELD}
    for i, d in docs.items():
        feed(engines, i, whole(d))
    flush(engines)
    if seen is not None:
        seen.clear()  # what follows is the mixed flush's alone
    for i, d in docs.items():
        feed(engines, i, typed_more(d, 2, "more"))
    for i, n in SHORT.items():
        docs[i] = prepended(100 + i, n, erase=(0,))
        feed(engines, i, whole(docs[i]))
    docs[BIG] = prepended(100 + BIG, big_rows, erase=big_erase)
    feed(engines, BIG, whole(docs[BIG]))
    flush(engines)
    return docs


def block_width(rows):
    w = 64
    while w < rows:
        w *= 2
    return w


@pytest.mark.parametrize("mesh", [False, True], ids=["one_device", "cpu_mesh"])
@pytest.mark.parametrize("planner", ["native", "python"])
@pytest.mark.parametrize(
    "rows", [2047, 2048, 2049], ids=["rows_2047", "rows_2048", "rows_2049"]
)
def test_mixed_chunk_leaves_the_lanes_tables(monkeypatch, rows, planner, mesh):
    eng, ref = pair(monkeypatch, planner, 16, mesh)
    seen = spy_dispatch(eng)
    docs = mixed_chunk((eng, ref), rows, range(0, rows, 7), seen)
    assert eng.mirrors[BIG].n_rows == rows
    assert_same_tables(eng, ref)
    m = eng.last_flush_metrics
    assert m["rooms_row_loaded"] == 4 and m["n_docs_flushed"] == 8
    # one lane dispatch for the four rooms that held rows, then the
    # blocks, the case's room in a class of its own width
    assert [d[0] for d in seen] == ["lanes"] + ["load"] * (len(seen) - 1)
    widths = [d[2][0][1] for d in loads_of(seen)]
    assert widths == sorted(widths) and widths[-1] == block_width(rows)
    assert all(d[2][1] == "int16" for d in loads_of(seen))
    assert m["row_block_bytes"] == sum(
        np.prod(shape) * np.dtype(dt).itemsize
        for d in loads_of(seen) for shape, dt in d[2:5]
    )
    assert m["n_sched_entries"] == ref.last_flush_metrics["n_sched_entries"]
    right, deleted, _starts = tables(eng)
    assert (right[BIG, rows:] == NULL).all() and not deleted[BIG, rows:].any()
    assert deleted[BIG, :rows].sum() == len(range(0, rows, 7))
    for i in (*HELD, *SHORT, BIG):
        assert eng.text(i) == docs[i].get_text("text").to_string()


@pytest.mark.parametrize(
    "rows, dtype", [(16384, "int16"), (16385, "int32")],
    ids=["int16_widest_block", "int32_block"],
)
def test_a_block_no_wider_than_32767_travels_int16(monkeypatch, rows, dtype):
    """Every link and head of a block is a row of its own room, so a
    block 16384 wide holds nothing above 16383; one row more makes the
    block 32768 wide, which int16 does not hold."""
    eng, ref = pair(monkeypatch, "native", 4)
    seen = spy_dispatch(eng)
    d = prepended(9, rows, erase=(0, rows - 1))
    feed((eng, ref), 1, whole(d))
    flush((eng, ref))
    assert_same_tables(eng, ref)
    (_kind, _idx, right, deleted, starts, _sums), = loads_of(seen)
    assert right == ((1, block_width(rows)), dtype)
    assert deleted == ((1, block_width(rows)), "bool")
    assert starts[1] == dtype
    # the list's head is the room's last row, the widest value a block holds
    assert tables(eng)[2][1].max() == rows - 1
    assert eng.text(1) == d.get_text("text").to_string()


@pytest.mark.parametrize("mesh", [False, True], ids=["one_device", "cpu_mesh"])
def test_a_100000_row_room_widens_no_block_but_its_own(monkeypatch, mesh):
    """Width classes in one chunk: the short rooms' blocks stay as wide
    as they are (3 and 40 rows in one 64 wide, 130 rows 256 wide), the
    long room's is its own and int32."""
    eng, ref = pair(monkeypatch, "native", 16, mesh)
    seen = spy_dispatch(eng)
    docs = mixed_chunk((eng, ref), 100_000, (5, 99_999), seen)
    assert eng.mirrors[BIG].n_rows == 100_000 and eng._cap == 131072
    assert_same_tables(eng, ref)
    assert [d[0] for d in seen] == ["lanes", "load", "load", "load"]
    blocks = [(d[2][0][1], d[2][1]) for d in loads_of(seen)]
    assert blocks == [(64, "int16"), (256, "int16"), (131072, "int32")]
    for i in (*HELD, *SHORT):
        assert eng.text(i) == docs[i].get_text("text").to_string()
    assert eng.state_vector(BIG) == {100 + BIG: 100_000}


@pytest.mark.parametrize("planner", ["native", "python"])
@pytest.mark.parametrize(
    "erase", ["none", "all"], ids=["no_tombstone", "all_tombstones"]
)
def test_tombstones_of_a_loaded_room(monkeypatch, erase, planner):
    eng, ref = pair(monkeypatch, planner, 4)
    n = 300
    d = prepended(3, n, erase=range(n) if erase == "all" else ())
    feed((eng, ref), 2, whole(d))
    flush((eng, ref))
    assert eng.last_flush_metrics["rooms_row_loaded"] == 1
    assert_same_tables(eng, ref)
    deleted = tables(eng)[1]
    assert deleted[2, :n].all() if erase == "all" else not deleted.any()
    assert not deleted[2, n:].any()
    assert eng.text(2) == d.get_text("text").to_string()


@pytest.mark.parametrize("mesh", [False, True], ids=["one_device", "cpu_mesh"])
@pytest.mark.parametrize("planner", ["native", "python"])
def test_a_slot_let_again_is_null_past_its_new_room(monkeypatch, planner, mesh):
    """The invariant the ``NULL`` behind a room's rows rests on: a
    released slot is at fill in every cell (``blank_rows``), so a short
    room loaded where a long one lived leaves nothing of it."""
    eng, ref = pair(monkeypatch, planner, 8, mesh)
    long_, short = prepended(4, 700, erase=range(0, 700, 3)), prepended(5, 40)
    for slot in (1, 6):
        feed((eng, ref), slot, whole(long_))
    flush((eng, ref))
    assert_same_tables(eng, ref)
    for e in (eng, ref):
        e.reset_doc(6)
    right, deleted, starts = tables(eng)
    assert (right[6] == NULL).all() and (starts[6] == NULL).all()
    assert not deleted[6].any()
    feed((eng, ref), 6, whole(short))
    flush((eng, ref))
    assert eng.last_flush_metrics["rooms_row_loaded"] == 1
    assert_same_tables(eng, ref)
    right, deleted, _starts = tables(eng)
    assert (right[6, 40:] == NULL).all() and not deleted[6].any()
    assert eng.text(6) == short.get_text("text").to_string()
    assert eng.text(1) == long_.get_text("text").to_string()


@pytest.mark.parametrize("planner", ["native", "python"])
def test_a_room_that_had_rows_keeps_the_lanes(monkeypatch, planner):
    """A plan may rewrite every link of a room that had rows (dense
    links, the mirror not from empty): it takes the lanes, because the
    room's older tombstones are on the device and in no plan."""
    eng, ref = pair(monkeypatch, planner, 4)
    seen = spy_dispatch(eng)
    d = Y.Doc(gc=False)
    d.client_id = 11
    sent = []
    d.on("update", lambda u, _origin, _doc: sent.append(u))
    t = d.get_text("text")
    t.insert(0, "a")
    t.delete(0, 1)  # the older tombstone: row 0
    t.insert(0, "b")  # row 1, and row 0's link: every link of the room
    for u in sent:
        feed((eng, ref), 3, u)
        flush((eng, ref))
    assert [d[0] for d in seen] == ["load", "lanes", "lanes"]
    m = eng.last_flush_metrics
    assert m["rooms_row_loaded"] == 0 and m["n_sched_entries"] == 2
    assert eng.mirrors[3].n_rows == 2
    assert_same_tables(eng, ref)
    assert tables(eng)[1][3, :2].tolist() == [True, False]
    assert eng.text(3) == "b"


@pytest.mark.parametrize("mesh", [False, True], ids=["one_device", "cpu_mesh"])
def test_flushes_without_a_loaded_room_meet_the_lane_programs(monkeypatch, mesh):
    """A flush with no room loaded into an empty slot stages no block
    and dispatches ``apply_plan2`` at its lane key, one program a
    distinct key as before; a second load of the same group after a
    release meets no new program."""
    import jax

    eng = _engine(monkeypatch, "native", 16, mesh)
    seen = spy_dispatch(eng)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **_kw: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None
    )
    group = {i: prepended(200 + i, 30 + 50 * (i % 4)) for i in range(0, 16, 2)}

    def load():
        for i, d in group.items():
            eng.queue_update(i, whole(d))
        eng.flush()
        assert eng.last_flush_metrics["rooms_row_loaded"] == len(group)

    load()
    first_load = loads_of(seen)
    assert first_load and [d[0] for d in seen] == ["load"] * len(first_load)
    # served flushes: every room held rows, so lanes and nothing else
    seen.clear()
    lane_programs = (
        (lambda: len(eng._sharded_apply)) if mesh
        else kernels.apply_plan2.__wrapped__._cache_size
    )
    programs_before = lane_programs()
    for rnd in range(3):
        for i, d in group.items():
            eng.queue_update(i, typed_more(d, 1, "xy"[: 1 + rnd % 2]))
        eng.flush()
        m = eng.last_flush_metrics
        assert m["rooms_row_loaded"] == 0 and m["row_block_bytes"] == 0
    assert [d[0] for d in seen] == ["lanes"] * 3
    keys = {d[1] for d in seen}
    assert lane_programs() - programs_before == len(keys)
    # the group is released and loaded again: the same blocks, no program
    for i in group:
        eng.reset_doc(i)
    group = {i: prepended(200 + i, 30 + 50 * (i % 4)) for i in group}
    seen.clear()
    before = len(compiles)
    load()
    assert loads_of(seen) == first_load
    assert len(compiles) - before == 0
    for i, d in group.items():
        assert eng.text(i) == d.get_text("text").to_string()


def test_counts_of_a_cold_load_and_of_a_keystroke(monkeypatch):
    """``rooms_row_loaded`` / ``row_block_bytes`` are in the schema, in
    every flush's record and in the registry: a cold load reads every
    room, a keystroke's flush and an empty one 0."""
    from yjs_tpu.obs import FLUSH_METRICS_SCHEMA

    assert {"rooms_row_loaded", "row_block_bytes"} <= set(FLUSH_METRICS_SCHEMA)
    n = 48
    eng = _engine(monkeypatch, "native", 64)
    docs = [prepended(300 + i, 10 + i) for i in range(n)]
    for i, d in enumerate(docs):
        eng.queue_update(i, whole(d))
    eng.flush()
    m = eng.last_flush_metrics
    assert (m["rooms_row_loaded"], m["n_docs_flushed"]) == (n, n)
    # one class: 48 rooms of 10..57 rows in a block 64 wide, links and
    # heads int16, tombstones a byte
    assert m["row_block_bytes"] == n * (64 * 2 + 64 + 8 * 2)
    assert m["n_sched_entries"] == sum(10 + i for i in range(n))
    # what went up, over the cells staged for it: 48 heads and the links
    links = m["n_sched_entries"]
    assert m["schedule_occupancy"] == (links + n) / (n * (64 + 64 + 8))
    eng.queue_update(5, typed_more(docs[5], 0, "k"))
    eng.flush()
    m = eng.last_flush_metrics
    assert (m["rooms_row_loaded"], m["row_block_bytes"]) == (0, 0)
    assert m["n_docs_flushed"] == 1 and m["schedule_occupancy"] > 0
    eng.flush()
    m = eng.last_flush_metrics
    assert (m["rooms_row_loaded"], m["row_block_bytes"]) == (0, 0)
    reg = eng.obs.registry
    assert reg.get("ytpu_flush_rooms_row_loaded_total").value == n
    assert "ytpu_flush_rooms_row_loaded_total" in eng.metrics_text()


def test_a_handful_of_short_rooms_share_the_served_block(monkeypatch):
    """One device, eight rooms or fewer, none wider than 4096: the block
    a served process's compactions stage (``_block_shapes``), whatever
    the rooms' lengths, so a room bound while others type meets one
    program."""
    eng, ref = pair(monkeypatch, "native", 8)
    for e in (eng, ref):  # tables wide enough for the served width
        e.queue_update(7, whole(prepended(1, 5000)))
        e.flush()
    seen = spy_dispatch(eng)
    shapes = []
    for slot, n in ((0, 3), (1, 900), (2, 4000)):
        feed((eng, ref), slot, whole(prepended(20 + slot, n, erase=(1,))))
        flush((eng, ref))
        shapes.append(loads_of(seen)[-1][1:])
    assert_same_tables(eng, ref)
    assert shapes[0] == shapes[1] == shapes[2]
    assert shapes[0][1] == ((8, 4096), "int16")
