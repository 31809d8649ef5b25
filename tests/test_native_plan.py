"""Differential tests: NativeMirror (C++ plan core) vs DocMirror (Python
oracle).  The two implement the same flush pipeline (reference
encoding.js:225-321 recast per SURVEY.md §7); plans and columns must agree
step for step on arbitrary traffic."""

import random

import pytest

import yjs_tpu as Y
from yjs_tpu.ops.columns import DocMirror, UnsupportedUpdate
from yjs_tpu.ops.native_mirror import NativeMirror, native_plan_available

pytestmark = pytest.mark.skipif(
    not native_plan_available(), reason="native plan core unavailable"
)

COLS = (
    "row_slot", "row_clock", "row_len", "row_origin_slot",
    "row_origin_clock", "row_right_slot", "row_right_clock", "row_is_gc",
    "row_countable", "row_content_ref", "row_seg", "client_of_slot",
    "state", "seg_info", "list_next", "head_of_seg",
)


def assert_step_equal(pm, nm, pp, np_, ctx=""):
    assert pm.n_rows == nm.n_rows, ctx
    assert pp.splits == list(map(tuple, np_.splits.tolist())), ctx
    assert pp.sched == list(map(tuple, np_.sched.tolist())), ctx
    assert sorted(pp.delete_rows) == sorted(np_.delete_rows.tolist()), ctx
    assert sorted(pp.applied_ds) == sorted(np_.applied_ds), ctx
    # bulk-apply form: final link/head values must agree exactly
    assert pp.link_rows == np_.link_rows.tolist(), ctx
    assert pp.link_vals == np_.link_vals.tolist(), ctx
    assert pp.head_segs == np_.head_segs.tolist(), ctx
    assert pp.head_vals == np_.head_vals.tolist(), ctx


def assert_state_equal(pm, nm, ctx="", encode=True):
    for attr in COLS:
        assert list(getattr(pm, attr)) == list(getattr(nm, attr)), (
            f"{attr} differs {ctx}"
        )
    assert pm.state_vector() == nm.state_vector(), ctx
    assert pm.has_pending() == nm.has_pending(), ctx
    assert pm.pending_depth() == nm.pending_depth(), ctx
    assert pm.map_chain == {
        k: list(v) for k, v in nm.map_chain.items()
    }, ctx
    assert pm._lww_deleted == nm._lww_deleted, ctx
    assert pm._host_deleted_rows == nm._host_deleted_rows, ctx
    if encode:
        assert pm.encode_state_vector() == nm.encode_state_vector(), ctx
        # state equivalence of the wire encodes (bytes may differ when the
        # Python mirror spills realized content; decoded state must not)
        a, b = Y.Doc(gc=False), Y.Doc(gc=False)
        Y.apply_update(a, pm.encode_state_as_update())
        Y.apply_update(b, nm.encode_state_as_update())
        assert Y.encode_state_as_update(a) is not None
        assert a.get_text("text").to_string() == b.get_text("text").to_string(), ctx
        assert Y.decode_state_vector(
            Y.encode_state_vector(a)
        ) == Y.decode_state_vector(Y.encode_state_vector(b)), ctx


def host_tables(m):
    """What the device tables of ``m``'s doc must hold, from the host
    mirror alone: right links, deleted bits, segment heads."""
    import numpy as np

    n = m.n_rows
    deleted = np.zeros(n, bool)
    deleted[sorted(m._host_deleted_rows)] = True
    return np.asarray(m.list_next)[:n], deleted, np.asarray(m.head_of_seg)


def run_differential(updates, v2=False, flush_every=1):
    pm, nm = DocMirror("text"), NativeMirror("text")
    for j, u in enumerate(updates):
        pm.ingest(u, v2)
        nm.ingest(u, v2)
        if (j + 1) % flush_every == 0 or j == len(updates) - 1:
            pp = pm.prepare_step()
            np_ = nm.prepare_step()
            assert_step_equal(pm, nm, pp, np_, ctx=f"flush after update {j}")
    assert_state_equal(pm, nm, ctx="final")
    return pm, nm


def two_client_session(rng, n_rounds, rich=False, astral=False):
    """Concurrent editing session; returns the per-round deltas of both
    clients (interleaved) plus the final docs."""
    a = Y.Doc(gc=False); a.client_id = 100
    b = Y.Doc(gc=False); b.client_id = 200
    updates = []
    words = ["alpha ", "beta ", "gamma", "δδ ", "é "]
    if astral:
        words += ["x\U0001F600y", "\U0001F680\U0001F680"]
    for _ in range(n_rounds):
        for d in (a, b):
            sv = Y.encode_state_vector(d)
            t = d.get_text("text")
            m = d.get_map("meta")
            arr = d.get_array("list")
            op = rng.random()
            if op < 0.45 or len(t) == 0:
                t.insert(rng.randint(0, len(t)), rng.choice(words))
            elif op < 0.65:
                pos = rng.randrange(len(t))
                t.delete(pos, min(rng.randint(1, 5), len(t) - pos))
            elif op < 0.75:
                m.set(rng.choice("abc"), rng.randint(0, 99))
            elif op < 0.85:
                arr.insert(
                    rng.randint(0, len(arr)),
                    [rng.randint(0, 9), "s", None, True],
                )
            elif rich:
                if rng.random() < 0.5 and len(t) > 2:
                    pos = rng.randrange(len(t) - 1)
                    t.format(pos, 2, {"bold": True})
                else:
                    nested = Y.YMap()
                    m.set("nested", nested)
                    nested.set("k", rng.randint(0, 9))
            elif len(t) > 0:
                pos = rng.randrange(len(t))
                t.delete(pos, min(1, len(t) - pos))
            updates.append(Y.encode_state_as_update(d, sv))
        if rng.random() < 0.4:  # cross-sync so edits become concurrent
            ua = Y.encode_state_as_update(a, Y.encode_state_vector(b))
            ub = Y.encode_state_as_update(b, Y.encode_state_vector(a))
            Y.apply_update(b, ua)
            Y.apply_update(a, ub)
    ua = Y.encode_state_as_update(a, Y.encode_state_vector(b))
    ub = Y.encode_state_as_update(b, Y.encode_state_vector(a))
    Y.apply_update(b, ua)
    Y.apply_update(a, ub)
    updates += [ua, ub]
    return updates, a, b


def test_plain_text_session(rng):
    updates, a, _ = two_client_session(rng, 60)
    pm, nm = run_differential(updates, flush_every=3)
    # converged content matches the CPU doc
    assert pm.state_vector() == {
        c: v for c, v in Y.get_state_vector(a.store).items() if v > 0
    }


def test_rich_session_maps_nested_formats(rng):
    updates, _, _ = two_client_session(rng, 60, rich=True)
    run_differential(updates, flush_every=2)


def test_astral_surrogate_splits(rng):
    updates, _, _ = two_client_session(rng, 40, astral=True)
    run_differential(updates, flush_every=1)


def test_random_delivery_order_pending(rng):
    updates, _, _ = two_client_session(rng, 50)
    shuffled = list(updates)
    rng.shuffle(shuffled)
    run_differential(shuffled, flush_every=4)


def test_v2_wire(rng):
    from yjs_tpu.coding import use_v1_encoding, use_v2_encoding

    use_v2_encoding()
    try:
        updates, _, _ = two_client_session(rng, 40, rich=True)
    finally:
        use_v1_encoding()
    run_differential(updates, v2=True, flush_every=2)


def test_gc_tombstones_in_updates(rng):
    # a doc WITH gc produces GC structs in its full-state updates
    d = Y.Doc(gc=True)
    d.client_id = 77
    t = d.get_text("text")
    t.insert(0, "hello world, this will be partially gc'd")
    t.delete(3, 10)
    t.insert(5, "more")
    u = Y.encode_state_as_update(d)
    run_differential([u])


def test_subdocument_raises_unsupported():
    d = Y.Doc(gc=False)
    d.client_id = 5
    sub = Y.Doc()
    d.get_map("m").set("sub", sub)
    u = Y.encode_state_as_update(d)
    nm = NativeMirror("text")
    nm.ingest(u)
    with pytest.raises(UnsupportedUpdate):
        nm.prepare_step()


def test_malformed_raises_like_python():
    nm = NativeMirror("text")
    nm.ingest(b"\x9f\x83garbage!!\x00\xff")
    with pytest.raises(Exception) as native_err:
        nm.prepare_step()
    pm = DocMirror("text")
    pm.ingest(b"\x9f\x83garbage!!\x00\xff")
    with pytest.raises(Exception) as py_err:
        pm.prepare_step()
    assert type(native_err.value) is type(py_err.value)
    assert not isinstance(native_err.value, UnsupportedUpdate)


def test_compaction_parity(rng):
    """Full engine-level compaction: run the same traffic through two
    engines (one per mirror backend) and compare exports after compaction
    triggers."""
    import os

    from yjs_tpu.ops import BatchEngine

    updates, a, _ = two_client_session(rng, 80)
    texts = {}
    for backend in ("native", "python"):
        if backend == "python":
            os.environ["YTPU_NO_NATIVE_PLAN"] = "1"
        try:
            eng = BatchEngine(1, compact_min_rows=8, gc=True)
            for j, u in enumerate(updates):
                eng.queue_update(0, u)
                if j % 5 == 4:
                    eng.flush()
            eng.flush()
            texts[backend] = (
                eng.text(0),
                eng.state_vector(0),
                eng.to_json(0, "list"),
                eng.map_json(0, "meta"),
            )
        finally:
            os.environ.pop("YTPU_NO_NATIVE_PLAN", None)
    assert texts["native"] == texts["python"]
    assert texts["native"][0] == a.get_text("text").to_string()


@pytest.mark.parametrize("flush_every", [1, 7])
@pytest.mark.parametrize(
    "session",
    ["plain", "rich", "astral", "prepend_storm", "interleaved", "storm",
     "b4_head"],
)
def test_device_tables_equal_across_planners(
    rng, monkeypatch, session, flush_every
):
    """The one device write path under both planners: the resident tables
    (``_right``, ``_deleted``, ``_starts``) the native and the Python
    planner leave are equal, equal the planner's own host mirror, and the
    text, map and list read back equal the CPU core's (``core.py``).  The
    last four sessions are the segment pass's shapes (chained runs,
    conflict storms: ``test_segment_planner.corpus``), the traffic both
    planners' fast sets are built for."""
    import numpy as np

    from yjs_tpu.ops import BatchEngine

    if session in ("plain", "rich", "astral"):
        updates, a, _ = two_client_session(
            rng, 50, rich=session == "rich", astral=session == "astral"
        )
    else:
        from test_segment_planner import core_doc, corpus

        updates = corpus(session, seed=71)
        a = core_doc(updates)
    states = {}
    for planner in ("native", "python"):
        if planner == "python":
            monkeypatch.setenv("YTPU_NO_NATIVE_PLAN", "1")
        eng = BatchEngine(2)
        for j, u in enumerate(updates):
            eng.queue_update(0, u)
            eng.queue_update(1, u)
            if (j + 1) % flush_every == 0:
                eng.flush()
        eng.flush()
        m = eng.mirrors[0]
        assert isinstance(m, NativeMirror) == (planner == "native")
        n, n_segs = m.n_rows, m.n_segs
        right = np.asarray(eng._right)[:, :n]
        deleted = np.asarray(eng._deleted)[:, :n]
        starts = np.asarray(eng._starts)[:, :n_segs]
        # both docs got the same updates: each equals the host mirror
        for table, host in zip((right, deleted, starts), host_tables(m)):
            assert (table == host[None]).all()
        states[planner] = (
            right.tolist(), deleted.tolist(), starts.tolist(),
            eng.text(0), eng.map_json(0, "meta"), eng.to_json(0, "list"),
        )
    assert states["native"] == states["python"]
    assert states["native"][3:] == (
        a.get_text("text").to_string(),
        a.get_map("meta").to_json(),
        a.get_array("list").to_json(),
    )


def test_host_links_match_device(rng):
    """The planner's host list state IS the device state after a flush."""
    import numpy as np

    from yjs_tpu.ops import BatchEngine

    updates, _, _ = two_client_session(rng, 40)
    eng = BatchEngine(1)
    for j, u in enumerate(updates):
        eng.queue_update(0, u)
        if j % 5 == 4:
            eng.flush()
    eng.flush()
    m = eng.mirrors[0]
    n = m.n_rows
    dev_right = np.asarray(eng._right)[0, :n]
    host_next = np.asarray(m.list_next if hasattr(m, "list_next")
                           else m._py.list_next)
    # device rows never touched by any list stay NULL on both sides
    assert (dev_right == host_next[:n]).all()
    dev_starts = np.asarray(eng._starts)[0, : m.n_segs]
    host_heads = np.asarray(m.head_of_seg if hasattr(m, "head_of_seg")
                            else m._py.head_of_seg)
    assert (dev_starts == host_heads).all()


def test_deleted_run_split_stays_deleted():
    """Splitting an already-deleted run in a LATER flush must ship the new
    fragment's deleted bit (r3 review finding: the device does no split
    surgery of its own — without the host-emitted delete lane the
    fragment's text resurrected), under either planner."""
    import os

    from yjs_tpu.ops import BatchEngine

    a = Y.Doc(gc=False)
    a.client_id = 1
    a.get_text("text").insert(0, "hello")
    u1 = Y.encode_state_as_update(a)
    sv1 = Y.encode_state_vector(a)
    # B diverges BEFORE the delete: its insert's origin is mid-run
    b = Y.Doc(gc=False)
    b.client_id = 2
    Y.apply_update(b, u1)
    a.get_text("text").delete(0, 5)
    u2 = Y.encode_state_as_update(a, sv1)
    b.get_text("text").insert(1, "X")
    u3 = Y.encode_state_as_update(b, sv1)
    Y.apply_update(a, u3)
    expect = a.get_text("text").to_string()
    assert expect == "X"
    for planner in ("native", "python"):
        if planner == "python":
            os.environ["YTPU_NO_NATIVE_PLAN"] = "1"
        try:
            eng = BatchEngine(1)
            for u in (u1, u2, u3):
                eng.queue_update(0, u)
                eng.flush()
            assert eng.text(0) == expect, f"{planner}: {eng.text(0)!r}"
        finally:
            os.environ.pop("YTPU_NO_NATIVE_PLAN", None)


def test_native_v2_encode_byte_parity(rng):
    """Native V2 wire encode (plancore ymx_encode_diff_v2) is byte-identical
    to the pure-Python UpdateEncoderV2 writer on fuzzed traffic, including
    diffs against arbitrary state vectors (reference UpdateEncoder.js:
    264-408)."""
    from yjs_tpu.coding import use_v1_encoding, use_v2_encoding

    for wire_v2 in (False, True):
        if wire_v2:
            use_v2_encoding()
        try:
            updates, a, _ = two_client_session(rng, 50, rich=True, astral=True)
        finally:
            use_v1_encoding()
        pm, nm = DocMirror("text"), NativeMirror("text")
        for u in updates:
            pm.ingest(u, wire_v2)
            nm.ingest(u, wire_v2)
        pm.prepare_step()
        nm.prepare_step()
        svs = [None, {a.client_id: 7},
               Y.decode_state_vector(Y.encode_state_vector(a))]
        for sv in svs:
            pb = pm.encode_state_as_update(sv, v2=True)
            nb = nm.encode_state_as_update(sv, v2=True)
            assert pb == nb, (
                f"v2 encode differs (src_v2={wire_v2}, sv={sv}): "
                f"{len(pb)} vs {len(nb)}"
            )
            # and the bytes round-trip into an equivalent doc
            d = Y.Doc(gc=False)
            Y.apply_update_v2(d, nb)
            if sv is None:
                assert (
                    d.get_text("text").to_string()
                    == a.get_text("text").to_string()
                )


def test_host_export_matches_device(rng):
    """The default (host list walk) export equals the device-rank export
    on fuzzed traffic — the per-doc device dispatch in exports is gone
    from the product path but stays the verification path."""
    from yjs_tpu.ops import BatchEngine

    updates, a, _ = two_client_session(rng, 50, rich=True)
    eng = BatchEngine(1)
    for j, u in enumerate(updates):
        eng.queue_update(0, u)
        if j % 6 == 5:
            eng.flush()
    eng.flush()
    eng.export_from_device = False
    host = (eng.rows_in_order(0), eng.text(0), eng.to_json(0, "list"),
            eng.map_json(0, "meta"), eng.to_delta(0))
    eng.export_from_device = True
    dev = (eng.rows_in_order(0), eng.text(0), eng.to_json(0, "list"),
           eng.map_json(0, "meta"), eng.to_delta(0))
    assert host == dev
    assert host[1] == a.get_text("text").to_string()


def test_broadcast_kernels_agree(rng):
    """The broadcast bulk apply (apply_plan_shared: ONE doc's final links
    fanned out to every doc of the batch) leaves the tables that
    apply_plan2 leaves when each doc gets the same lanes of its own, and
    both equal the planner's host mirror (``list_next``,
    ``head_of_seg``) — the B4-replay shape held to the production
    kernel."""
    import jax.numpy as jnp
    import numpy as np

    from yjs_tpu.ops import kernels
    from yjs_tpu.ops.columns import NULL, DocMirror

    updates, a, _ = two_client_session(rng, 40)
    mirror = DocMirror("text")
    for u in updates:
        mirror.ingest(u)
    plan = mirror.prepare_step()
    n = mirror.n_rows
    n_docs = 4
    cap = max(64, n)
    seg_cap = max(8, mirror.n_segs)

    def fresh():
        return (
            jnp.full((n_docs, cap + 1), NULL, jnp.int32),
            jnp.zeros((n_docs, cap + 1), bool),
            jnp.full((n_docs, seg_cap + 1), NULL, jnp.int32),
        )

    def pad_lanes(idx, vals, minimum, oob):
        k = len(idx)
        padded = max(minimum, 1 << max(0, (k - 1).bit_length()))
        i = np.full(padded, oob, np.int32)
        i[:k] = np.asarray(idx, np.int32)
        if vals is None:
            return i
        v = np.full(padded, NULL, np.int32)
        v[:k] = np.asarray(vals, np.int32)
        return i, v

    rows_p, vals_p = pad_lanes(plan.link_rows, plan.link_vals, 64, cap + 1)
    segs_p, hvals_p = pad_lanes(plan.head_segs, plan.head_vals, 8, seg_cap + 1)
    dels_p = pad_lanes(plan.delete_rows, None, 64, cap + 1)
    lanes = jnp.asarray(np.concatenate([rows_p, vals_p, segs_p, hvals_p, dels_p]))
    out_shared = kernels.apply_plan_shared(
        fresh(), lanes, len(rows_p), len(segs_p), len(dels_p)
    )

    # the same lanes once per doc, in apply_plan2's layout: the per-doc
    # counts header, then every doc's real (row, value) lanes back to back
    def per_doc(idx, vals, minimum, oob):
        return pad_lanes(
            list(idx) * n_docs,
            None if vals is None else list(vals) * n_docs,
            minimum, oob,
        )

    sp_r, sp_v = per_doc(plan.link_rows, plan.link_vals, 64, cap + 1)
    h_s, h_v = per_doc(plan.head_segs, plan.head_vals, 8, seg_cap + 1)
    d_r = per_doc(plan.delete_rows, None, 64, cap + 1)
    counts = np.concatenate([
        np.zeros(n_docs, np.int32),  # no dense sections
        np.full(n_docs, len(plan.link_rows), np.int32),
        np.full(n_docs, len(plan.head_segs), np.int32),
        np.full(n_docs, len(plan.delete_rows), np.int32),
    ])
    lanes2 = jnp.asarray(np.concatenate([counts, sp_r, sp_v, h_s, h_v, d_r]))
    out_apply = kernels.apply_plan2(
        fresh(), lanes2, 0, len(sp_r), len(h_s), len(d_r)
    )
    for name, x, y, h in zip(
        ("right", "deleted", "starts"), out_shared, out_apply,
        host_tables(mirror),
    ):
        xa, ya = np.asarray(x), np.asarray(y)
        xa, ya = xa[:, : len(h)], ya[:, : len(h)]
        assert (xa == ya).all(), name
        assert (xa == h[None]).all(), name


def test_pool_width_engine_state_identical(monkeypatch):
    """Plans must be bit-identical at any worker-pool width: same updates
    flushed under YTPU_PLAN_THREADS=1 and =4 produce identical engine
    text, state vectors, and link/deleted exports (oversubscription on a
    1-core host exercises the pool code path either way)."""
    import random

    import numpy as np

    import yjs_tpu as Y
    from yjs_tpu.ops import BatchEngine

    def mk(seed):
        gen = random.Random(seed)
        a = Y.Doc(gc=False)
        a.client_id = 900 + seed
        b = Y.Doc(gc=False)
        b.client_id = 950 + seed
        for _ in range(120):
            d = a if gen.random() < 0.5 else b
            t = d.get_text("text")
            ln = len(t.to_string())
            if gen.random() < 0.7 or ln == 0:
                t.insert(gen.randint(0, ln), gen.choice(["ab", "c ", "🙂"]))
            else:
                pos = gen.randrange(ln)
                t.delete(pos, min(gen.randint(1, 3), ln - pos))
            if gen.random() < 0.2:
                ua = Y.encode_state_as_update(a, Y.encode_state_vector(b))
                ub = Y.encode_state_as_update(b, Y.encode_state_vector(a))
                Y.apply_update(b, ua)
                Y.apply_update(a, ub)
        u = Y.encode_state_as_update(a, Y.encode_state_vector(b))
        Y.apply_update(b, u)
        return Y.encode_state_as_update(a)

    updates = [mk(s) for s in range(12)]

    def run(width):
        monkeypatch.setenv("YTPU_PLAN_THREADS", width)
        eng = BatchEngine(len(updates))
        for i, u in enumerate(updates):
            eng.queue_update(i, u)
        eng.flush()
        out = []
        for i in range(len(updates)):
            out.append((eng.text(i), tuple(sorted(eng.state_vector(i).items()))))
        links = np.asarray(eng._right)
        dels = np.asarray(eng._deleted)
        return out, links, dels

    out1, l1, d1 = run("1")
    out4, l4, d4 = run("4")
    assert out1 == out4
    assert (l1 == l4).all()
    assert (d1 == d4).all()


@pytest.mark.parametrize("want_sched", [True, False], ids=["sched", "no_sched"])
def test_prepare_many_longest_first_is_index_order(monkeypatch, want_sched):
    """``ymx_prepare_many`` hands its pool the call's long rooms first
    (four times its mean staged bytes or more, longest first), then the
    others in index order.  That is a permutation of the work index and
    nothing else:
    counts, return codes, plans and the updates encoded from them are
    what index order (the serial path, one thread) gives, room by room,
    and what the core gave before its laps were a clock (``PLANS_PR37``);
    and the call's own clock comes back with them."""
    import hashlib

    import numpy as np

    from yjs_tpu.ops.native_mirror import (
        PLAN_POOL_COUNTS, PLAN_TIMES, encode_steps_many, prepare_many,
    )

    rng = random.Random(34)

    def session(seed, n_ops):
        docs = [Y.Doc(gc=False), Y.Doc(gc=False)]
        for c, d in enumerate(docs):
            d.client_id = 10 * (seed + 1) + c
        for _ in range(n_ops):
            d = rng.choice(docs)
            t = d.get_text("text")
            ln = len(t.to_string())
            if ln and rng.random() < 0.3:
                pos = rng.randrange(ln)
                t.delete(pos, min(rng.randint(1, 4), ln - pos))
            else:
                t.insert(rng.randint(0, ln), rng.choice(["ab", "c ", "xyz"]))
            if rng.random() < 0.1:
                Y.apply_update(docs[1], Y.encode_state_as_update(docs[0]))
                Y.apply_update(docs[0], Y.encode_state_as_update(docs[1]))
        Y.apply_update(docs[0], Y.encode_state_as_update(docs[1]))
        return Y.encode_state_as_update(docs[0])

    # short rooms with the long ones last, as a deployment's slots hold
    # them; one room brings two updates, one a malformed one
    sizes = [20, 35, 10, 25, 30, 15, 40, 20, 25, 30, 15, 20, 35, 10, 25, 30]
    sizes += [500, 900]
    updates = [[session(s, n)] for s, n in enumerate(sizes)]
    updates[1].append(session(100, 5))
    updates[4] = [b"\x01\xff\xff\xff"]
    # the last two are long by the pool's rule, and go first, the longer
    # of them before the other; no other room is
    staged = [sum(map(len, ups)) for ups in updates]
    long = [4 * sum(staged) <= n * len(staged) for n in staged]
    assert long == [False] * 16 + [True, True] and staged[-1] > staged[-2]

    def plan(threads):
        monkeypatch.setenv("YTPU_PLAN_THREADS", threads)
        work = []
        for i, ups in enumerate(updates):
            m = NativeMirror("text")
            for u in ups:
                m.ingest(u)
            work.append((i, m))
        counts, rcs, staged, times = prepare_many(work, want_sched=want_sched)
        plans, ok = [], []
        for k, (i, m) in enumerate(work):
            if rcs[k] != 0:
                plans.append(None)
                continue
            m._finish_prepare(int(rcs[k]), staged[k][0], staged[k][1], counts[k])
            p = m.make_plan(counts[k])
            plans.append((
                p.splits.tolist(), p.sched.tolist(),
                sorted(p.delete_rows.tolist()), sorted(p.applied_ds),
                p.link_rows.tolist(), p.link_vals.tolist(),
                p.head_segs.tolist(), p.head_vals.tolist(),
            ))
            ok.append((i, m, int(counts[k][15])))
        encoded, erc = encode_steps_many(ok, {})
        # a plan's number counts its mirror's prepares: the same either way
        return counts.copy(), rcs.tolist(), plans, encoded, erc.tolist(), times

    c1, rc1, p1, e1, erc1, t1 = plan("1")
    c4, rc4, p4, e4, erc4, t4 = plan("4")
    assert rc1 == rc4 and rc1[4] != 0 and sum(map(bool, rc1)) == 1
    np.testing.assert_array_equal(c1, c4)
    assert p1 == p4 and e1 == e4 and erc1 == erc4
    assert all(u is not None for u in e1)
    # counts[3..5] hold what a plan integrated by kind from PR 40 on
    # (plancore.cpp plan_kind_counts); PR 37's rows had zeros there
    as_pr37 = c1.copy()
    assert (as_pr37[:, 3:6] != 0).any()
    as_pr37[:, 3:6] = 0
    # counts[14] says from PR 44 on whether the mirror held no row before
    # the step too (bit 1, plan_shape); PR 37's rows had the dense flag
    assert (as_pr37[:, 14] & 2).any()
    as_pr37[:, 14] &= 1
    digest = hashlib.blake2b(
        repr((as_pr37.tolist(), rc1, p1, e1, erc1)).encode(), digest_size=16
    ).hexdigest()
    assert digest == PLANS_PR37[want_sched]
    phases = PLAN_TIMES[2:7]
    for t in (t1, t4):
        assert tuple(t) == PLAN_TIMES + PLAN_POOL_COUNTS
        assert all(v >= 0.0 for v in t.values())
        # the longest room's prepare is one of the sum's terms, and the
        # phases' laps lie inside their rooms' prepares
        assert 0.0 < t["plan_room_max_s"] <= t["plan_pool_s"]
        assert 0.0 < sum(t[k] for k in phases) <= t["plan_pool_s"]
        assert all(t[k] > 0.0 for k in phases)
    # the pool's own cost to the calling thread: none without a pool,
    # and these eighteen rooms (20 KB staged) are reckoned under a
    # millisecond, so a width of 4 wakes nobody for them either (calls
    # that do: test_pool_gives_what_one_thread_gives_call_after_call)
    for t in (t1, t4):
        assert t["plan_pool_start_s"] == t["plan_pool_join_s"] == 0.0
        assert [t[k] for k in PLAN_POOL_COUNTS] == [1, 0, 0]


# blake2b-128 of the counts, return codes, plans and encoded updates of
# test_prepare_many_longest_first_is_index_order, as the parent of the
# change that timed the core's laps (PR 38) gave them, by want_sched
PLANS_PR37 = {
    True: "7a128842c85145f6d6c9d34b52ec3c5a",
    False: "3b96490a50e2ec152779f9808ae84196",
}


def test_core_has_no_timing_switch_of_its_own(monkeypatch, capfd):
    """The core's laps are a clock the flush reports
    (``last_flush_metrics`` ``plan_scan_s`` ... ``plan_finalize_s``), not
    a switch in the environment: with ``YMX_TIMING`` set, a prepare
    through either entry writes nothing to stdout or stderr."""
    from yjs_tpu.ops.native_mirror import prepare_many

    monkeypatch.setenv("YMX_TIMING", "1")
    doc = Y.Doc(gc=False)
    doc.get_text("text").insert(0, "hello")
    update = Y.encode_state_as_update(doc)
    alone, batched = NativeMirror("text"), NativeMirror("text")
    alone.ingest(update)
    alone.prepare_step()
    batched.ingest(update)
    _counts, rcs, _staged, times = prepare_many([(0, batched)])
    assert rcs.tolist() == [0] and times["plan_scan_s"] > 0.0
    out, err = capfd.readouterr()
    assert (out, err) == ("", "")


# -- the pool that outlives the call (PR 41) ---------------------------------

POOL_CALL_SIZES = (1, 2, 12, 72, 300)
POOL_ROUNDS = 10  # a call a size a round: 50 consecutive calls


@pytest.fixture(scope="module")
def pool_traffic():
    """Eight short writing sessions and a long one, each as the update
    that loads it and ``POOL_ROUNDS - 1`` keystrokes after it.  A load
    is a pasted page and some edits: the core reckons a call's work from
    its rooms and staged bytes, and a call reckoned under a millisecond
    wakes nobody."""
    gen = random.Random(41)

    def session(client, n_ops, pasted=1500):
        doc = Y.Doc(gc=False)
        doc.client_id = client
        text = doc.get_text("text")
        text.insert(0, "".join(gen.choice("abcdef ") for _ in range(pasted)))

        def edit():
            ln = len(text.to_string())
            if ln and gen.random() < 0.3:
                pos = gen.randrange(ln)
                text.delete(pos, min(gen.randint(1, 3), ln - pos))
            else:
                text.insert(gen.randint(0, ln), gen.choice(["a", "bc", "d "]))

        for _ in range(n_ops):
            edit()
        updates = [Y.encode_state_as_update(doc)]
        for _ in range(POOL_ROUNDS - 1):
            sv = Y.encode_state_vector(doc)
            edit()
            updates.append(Y.encode_state_as_update(doc, sv))
        return updates

    return {
        "short": [session(500 + k, 20 + 5 * k) for k in range(8)],
        "long": session(600, 400, pasted=60000),
    }


def _pool_calls(traffic, threads, monkeypatch):
    """``POOL_ROUNDS`` rounds of one ``prepare_many`` call a size of
    ``POOL_CALL_SIZES`` over mirrors that live through the rounds (a
    group's last room is the long one); a digest of each call's counts,
    return codes and plans, and each call's pool counts."""
    import hashlib

    from yjs_tpu.ops.native_mirror import PLAN_POOL_COUNTS, prepare_many

    monkeypatch.setenv("YTPU_PLAN_THREADS", threads)
    groups = {
        n: [(j, NativeMirror("text")) for j in range(n)]
        for n in POOL_CALL_SIZES
    }
    digests, pools = [], []
    for r in range(POOL_ROUNDS):
        for n, work in groups.items():
            for j, m in work:
                long = n > 1 and j == n - 1
                ups = traffic["long"] if long else traffic["short"][j % 8]
                m.ingest(ups[r])
            counts, rcs, staged, times = prepare_many(work)
            plans = []
            for k, (_j, m) in enumerate(work):
                m._finish_prepare(
                    int(rcs[k]), staged[k][0], staged[k][1], counts[k]
                )
                p = m.make_plan(counts[k])
                plans.append((
                    p.splits.tolist(), p.sched.tolist(),
                    p.delete_rows.tolist(), p.applied_ds,
                    p.link_rows.tolist(), p.link_vals.tolist(),
                    p.head_segs.tolist(), p.head_vals.tolist(),
                ))
            digests.append(hashlib.blake2b(
                repr((counts.tolist(), rcs.tolist(), plans)).encode(),
                digest_size=16,
            ).hexdigest())
            pools.append([times[k] for k in PLAN_POOL_COUNTS])
    return digests, pools


@pytest.fixture(scope="module")
def pool_serial_digests(pool_traffic):
    mp = pytest.MonkeyPatch()
    try:
        digests, pools = _pool_calls(pool_traffic, "1", mp)
    finally:
        mp.undo()
    # the serial branch: one thread, nobody woken, nothing constructed
    assert pools == [[1, 0, 0]] * len(digests)
    return digests


@pytest.mark.parametrize("threads", ["2", "4", "13"])
def test_pool_gives_what_one_thread_gives_call_after_call(
    monkeypatch, pool_traffic, pool_serial_digests, threads
):
    """Fifty consecutive calls of 1, 2, 12, 72 and 300 rooms on workers
    that live through them, one long room among the short ones of each:
    counts, return codes and plans are the serial branch's, call by
    call, at every width; and a call plans on no more threads than the
    width or its rooms."""
    digests, pools = _pool_calls(pool_traffic, threads, monkeypatch)
    assert digests == pool_serial_digests
    sizes = POOL_CALL_SIZES * POOL_ROUNDS
    for n, (used, woken, _made) in zip(sizes, pools):
        assert 1 <= used <= min(int(threads), n) and woken == used - 1
    # the widest calls hold work for every thread the width allows
    assert max(p[0] for p in pools) == int(threads)


POOL_PROCESS = r"""
import json, os, sys, warnings
import numpy as np
import yjs_tpu as Y
from yjs_tpu.ops.native_mirror import NativeMirror, prepare_many

def update(client):
    doc = Y.Doc(gc=False)
    doc.client_id = client
    doc.get_text("text").insert(0, "a room of the pool's test " * 40)
    return Y.encode_state_as_update(doc)

UPDATES = [update(700 + k) for k in range(8)]

def call(threads, n=300):
    os.environ["YTPU_PLAN_THREADS"] = threads
    work = [(j, NativeMirror("text")) for j in range(n)]
    for j, m in work:
        m.ingest(UPDATES[j % 8])
    counts, rcs, _staged, times = prepare_many(work)
    return counts.tolist(), rcs.tolist(), [
        times[k] for k in ("plan_threads", "plan_pool_woken", "plan_pool_started")
    ]

out = {"calls": []}
serial = call("1")
for threads in ("2", "2", "4", "4", "2", "13", "13", "4"):
    counts, rcs, pool = call(threads)
    out["calls"].append([int(threads), pool, (counts, rcs) == serial[:2]])
r, w = os.pipe()
with warnings.catch_warnings():
    # the pool's parked workers are the threads Python warns of
    warnings.simplefilter("ignore", DeprecationWarning)
    pid = os.fork()
if pid == 0:
    os.close(r)
    counts, rcs, pool = call("4")
    again = call("4")[2]
    os.write(w, json.dumps([pool, again, (counts, rcs) == serial[:2]]).encode())
    os._exit(0)
os.close(w)
out["child"] = json.loads(os.read(r, 1 << 16).decode())
out["child_status"] = os.waitpid(pid, 0)[1]
out["after_fork"] = call("4")[2]
print(json.dumps(out))
# and now exit, with the workers parked
"""


@pytest.fixture(scope="module")
def pool_process():
    """One process of its own, so that the pool starts empty: calls of
    300 rooms at widths 2, 2, 4, 4, 2, 13, 13, 4; then a fork whose
    child plans twice at width 4; then the exit with workers parked."""
    import json
    import os
    import subprocess
    import sys

    if not hasattr(os, "fork"):
        pytest.skip("no os.fork here")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": root}
    p = subprocess.run(
        [sys.executable, "-c", POOL_PROCESS], env=env, cwd=root,
        capture_output=True, text=True, timeout=300,
    )
    out = json.loads(p.stdout) if p.returncode == 0 and p.stdout else None
    return p.returncode, out, p.stderr


def test_pool_constructs_its_threads_once(pool_process):
    """``plan_pool_started`` is what a call had to construct: the
    width's workers in the first call of a width wider than any before,
    0 in every call after it, a narrower width's among them."""
    rc, out, err = pool_process
    assert rc == 0, err
    assert [c[:2] for c in out["calls"]] == [
        [2, [2, 1, 1]], [2, [2, 1, 0]],
        [4, [4, 3, 2]], [4, [4, 3, 0]],
        [2, [2, 1, 0]],
        [13, [13, 12, 9]], [13, [13, 12, 0]],
        [4, [4, 3, 0]],
    ]
    assert all(same for _t, _pool, same in out["calls"])
    assert out["after_fork"] == [4, 3, 0]


def test_pool_lets_the_process_exit_with_workers_parked(pool_process):
    """Twelve workers are parked when the interpreter exits: exit code
    0, and nothing on stderr (a joinable ``std::thread`` destroyed at
    exit would call ``std::terminate``)."""
    rc, out, err = pool_process
    assert (rc, err) == (0, "") and out is not None


def test_pool_is_made_anew_in_a_forked_child(pool_process):
    """A forked child holds the parent's pool and none of its threads:
    its first call constructs workers of its own, its second none, and
    its plans are the serial branch's."""
    rc, out, err = pool_process
    assert rc == 0, err
    first, again, same = out["child"]
    assert first == [4, 3, 3] and again == [4, 3, 0] and same
    assert out["child_status"] == 0


def test_pool_serves_one_call_at_a_time(monkeypatch):
    """Two Python threads flush an engine each at once (``ctypes``
    releases the GIL around the native call): the call that finds the
    pool taken plans on its own thread, both engines hold what a
    ``Y.Doc`` holds, and neither waits for the other."""
    import sys
    import threading

    from yjs_tpu.ops import BatchEngine

    monkeypatch.setenv("YTPU_PLAN_THREADS", "4")
    monkeypatch.setenv("YTPU_PLAN_CACHE", "0")
    n_rooms, rounds = 48, 12
    gen = random.Random(7)

    def typist(client):
        doc = Y.Doc(gc=False)
        doc.client_id = client
        text = doc.get_text("text")
        updates = []
        for _ in range(rounds):
            sv = Y.encode_state_vector(doc)
            for _ in range(30):
                ln = len(text.to_string())
                text.insert(gen.randint(0, ln), gen.choice(["ab", "c ", "xyz"]))
            updates.append(Y.encode_state_as_update(doc, sv))
        return updates, text.to_string()

    typists = [typist(800 + k) for k in range(8)]
    engines = [BatchEngine(n_rooms), BatchEngine(n_rooms)]
    failed = []

    def flusher(k):
        eng = engines[k]
        try:
            for r in range(rounds):
                for i in range(n_rooms):
                    eng.queue_update(i, typists[i % 8][0][r])
                eng.flush()
        except BaseException as e:  # noqa: BLE001 - reported by the test
            failed.append(e)
            raise

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=flusher, args=(k,)) for k in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    assert not failed
    for eng in engines:
        for i in range(n_rooms):
            assert eng.text(i) == typists[i % 8][1]
