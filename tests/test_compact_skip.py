"""A compaction that would change nothing is not run: ``_maybe_compact``
puts the rooms that have doubled to the native mirror
(``NativeMirror.compact_changes_many``, ``Mirror::compact_changes``) and
rebuilds those that answer yes.  Held here to the rebuild itself, which
the tests keep as the reference: the answer is what
``rebuild_compacted_self`` then does to the room, and an engine that
skips leaves the device tables, the encoded state and every read as an
engine whose question is forced to yes leaves them."""

import importlib.util
import json
import random
import re
import zlib
from pathlib import Path

import numpy as np
import pytest

import yjs_tpu as Y
from yjs_tpu.ops import BatchEngine
from yjs_tpu.ops.native_mirror import NativeMirror, native_plan_available

pytestmark = pytest.mark.skipif(
    not native_plan_available(), reason="native plan core unavailable"
)

ROOT = Path(__file__).resolve().parent.parent
FIXTURES = ROOT / "tests" / "fixtures"


def _traces(stem):
    from benchmarks.deployment import load_traces

    return load_traces(stem)


def _typed(base, client, n, rng=None, backspace=0.0, cursor=False):
    """``base`` (one encoded state) and ``n`` keystrokes typed on top of
    it by ``client``, each its own update: at the end of the text, or
    where ``rng`` says (``cursor``: at a cursor that jumps now and then,
    as a person types), a share ``backspace`` of them deletions."""
    d = Y.Doc(gc=False)
    if base is not None:
        Y.apply_update(d, base)
    d.client_id = client
    t = d.get_text("text")
    out = [] if base is None else [base]
    at = len(t)
    for k in range(n):
        sv = Y.encode_state_vector(d)
        if rng is None:
            at = len(t)
        elif not cursor or rng.random() < 0.04:
            at = rng.randint(0, len(t))
        if rng is not None and at and rng.random() < backspace:
            at -= 1
            t.delete(at, 1)
        else:
            t.insert(at, "abcdefgh "[k % 9])
            at += 1
        out.append(Y.encode_state_as_update(d, sv))
    return out


def _whole(updates, gc=False):
    """What a server that held ``updates`` writes for a restart: one
    encoded state."""
    d = Y.Doc(gc=gc)
    for u in updates:
        Y.apply_update(d, u)
    return Y.encode_state_as_update(d)


def _map_room():
    """A ``Y.Map`` root whose keys are written again and again, each
    write its own update (last writer wins: the chains grow)."""
    d = Y.Doc(gc=False)
    d.client_id = 31
    m = d.get_map("text")
    out = []
    for k in range(40):
        sv = Y.encode_state_vector(d)
        m.set(f"key{k % 5}", k)
        out.append(Y.encode_state_as_update(d, sv))
    return out


def _gc_structs():
    """A room that holds GC structs, brought by two updates so that two
    of one client's lie clock to clock: a collecting ``Y.Doc`` replaces
    the children of a deleted nested type by GC structs."""
    d = Y.Doc(gc=True)
    d.client_id = 41
    arr = d.get_array("text")
    out = []
    for k in range(2):
        sv = Y.encode_state_vector(d)
        inner = Y.YArray()
        arr.insert(0, [inner])
        inner.insert(0, [f"child {k} {j}" for j in range(6)])
        inner.insert(3, ["x", "y"])
        arr.delete(0, 1)
        out.append(Y.encode_state_as_update(d, sv))
    return out


def _gc_structs_clock_to_clock():
    """Two updates of one GC struct each, the second's clock where the
    first ends (V1: one client, one struct, client 41, its clock; info 0,
    the length; an empty delete set): two rows that merge."""
    return [bytes([1, 1, 41, 0, 0, 5, 0]), bytes([1, 1, 41, 5, 0, 4, 0])]


def _prosemirror():
    return zlib.decompress(
        (ROOT / "benchmarks" / "prosedocs" / "pm-101.bin.z").read_bytes()
    )


def _prepend_100000():
    return zlib.decompress(
        (FIXTURES / "prepend_frag_100000.bin.z").read_bytes()
    )


# name -> (updates of the room, gc, the answer: None where the reference
# alone says).  Every kind of room the fixtures give, each a case.
ROOMS = {
    "one-state": (lambda: [_traces("distinct_traces")[0]], False, False),
    "one-state-gc": (lambda: [_traces("distinct_traces")[0]], True, True),
    "one-state-typed-tail": (
        lambda: _typed(_traces("distinct_traces")[3], 777, 24), False, True,
    ),
    "typed-then-saved": (
        lambda: [_whole(_typed(_traces("distinct_traces")[3], 777, 24))],
        False, False,
    ),
    "backspaces": (
        lambda: _typed(None, 5, 300, random.Random(2), 0.4), False, True,
    ),
    "backspaces-gc": (
        lambda: _typed(None, 5, 300, random.Random(2), 0.4), True, True,
    ),
    "backspaces-saved": (
        lambda: [_whole(_typed(None, 5, 300, random.Random(2), 0.4))],
        False, False,
    ),
    "backspaces-saved-gc": (
        lambda: [_whole(_typed(None, 5, 300, random.Random(2), 0.4))],
        True, True,
    ),
    "backspaces-saved-by-a-collecting-doc": (
        lambda: [_whole(_typed(None, 5, 300, random.Random(2), 0.4), gc=True)],
        True, False,
    ),
    "storm": (lambda: [_traces("storm_traces")[2]], False, False),
    "storm-gc": (lambda: [_traces("storm_traces")[2]], True, True),
    "b4": (lambda: [(FIXTURES / "b4_trace.bin").read_bytes()], False, None),
    "map": (_map_room, False, None),
    "map-gc": (_map_room, True, True),
    "prosemirror": (lambda: [_prosemirror()], False, False),
    "prosemirror-typed": (
        lambda: [_prosemirror()] + _typed(None, 909, 12), False, True,
    ),
    "gc-structs": (_gc_structs, True, None),
    "gc-structs-clock-to-clock": (_gc_structs_clock_to_clock, False, True),
    "gc-structs-saved": (lambda: [_whole(_gc_structs(), gc=True)], True, None),
    "prepend-100000": (lambda: [_prepend_100000()], False, False),
    "empty": (lambda: [], False, False),
}


def _mirror(updates):
    m = NativeMirror("text")
    for u in updates:
        m.ingest(u)
        m.prepare_step()
    return m


def _columns(m):
    """What a rebuild may change of a room, read from the core."""
    m._sync()
    py = m._py
    deleted = np.zeros(m.n_rows, bool)
    deleted[sorted(py._host_deleted_rows)] = True
    return {
        "n_rows": m.n_rows,
        "right": np.array(py.list_next, np.int64),
        "deleted": deleted,
        "heads": np.array(py.head_of_seg, np.int64),
        "ref": np.array(py.row_content_ref, np.int64),
        "is_gc": np.array(py.row_is_gc, np.int64),
        "len": np.array(py.row_len, np.int64),
    }


def _delete_set(m):
    return {
        c: [(i.clock, i.len) for i in r]
        for c, r in m.delete_set().clients.items()
    }


def _reads(m):
    return {
        "state": m.encode_state_as_update(),
        "sv": m.state_vector(),
        "ds": _delete_set(m),
    }


@pytest.mark.parametrize("name", sorted(ROOMS))
def test_the_answer_is_what_the_rebuild_does(name):
    """The native answer equals "``rebuild_compacted_self`` changed
    ``n_rows``, a content or a ``gc`` bit"; and where it is no, the
    rebuild hands back the links, deleted bits and heads the room held,
    and leaves every read of it as it was (the delete set's ranges, which
    it still sorts and unions in place, are read through a union)."""
    make, gc, expected = ROOMS[name]
    m = _mirror(make())
    answer = bool(NativeMirror.compact_changes_many([m], gc)[0])
    before, reads = _columns(m), _reads(m)
    assert NativeMirror.compact_changes_many([m], gc)[0] == answer  # no write
    r, d, h = m.rebuild_compacted_self(gc)
    after = _columns(m)
    changed = before["n_rows"] != after["n_rows"] or any(
        not np.array_equal(before[k], after[k]) for k in ("ref", "is_gc")
    )
    assert answer == changed
    if expected is not None:
        assert answer == expected
    if not answer:
        n = before["n_rows"]
        assert len(r) == len(d) == n
        np.testing.assert_array_equal(r, before["right"])
        np.testing.assert_array_equal(d, before["deleted"])
        np.testing.assert_array_equal(h[: len(before["heads"])], before["heads"])
        for k in before:
            np.testing.assert_array_equal(before[k], after[k])
        assert _reads(m) == reads
        # and a second rebuild is asked the same
        assert not NativeMirror.compact_changes_many([m], gc)[0]
    else:
        # what a rebuild leaves has nothing more to merge
        assert not NativeMirror.compact_changes_many([m], gc)[0]


def test_one_call_answers_for_every_room_it_is_given():
    names = sorted(n for n in ROOMS if n != "prepend-100000")
    for gc in (False, True):
        mirrors = [_mirror(ROOMS[n][0]()) for n in names]
        many = NativeMirror.compact_changes_many(mirrors, gc)
        assert many.dtype == bool and many.shape == (len(names),)
        one = [
            bool(NativeMirror.compact_changes_many([m], gc)[0])
            for m in mirrors
        ]
        assert many.tolist() == one
        assert True in one and False in one
    assert NativeMirror.compact_changes_many([], False).shape == (0,)


# ---- the engine: a skip against a forced rebuild ---------------------------


def _force_yes(monkeypatch):
    monkeypatch.setattr(
        NativeMirror, "compact_changes_many",
        staticmethod(lambda mirrors, gc: np.ones(len(mirrors), bool)),
    )


def _snapshot(eng, slots):
    out = {
        "tables": [
            np.asarray(t).copy()
            for t in (eng._right, eng._deleted, eng._starts)
        ],
        "rows_at_compact": list(eng._rows_at_compact),
    }
    for i in slots:
        out[i] = {
            "state": eng.encode_state_as_update(i),
            "sv": eng.state_vector(i),
            "ds": _delete_set(eng.mirrors[i]),
            "text": eng.text(i),
            "n_rows": eng.mirrors[i].n_rows,
        }
    return out


def _same(a, b):
    assert a.keys() == b.keys()
    for k in a:
        if k == "tables":
            for x, y in zip(a[k], b[k]):
                assert x.shape == y.shape and x.dtype == y.dtype
                np.testing.assert_array_equal(x, y)
        else:
            assert a[k] == b[k], k


@pytest.mark.parametrize("gc", [False, True])
@pytest.mark.parametrize("mesh", [False, True])
def test_a_skip_leaves_what_a_rebuild_leaves(monkeypatch, gc, mesh):
    """Two engines fed the same updates, one with the question forced to
    yes: equal device rows, encoded state, state vector, delete set and
    text after the load, after the look that skips and after 600 more
    keystrokes (so the rooms double and compact from the skipped
    state)."""
    saved = [
        _whole(_typed(None, 5, 300, random.Random(2), 0.4), gc=gc),
        _traces("distinct_traces")[1],
        _whole(_typed(None, 6, 90, random.Random(3), 0.2), gc=gc),
    ]
    # the traffic, made once: both engines take the same bytes
    first = [_typed(s, 900 + k, 1)[1] for k, s in enumerate(saved)]
    # (the trace's room holds 988 rows: it takes more typing to double)
    more = [
        _typed(
            Y.merge_updates([s, f]), 950 + k, n, random.Random(k), 0.25,
            cursor=True,
        )[1:]
        for k, (s, f, n) in enumerate(zip(saved, first, (600, 1500, 600)))
    ]
    slots = (1, 4, 6)

    def run(forced):
        kw = {}
        if mesh:
            from yjs_tpu.parallel import doc_mesh

            try:
                kw["mesh"] = doc_mesh(4, backend="cpu")
            except RuntimeError as e:  # YTPU_TEST_PLATFORM=tpu: one chip
                pytest.skip(f"no CPU mesh beside this backend: {e}")
        if forced:
            _force_yes(monkeypatch)
        eng = BatchEngine(8, gc=gc, compact_min_rows=64, **kw)
        shots, skipped = [], []
        for i, s in zip(slots, saved):
            assert eng.queue_update(i, s)
        eng.flush()
        shots.append(_snapshot(eng, slots))
        for i, u in zip(slots, first):
            assert eng.queue_update(i, u)
        eng.flush()  # its look is put to the three loaded rooms
        skipped.append(eng.last_flush_metrics["rooms_compact_skipped"])
        shots.append(_snapshot(eng, slots))
        compactions, compacted = 0, set()
        last = eng.last_compaction
        if forced:  # the rebuild the other engine is spared merged nothing
            assert [c["doc"] for c in last] == list(slots)
            assert all(c["rows_before"] == c["rows_after"] for c in last)
        elif gc:  # the trace was saved by a doc that kept its contents
            assert [c["doc"] for c in last] == [4]
        else:
            assert last is None
        for k in range(1500):
            for i, us in zip(slots, more):
                if k < len(us):
                    assert eng.queue_update(i, us[k])
            if k % 7 == 0:
                eng.flush()
                skipped.append(eng.last_flush_metrics["rooms_compact_skipped"])
                if eng.last_compaction is not last:
                    last = eng.last_compaction
                    compactions += 1
                    compacted.update(c["doc"] for c in last)
        eng.flush()
        shots.append(_snapshot(eng, slots))
        if forced:
            monkeypatch.undo()
        # every room has doubled and compacted from where the look left it
        assert compacted == set(slots)
        return shots, skipped, compactions

    (a0, a1, a2), skipped, compactions = run(forced=False)
    (b0, b1, b2), skipped_b, compactions_b = run(forced=True)
    # with gc a room saved by a collecting doc has nothing left to drop
    assert skipped[0] == (2 if gc else 3) and not any(skipped_b)
    assert compactions_b == compactions >= 3
    for a, b in ((a0, b0), (a1, b1), (a2, b2)):
        _same(a, b)
    # and both hold what a Y.Doc holds
    for k, i in enumerate(slots):
        ref = Y.Doc(gc=False)
        for u in [saved[k], first[k], *more[k]]:
            Y.apply_update(ref, u)
        assert a2[i]["text"] == ref.get_text("text").to_string()
        assert a2[i]["sv"] == Y.decode_state_vector(Y.encode_state_vector(ref))


def test_a_skipped_room_is_asked_again_only_when_it_has_doubled(monkeypatch):
    """A room that answered no gets the ``_rows_at_compact`` a rebuild
    that merged nothing would have left: the look leaves it alone until
    it holds twice those rows, and ``last_compaction`` keeps the object
    it held while every look skips."""
    asked = []
    real = NativeMirror.compact_changes_many

    def spy(mirrors, gc):
        out = real(mirrors, gc)
        asked.append([(m.n_rows, bool(c)) for m, c in zip(mirrors, out)])
        return out

    monkeypatch.setattr(NativeMirror, "compact_changes_many", staticmethod(spy))
    eng = BatchEngine(4, compact_min_rows=64)
    # 100 prepended characters in one transaction: 100 rows, none merges
    d = Y.Doc(gc=False)
    d.client_id = 3
    t = d.get_text("text")
    d.transact(lambda _txn: [t.insert(0, "abcdefghij"[k % 10]) for k in range(100)])
    eng.queue_update(2, Y.encode_state_as_update(d))
    eng.flush()
    assert eng.last_flush_metrics["rooms_compact_looked"] == 0
    assert eng._rows_at_compact[2] == 0 and asked == []
    before = eng.last_compaction

    def prepend(n):
        # one transaction, n prepends: n rows that no neighbour merges with
        sv = Y.encode_state_vector(d)
        d.transact(
            lambda _txn: [t.insert(0, "klmnopqrst"[k % 10]) for k in range(n)]
        )
        eng.queue_update(2, Y.encode_state_as_update(d, sv))
        eng.flush()

    prepend(1)  # this flush's look is the first to read the loaded room
    assert asked == [[(100, False)]]
    assert eng._rows_at_compact[2] == 100
    m = eng.last_flush_metrics
    assert (m["rooms_compact_looked"], m["rooms_compact_skipped"]) == (1, 1)
    for _ in range(5):
        prepend(19)  # 101 rows at the first look of these, 177 at the last
        m = eng.last_flush_metrics
        assert (m["rooms_compact_looked"], m["rooms_compact_skipped"]) == (1, 0)
    assert len(asked) == 1 and eng.mirrors[2].n_rows == 196
    prepend(10)  # the look reads 196 rows: under twice 100
    assert len(asked) == 1
    prepend(1)  # the look reads 206: doubled from where it was skipped
    assert asked[1:] == [[(206, False)]]
    assert eng._rows_at_compact[2] == 206
    assert eng.last_compaction is before
    assert eng.text(2) == t.to_string()


# ---- the benchmark's pin of what that rebuild staged ------------------------


def test_the_tiny_longtail_cell_stages_no_block_for_the_rooms_it_loaded(
    monkeypatch, capsys
):
    """``tests/bench/test_longtail_cell.py::test_the_tiny_cell_is_correct_
    and_plans_every_room_cold`` (``tests/conftest.py``,
    ``PINNED_TO_A_REBUILD_OF_NOTHING``) as it stands, but for the line it
    pins to the three blocks the keystroke's flush staged for rooms it
    rebuilt to themselves: that flush now asks the group's 12 rooms,
    rebuilds none and stages nothing."""
    from benchmarks import harness

    spec = importlib.util.spec_from_file_location(
        "bench_conftest", ROOT / "tests" / "bench" / "conftest.py"
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    tiny = bench.tiny_manifest_of(manifest)
    tiny["workloads"].append({
        "name": "tiny-longtail", "config": "tiny-longtail",
        "traffic": "tiny-coldstart-longtail", "chips": 1, "why": "tests",
    })
    real = {x["name"]: x for x in manifest["end_to_end"] + manifest["per_layer"]}
    for metric in tiny["end_to_end"] + tiny["per_layer"]:
        if "longtail-coldstart" in real[metric["name"]].get("workloads", ()):
            metric["workloads"] = metric["workloads"] + ["tiny-longtail"]
    looks = []  # (looked, skipped, blocks staged) a flush of the window
    flush = harness.Cell.flush

    def noted(cell):
        flush(cell)
        if cell.in_window:
            m = cell.prov.engine.last_flush_metrics
            looks.append((
                m["rooms_compact_looked"], m["rooms_compact_skipped"],
                m["rows_staged_blocks"],
            ))

    monkeypatch.setattr(harness.Cell, "flush", noted)
    r = harness.run_cell(
        "tiny-longtail", bench.BIG_SEED, 0.4, False, platform="cpu",
        roots=(bench.CELLS, harness.HERE), manifest=tiny,
    )
    out = capsys.readouterr().out
    assert r["correct"] is True and r["failed"] == 0
    assert set(r["metrics"]) == {"bulk_rate", "setup_s"}
    checks = re.findall(r"check (\w+): (\d+) \(limit 0\) (ok|FAILED)", out)
    assert len(checks) >= 19 and all(v == "0" and s == "ok" for _n, v, s in checks)
    assert "load flushes in the window: cold plans [12], clones and cache hits [0]" in out
    assert (
        "keystroke flushes in the window: cold plans [1], clones and cache "
        "hits [0], rows_staged_blocks [0]"
    ) in out
    assert "2 long rooms held to documents.json" in out and ", 0 differ" in out
    elements, loads = map(int, re.search(
        r"(\d+) elements a load, (\d+) loads", out
    ).groups())
    assert loads >= 1 and elements > 282_000
    # a load's flush has nothing to look at (the rooms planned before it
    # were released); the keystroke's flush looks at the group it loaded
    assert looks == [(0, 0, 0), (12, 12, 0)] * loads
