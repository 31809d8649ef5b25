"""The planners' segment pass (ISSUE 15): fast set and conflict residue.

The correctness bar: under every seeded corpus shape (prepend-storm,
interleaved, 4-client conflict storm, B4-texture trace head) a plan made
with the segment pass must equal the sequential YATA walk
**struct-for-struct** — identical sched/link/head/delete plans — the
native core's plans must equal the Python planner's, and the engine must
converge on what the CPU core (``yjs_tpu/core.py``) holds on both native
and pure-Python mirrors, including across demotion→promotion and
kill-primary failover.  Plus the ISSUE 15 satellite pins: snapshot reuse
on monotone prepend runs (the `plan_snapshot` host op must stay cold)
and the fast-set/residue metrics accounting.
"""

import random

import pytest

import yjs_tpu as Y
from yjs_tpu.obs import FLUSH_METRICS_SCHEMA
from yjs_tpu.obs.prof import kernel_profiler
from yjs_tpu.ops import BatchEngine
from yjs_tpu.ops import plan_cache
from yjs_tpu.ops import segment_planner
from yjs_tpu.ops.columns import DocMirror
from yjs_tpu.updates import (
    apply_update,
    encode_state_as_update,
    encode_state_vector,
)

pytestmark = pytest.mark.planner

SHAPES = ("prepend_storm", "interleaved", "storm", "b4_head")


@pytest.fixture(autouse=True)
def _fresh_cache():
    plan_cache.reset_cache()
    yield
    plan_cache.reset_cache()


def corpus(shape: str, seed: int, n_ops: int = 90) -> list[bytes]:
    """Seeded incremental updates from concurrent editors, one list per
    (shape, seed).  ``b4_head`` reproduces the head of the B4 fixture's
    editing texture (scripts/gen_b4_fixture.py): single-char typing and
    backspace runs at a mostly-sequential cursor, periodic syncs."""
    gen = random.Random(seed)
    n_clients = 4 if shape == "storm" else 2 if shape == "b4_head" else 3
    docs = []
    for k in range(n_clients):
        d = Y.Doc(gc=False)
        d.client_id = 300 + k
        docs.append(d)
    out: list[bytes] = []
    cursors = {id(d): 0 for d in docs}
    j = 0
    while len(out) < n_ops:
        if shape == "b4_head" and gen.random() < 0.1:
            j = gen.randrange(n_clients)
        elif shape != "b4_head":
            j = gen.randrange(n_clients)
        d = docs[j]
        t = d.get_text("text")
        sv = encode_state_vector(d)
        if shape == "prepend_storm":
            t.insert(0, gen.choice("abcdef") * gen.randint(1, 2))
        elif shape == "storm":
            t.insert(min(len(t), gen.randrange(3)), gen.choice("xyz "))
        elif shape == "b4_head":
            cur = min(cursors[id(d)], len(t))
            if gen.random() < 0.05:
                cur = gen.randint(0, len(t))
            if len(t) and cur and gen.random() < 0.3:
                t.delete(cur - 1, 1)  # backspace
                cur -= 1
            else:
                t.insert(cur, gen.choice("etaoin shr"))
                cur += 1
            cursors[id(d)] = cur
        elif len(t) and gen.random() < 0.25:
            t.delete(gen.randrange(len(t)), 1)
        else:
            t.insert(gen.randrange(len(t) + 1), gen.choice("abcdef "))
        out.append(encode_state_as_update(d, sv))
        sync_p = 0.05 if shape == "storm" else 0.3
        if gen.random() < sync_p:
            k = gen.randrange(n_clients)
            if k != j:
                apply_update(docs[k], encode_state_as_update(d))
    return out


def core_doc(updates) -> Y.Doc:
    """The CPU core fed the same updates: the oracle that planned
    nothing."""
    d = Y.Doc(gc=False)
    for u in updates:
        apply_update(d, u)
    return d


def canonical(update: bytes) -> bytes:
    return Y.merge_updates([update])


# -- oracle: plans with the segment pass == sequential YATA walk --------------


def plan_tuple(p):
    return (
        [tuple(int(x) for x in e) for e in p.sched],
        [tuple(int(x) for x in e) for e in p.splits],
        [int(x) for x in p.link_rows], [int(x) for x in p.link_vals],
        [int(x) for x in p.head_segs], [int(x) for x in p.head_vals],
        sorted(int(r) for r in p.delete_rows),
    )


def drive_mirror(m, updates, every=6):
    """``(plans, state, frontier)`` and the structs the Python planner's
    fast set placed (0 for the native core, which counts its own)."""
    plans, fast = [], 0
    for j, u in enumerate(updates):
        m.ingest(u, False)
        if (j + 1) % every == 0 or j == len(updates) - 1:
            p = m.prepare_step()
            fast += getattr(p, "segment_fast", 0)
            plans.append(plan_tuple(p))
    return (plans, m.encode_state_as_update(), m.plan_frontier), fast


def walk_alone(mp, updates):
    """The sequential walk with no segment pass before it: every struct
    is residue.  Steered from the test; the program has no such lane."""
    mp.setattr(segment_planner, "plan_doc", lambda q, snapshot=None: None)
    ref, fast = drive_mirror(DocMirror("text"), updates)
    assert fast == 0
    return ref


@pytest.mark.parametrize("shape", SHAPES)
def test_device_ranks_match_sequential_walk(shape, monkeypatch):
    """Struct-for-struct: every flush's sched entries, link writes, head
    writes and delete rows must be identical between a plan whose fast
    set was spliced in bulk from its ranks and the pure sequential walk,
    and the room must read as the CPU core's."""
    updates = corpus(shape, seed=15)
    got, _fast = drive_mirror(DocMirror("text"), updates)
    with monkeypatch.context() as mp:
        ref = walk_alone(mp, updates)
    assert got == ref, "segment pass diverged from walk"
    back = core_doc([got[1]])
    assert (
        back.get_text("text").to_string()
        == core_doc(updates).get_text("text").to_string()
    )


@pytest.mark.parametrize("shape", SHAPES)
def test_native_plans_match_walk(shape, monkeypatch):
    """The native core's chain-run anchor adoption must not change one
    plan array either: held to the Python planner's sequential walk."""
    from yjs_tpu.ops.native_mirror import NativeMirror, native_plan_available

    if not native_plan_available():
        pytest.skip("native plancore unavailable")
    updates = corpus(shape, seed=23)
    (plans, state, _frontier), _ = drive_mirror(NativeMirror("text"), updates)
    with monkeypatch.context() as mp:
        ref_plans, ref_state, _f = walk_alone(mp, updates)
    assert plans == ref_plans
    want = core_doc(updates)
    for s in (state, ref_state):
        assert (
            core_doc([s]).get_text("text").to_string()
            == want.get_text("text").to_string()
        )


# -- engine-level identity: either planner vs the CPU core --------------------


def run_engine(updates, n_docs, monkeypatch, py=False, flush_every=6):
    if py:
        monkeypatch.setenv("YTPU_NO_NATIVE_PLAN", "1")
    eng = BatchEngine(n_docs)
    deltas = {i: [] for i in range(n_docs)}
    eng.on_update(lambda i, u: deltas[i].append(u))
    sums = {"plan_segment_fast": 0, "plan_segment_residue": 0,
            "plan_threads": 0}
    keysets = set()
    for j, u in enumerate(updates):
        for i in range(n_docs):
            eng.queue_update(i, u)
        if (j + 1) % flush_every == 0 or j == len(updates) - 1:
            eng.flush()
            m = eng.last_flush_metrics
            keysets.add(frozenset(m))
            sums["plan_segment_fast"] += m["plan_segment_fast"]
            sums["plan_segment_residue"] += m["plan_segment_residue"]
            sums["plan_threads"] = max(
                sums["plan_threads"], m["plan_threads"]
            )
    states = [eng.encode_state_as_update(i) for i in range(n_docs)]
    texts = [eng.text(i) for i in range(n_docs)]
    return states, texts, deltas, sums, keysets


@pytest.mark.parametrize("py", [False, True], ids=["native", "python"])
@pytest.mark.parametrize("shape", ["prepend_storm", "storm", "b4_head"])
def test_engine_device_vs_off_byte_identical(shape, py, monkeypatch):
    """An engine on either planner against the CPU core: every room's
    text and state, and the broadcasts replayed on a new ``Y.Doc``."""
    updates = corpus(shape, seed=31)
    monkeypatch.setenv("YTPU_PLAN_CACHE", "0")
    states, texts, deltas, sums, keys = run_engine(
        updates, 3, monkeypatch, py=py
    )
    want = core_doc(updates)
    want_text = want.get_text("text").to_string()
    want_state = canonical(encode_state_as_update(want))
    assert texts == [want_text] * 3
    assert [canonical(s) for s in states] == [want_state] * 3
    for i in range(3):
        assert core_doc(deltas[i]).get_text("text").to_string() == want_text
    # the Python planner's lane plans a room at a time
    if py:
        assert sums["plan_threads"] == 1
    # ONE metrics schema either way
    assert keys == {frozenset(FLUSH_METRICS_SCHEMA)}


def test_device_mode_counts_fast_set(monkeypatch):
    """Typing/prepend-heavy traffic must actually exercise the fast set
    (bulk integration of chained runs), not silently fall back."""
    updates = corpus("prepend_storm", seed=47)
    monkeypatch.setenv("YTPU_PLAN_CACHE", "0")
    _s, _t, _d, sums, _k = run_engine(updates, 2, monkeypatch, py=True)
    assert sums["plan_segment_fast"] > 0


# -- plan-cache interop: warm hits byte-identical, cache on vs off ------------


def test_device_plans_fold_same_frontier_as_walk(monkeypatch):
    """Cache interop is exact: a prepare with a fast set folds the same
    frontier digest as the walk (``drive_mirror`` returns it, above), so
    warm cache hits replay states that are byte-identical with the cache
    off."""
    updates = corpus("interleaved", seed=7)
    monkeypatch.setenv("YTPU_PLAN_CACHE", "1")
    plan_cache.reset_cache()
    s_on, t_on, d_on, _s1, _k1 = run_engine(updates, 2, monkeypatch, py=True)
    plan_cache.reset_cache()
    monkeypatch.setenv("YTPU_PLAN_CACHE", "0")
    s_off, t_off, d_off, _s2, _k2 = run_engine(
        updates, 2, monkeypatch, py=True
    )
    assert (t_on, s_on, d_on) == (t_off, s_off, d_off)


# -- lifecycle: demotion→promotion and failover ------------------------------


def test_demotion_promotion_device_vs_off():
    """A room demoted and promoted on demand, then typed into again,
    holds what the CPU core holds after the same two updates."""
    from yjs_tpu.provider import TpuProvider
    from yjs_tpu.tiering import TierConfig

    def upd(text, cid=1, at=0):
        d = Y.Doc(gc=False)
        d.client_id = cid
        d.get_text("text").insert(at, text)
        return encode_state_as_update(d)

    first, second = upd("round trip "), upd("second", cid=2)
    p = TpuProvider(2, tier_config=TierConfig(enabled=True))
    p.receive_update("r", first)
    p.flush()
    assert p.demote_doc("r", "warm")
    assert p.text("r") == "round trip "  # demand promotion
    p.receive_update("r", second)
    p.flush()
    want = core_doc([first, second])
    assert p.text("r") == want.get_text("text").to_string()
    assert canonical(p.encode_state_as_update("r")) == canonical(
        encode_state_as_update(want)
    )


def test_failover_promotion_with_planner_on(tmp_path):
    """Kill-primary failover: promoted slots rebuild from journals and
    must converge to the uninterrupted reference byte-for-byte."""
    from yjs_tpu.fleet import FailoverConfig, FleetRouter
    from yjs_tpu.persistence import WalConfig

    fleet = FleetRouter(
        3, 4, backend="cpu", wal_dir=tmp_path,
        wal_config=WalConfig(segment_bytes=256, fsync="never"),
        failover_config=FailoverConfig(
            suspect_ticks=2, confirm_ticks=1, jitter_ticks=0
        ),
    )
    rooms = {}
    for j in range(4):
        d = Y.Doc(gc=False)
        d.client_id = 100 + j
        g = f"room-{j}"
        rooms[g] = d
        for step in range(6):
            sv = encode_state_vector(d)
            d.get_text("text").insert(0, f"{j}:{step} ")
            fleet.receive_update(g, encode_state_as_update(d, sv))
    fleet.flush()
    fleet.tick()
    victim = fleet.owner_of("room-0")
    fleet.kill_shard(victim)
    for _ in range(16):
        fleet.tick()
        if victim in fleet._down:
            break
    else:
        raise AssertionError("victim never convicted")
    for g, d in rooms.items():
        ref = Y.merge_updates([encode_state_as_update(d)])
        assert Y.merge_updates([fleet.encode_state_as_update(g)]) == ref
    d = rooms["room-0"]
    sv = encode_state_vector(d)
    d.get_text("text").insert(0, "after! ")
    fleet.receive_update("room-0", encode_state_as_update(d, sv))
    assert fleet.text("room-0") == d.get_text("text").to_string()


# -- satellite 6: monotone runs reuse the sorted segment ----------------------


def _snapshot_ops() -> int:
    return kernel_profiler().host_op_stats().get(
        "plan_snapshot", {"count": 0}
    )["count"]


def test_monotone_prepend_skips_snapshot_rebuild():
    """A pure head-prepend run is one monotone chain: the planner must
    reuse the prior sorted segment instead of re-sorting (rebuilding)
    the whole fragment snapshot every flush."""
    d = Y.Doc(gc=False)
    d.client_id = 9
    t = d.get_text("text")
    m = DocMirror("text")
    before = _snapshot_ops()
    for j in range(120):
        sv = encode_state_vector(d)
        t.insert(0, "p")
        m.ingest(encode_state_as_update(d, sv), False)
        if (j + 1) % 12 == 0:
            m.prepare_step()
    assert _snapshot_ops() == before, (
        "head-prepend flushes must not rebuild the fragment snapshot"
    )
    ref = Y.Doc(gc=False)
    apply_update(ref, m.encode_state_as_update())
    assert ref.get_text("text").to_string() == t.to_string()


def test_conflicted_runs_still_build_snapshot():
    """The reuse shortcut must not swallow real anchor lookups: a
    conflicted corpus with many non-chained anchors rebuilds."""
    updates = corpus("interleaved", seed=3, n_ops=120)
    m = DocMirror("text")
    before = _snapshot_ops()
    for j, u in enumerate(updates):
        m.ingest(u, False)
        if (j + 1) % 30 == 0 or j == len(updates) - 1:
            m.prepare_step()
    assert _snapshot_ops() > before


# -- what is left of the device's programs ------------------------------------


def test_device_programs_are_the_writers_and_one_reader():
    """The device holds the tables and computes nothing the host sends it
    to compute: ``ops/kernels.py`` jits the four programs that write the
    tables, ``list_ranks`` that reads them back and (until ``bench.py``
    goes) ``apply_plan_shared``, no other, and the segment pass is the
    host's: ``ops/segment_planner.py`` imports no ``jax``."""
    import ast
    import inspect

    from yjs_tpu.ops import kernels

    def jitted(f):  # a jax.jit program, bare or under ``profiled``
        return hasattr(f, "lower") or hasattr(
            getattr(f, "__wrapped__", None), "lower"
        )

    programs = {
        name for name, f in vars(kernels).items()
        if callable(f) and jitted(f)
    }
    assert programs == {
        "apply_plan2", "apply_plan2_rows", "scatter_rows", "blank_rows",
        "list_ranks", "apply_plan_shared",
    }
    tree = ast.parse(inspect.getsource(kernels))
    defined = {
        n.name for n in tree.body if isinstance(n, ast.FunctionDef)
    }
    assert defined == programs | {
        "apply_lanes", "load_rows", "_put_rows", "_doc_lanes",
    }
    imported = set()
    for node in ast.walk(ast.parse(inspect.getsource(segment_planner))):
        if isinstance(node, ast.Import):
            imported.update(a.name.split(".")[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert "jax" not in imported
