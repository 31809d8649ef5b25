"""One span API on the profiler's clock (``yjs_tpu/obs/trace.py``).

A small ``TpuProvider`` with a WAL takes updates and flushes, with a
compaction and an update-log fold forced, under ``jax.profiler``.  What
the benchmark's ``trace_reduce.read_xplane`` reads back holds every span
of the program's contract (``tests/bench/data/spans_synthetic.json``,
which the per-layer readers are held to as well, and the leaves' inner
spans of ``obs.trace.LEAF_SPANS``) under its bare name, each child
inside its parent on one thread.  With the profiler off the
ring holds what it held before the two systems met; under
``YTPU_OBS_DISABLED=1`` it holds nothing and the profiler still sees
every span.
"""

import collections
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:  # the benchmark's reader, as tests/bench does
    sys.path.insert(0, str(ROOT))

import yjs_tpu as Y
from yjs_tpu.admission import AdmissionConfig, AdmissionRejected
from yjs_tpu.obs.trace import (
    FORMAT_SPANS, LEAF_SPANS, PACK_SPANS, RECOVER_SPANS,
)
from yjs_tpu.persistence import WalConfig
from yjs_tpu.provider import TpuProvider

jax = pytest.importorskip("jax")

from benchmarks import trace_reduce  # noqa: E402

PARENTS = {
    **json.loads(
        (ROOT / "tests" / "bench" / "data" / "spans_synthetic.json").read_text()
    )["parents"],
    **LEAF_SPANS,
    # the look of the formatting clean-up, once a flush (PR 40)
    **FORMAT_SPANS,
    # the pack phase by write path, at most once a chunk (PR 46)
    **PACK_SPANS,
}
# spans the ring held before this PR (the journal's record of an append
# among them, now a span the journal opens itself), spans it holds from
# this PR on (one a flush), and the per-update child it must never hold
RING_BEFORE = {
    "ytpu.provider.receive_update", "ytpu.provider.flush", "ytpu.flush",
    "ytpu.compact", "ytpu.plan", "ytpu.pack", "ytpu.dispatch", "ytpu.emit",
    "ytpu.wal.append",
}
PROFILER_ONLY = {"ytpu.slo.receive", "ytpu.wal.write"}
PER_UPDATE = PROFILER_ONLY | {
    "ytpu.wal.append", "ytpu.provider.receive_update",
}
# the plan phase's steps around the native call: once a flush that
# plans (one chunk, one cold call), never once a room
PLAN_STEPS = {
    n for n, p in {**LEAF_SPANS, **FORMAT_SPANS}.items() if p == "ytpu.plan"
}
RING_NEW = set(PARENTS) - RING_BEFORE - PROFILER_ONLY - PLAN_STEPS - {
    "ytpu.wal.fsync",
}
FLUSH_EVERY = 10
FSYNC_EVERY = 16


def keystrokes(n: int, client: int) -> list[bytes]:
    """One update a keystroke, as a y-websocket client sends them; a
    deletion now and then so a compaction has runs to merge."""
    doc = Y.Doc(gc=False)
    doc.client_id = client
    out: list[bytes] = []
    doc.on("update", lambda u, *_: out.append(u))
    text = doc.get_text("text")
    for k in range(n):
        text.insert(len(str(text)), "ab" if k % 3 else "c")
        if k % 5 == 4:
            text.delete(0, 1)
    return out


def drive(prov, updates) -> int:
    """Every update into one room, a flush every ten and at the end:
    more than 64 log entries (a fold) and a table that doubles (a
    compaction).  Returns the number of flushes."""
    flushes = 0
    for k, u in enumerate(updates):
        assert prov.receive_update("room", u)
        if k % FLUSH_EVERY == FLUSH_EVERY - 1:
            prov.flush()
            flushes += 1
    prov.flush()
    prov.slo_snapshot()  # the burn pass from another caller than visible()
    return flushes + 1


def provider(tmp_path, fsync="interval", **kw):
    prov = TpuProvider(
        4, wal_dir=str(tmp_path / "wal"),
        wal_config=WalConfig(fsync=fsync, fsync_interval=FSYNC_EVERY), **kw,
    )
    prov.engine.compact_min_rows = 8
    prov.on_update(lambda guid, update: None)
    return prov


def traced(tmp_path, run):
    """``run()`` under the profiler, read back as the benchmark reads a
    traced run: ``[plane, line, name, start_ns, duration_ns]``."""
    trace_dir = tmp_path / "trace"
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        out = run()
    finally:
        jax.profiler.stop_trace()
    events = trace_reduce.read_xplane(trace_reduce.find_xplane(trace_dir))
    return out, [e for e in events if e[2].startswith("ytpu.")]


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("span_clock")
    prov = provider(tmp)
    updates = keystrokes(70, 7)
    flushes, events = traced(tmp, lambda: drive(prov, updates))
    assert prov.engine.last_compaction and not prov.engine.fallback
    ring = prov.engine.obs.tracer.trace_events()
    prov.close(checkpoint=False)
    return {
        "events": events, "ring": ring, "updates": len(updates),
        "flushes": flushes,
    }


def test_the_profiler_holds_the_contract_and_nothing_else(run):
    """Bare names: a span whose arguments came back in its name would
    show here as a name outside the contract."""
    assert {e[2] for e in run["events"]} == set(PARENTS)
    assert len({(e[0], e[1]) for e in run["events"]}) == 1  # one thread


@pytest.mark.parametrize("name", sorted(PARENTS))
def test_span_on_the_profilers_clock_inside_its_parent(run, name):
    mine = [e for e in run["events"] if e[2] == name]
    assert mine, f"{name} is not in the device trace"
    if name in PER_UPDATE:
        assert len(mine) == run["updates"]
    elif name == "ytpu.wal.fsync":
        assert len(mine) == run["updates"] // FSYNC_EVERY
    elif name in PLAN_STEPS or name in (
        "ytpu.provider.flush", "ytpu.flush", "ytpu.slo.visible",
        "ytpu.cost.on_flush", "ytpu.compact", "ytpu.emit",
        "ytpu.compact.scan", "ytpu.emit.fold",
    ):
        assert len(mine) == run["flushes"]
    parent = PARENTS[name]
    if parent is None:
        return
    # ytpu.slo.burn also runs for state() and snapshot(), whose caller
    # is not the program's: there it has no parent
    orphans = 1 if name == "ytpu.slo.burn" else 0
    around = [
        (e[3], e[3] + e[4]) for e in run["events"]
        if e[2] == parent and (e[0], e[1]) == (mine[0][0], mine[0][1])
    ]
    outside = [
        e for e in mine
        if not any(a <= e[3] and e[3] + e[4] <= b for a, b in around)
    ]
    assert len(outside) == orphans, (name, parent, outside[:3])


@pytest.mark.parametrize("name", sorted(set(PARENTS) | {"ytpu.convergence"}))
def test_ring_holds_what_it_held_and_the_per_flush_spans(run, name):
    """Profiler on or off the ring is the same code path; ``run`` had it
    on, ``test_profiler_off_ring`` below has it off."""
    check_ring(run["ring"], run["updates"], run["flushes"], name)


def check_ring(ring, updates, flushes, name):
    got = collections.Counter(
        (e["name"], e["ph"]) for e in ring if e["ph"] != "M"
    )
    if name == "ytpu.convergence":  # one arrow an update, both ends
        assert got[(name, "s")] == got[(name, "f")] == updates
    elif name in ("ytpu.provider.receive_update", "ytpu.wal.append"):
        assert got[(name, "X")] == updates  # one an update, as before
    elif name in PROFILER_ONLY:
        assert (name, "X") not in got
    elif name == "ytpu.wal.fsync":
        assert got[(name, "X")] == updates // FSYNC_EVERY
    elif name in ("ytpu.plan", "ytpu.pack", "ytpu.dispatch"):
        assert got[(name, "X")] >= flushes  # one a chunk
    elif name in PLAN_STEPS or name in RING_BEFORE or name in (
        "ytpu.slo.visible", "ytpu.cost.on_flush", "ytpu.compact.scan",
        "ytpu.emit.fold",
    ):
        assert got[(name, "X")] == flushes
    else:
        assert name in RING_NEW and 1 <= got[(name, "X")] <= flushes + 1
    assert {n for n, _ in got} == set(PARENTS) - PROFILER_ONLY | {
        "ytpu.convergence"
    }


def test_profiler_off_ring(tmp_path):
    prov = provider(tmp_path)
    updates = keystrokes(70, 9)
    flushes = drive(prov, updates)
    ring = prov.engine.obs.tracer.trace_events()
    for name in sorted(set(PARENTS) | {"ytpu.convergence"}):
        check_ring(ring, len(updates), flushes, name)
    # nesting in the ring as before: a phase inside ytpu.flush inside
    # ytpu.provider.flush, by the ring's own clock
    spans = [e for e in ring if e["ph"] == "X"]

    def inside(child, parent):
        return any(
            p["name"] == parent and p["ts"] <= child["ts"]
            and child["ts"] + child["dur"] <= p["ts"] + p["dur"]
            for p in spans
        )

    for e in spans:
        parent = PARENTS.get(e["name"])
        if parent and e["name"] != "ytpu.slo.burn":
            assert inside(e, parent), e["name"]
    # the journal's span keeps the argument its record had
    assert all(
        e["args"] == {"kind": "update"} for e in spans
        if e["name"] == "ytpu.wal.append"
    )
    # the phase spans carry no argument dicts any more; the receive span
    # keeps its guid
    assert all(
        "args" not in e for e in spans if e["name"] in
        ("ytpu.plan", "ytpu.pack", "ytpu.dispatch")
    )
    assert all(
        e["args"]["guid"] == "room" for e in spans
        if e["name"] == "ytpu.provider.receive_update"
    )
    prov.close(checkpoint=False)


def test_obs_disabled_empties_the_ring_and_keeps_the_profiler(
    tmp_path, monkeypatch
):
    monkeypatch.setenv("YTPU_OBS_DISABLED", "1")
    prov = provider(tmp_path)
    assert not prov.engine.obs.tracer.enabled
    updates = keystrokes(70, 11)
    _, events = traced(tmp_path, lambda: drive(prov, updates))
    assert prov.engine.obs.tracer.trace_events() == []
    # the tracker is off with the registry and returns before its two
    # passes; everything else reaches the profiler as with obs on
    assert {e[2] for e in events} == set(PARENTS) - {
        "ytpu.slo.visible", "ytpu.slo.burn",
    }
    prov.close(checkpoint=False)


def test_queued_update_is_spanned_for_the_profiler_only(tmp_path):
    """The admission queue's branch of receive_update: its span reaches
    the profiler and stays out of the ring, as that branch always did;
    the journal's span is the journal's, whichever branch appends."""
    prov = provider(tmp_path, admission_config=AdmissionConfig(
        enabled=True, tenant_rate=0.0, tenant_burst=1, doc_rate=0.0,
        doc_burst=1, queue_max=64,
    ))
    updates = keystrokes(3, 13)[:3]

    def go():
        for u in updates:
            assert prov.receive_update("room", u)
        assert prov.admission.snapshot()["queued"] == 2

    _, events = traced(tmp_path, go)
    names = collections.Counter(e[2] for e in events)
    assert names["ytpu.provider.receive_update"] == 3
    assert names["ytpu.wal.append"] == 3
    assert names["ytpu.slo.receive"] == 1  # the tracker waits for the drain
    ring = collections.Counter(
        e["name"] for e in prov.engine.obs.tracer.trace_events()
    )
    assert ring["ytpu.provider.receive_update"] == 1
    assert ring["ytpu.wal.append"] == 3
    prov.close(checkpoint=False)


def test_tracker_without_a_tracer_spans_on_the_one_it_is_handed():
    """The session and supervisor trackers are built without a tracer
    and handed one at ``visible``: both passes open their span on it."""
    from yjs_tpu.obs import MetricsRegistry, Tracer
    from yjs_tpu.obs.slo import ConvergenceTracker

    tracker, tracer = ConvergenceTracker(MetricsRegistry()), Tracer()
    key = tracker.receive(keystrokes(1, 15)[0])
    tracker.integrated(key)
    assert tracker.visible(tracer=tracer) == 1
    assert tracker.visible() == 0 and tracker.state() == "ok"  # no tracer
    assert sorted(
        e["name"] for e in tracer.trace_events() if e["ph"] == "X"
    ) == ["ytpu.slo.burn", "ytpu.slo.visible"]


def test_sync_handshake_spans_on_both_clocks(tmp_path):
    """``handle_sync_step1_batch`` and ``sync_step1`` open the spans of
    ``obs.trace.SYNC_SPANS``: bare names, each inside its parent on one
    thread, the batch's three in the ring and the per-connection one for
    the profiler alone; a bad frame changes none of it."""
    from yjs_tpu.obs.trace import SYNC_SPANS

    prov = provider(tmp_path)
    for u in keystrokes(12, 17):
        assert prov.receive_update("room", u)
    step1 = b"\x00\x01\x00"  # sync step 1 with an empty state vector
    msgs = [("room", step1), ("room", b"\x00\x05\xff"), ("room", step1)]

    def go():
        replies = prov.handle_sync_step1_batch(msgs)
        return replies, prov.sync_step1("room")

    (replies, _frame), events = traced(tmp_path, go)
    assert replies[1] is None and replies[0] == replies[2] is not None
    sync = [e for e in events if e[2].startswith("ytpu.sync.")]
    assert collections.Counter(e[2] for e in sync) == dict.fromkeys(SYNC_SPANS, 1)
    assert len({(e[0], e[1]) for e in sync}) == 1  # one thread
    at = {e[2]: (e[3], e[3] + e[4]) for e in sync}
    for name, parent in SYNC_SPANS.items():
        if parent is not None:
            assert at[parent][0] <= at[name][0] and at[name][1] <= at[parent][1]
    assert at["ytpu.sync.decode"][1] <= at["ytpu.sync.encode"][0]
    # the flush at the batch's head is outside the batch's span
    flushes = [e for e in events if e[2] == "ytpu.provider.flush"]
    assert flushes and all(
        e[3] + e[4] <= at["ytpu.sync.step1_batch"][0] for e in flushes
    )
    ring = collections.Counter(
        e["name"] for e in prov.engine.obs.tracer.trace_events()
        if e["ph"] == "X" and e["name"].startswith("ytpu.sync.")
    )
    assert ring == dict.fromkeys(set(SYNC_SPANS) - {"ytpu.sync.step1"}, 1)
    m = prov.last_sync_metrics
    assert (m["n_requests"], m["n_full"], m["n_bad"]) == (3, 2, 1)
    prov.close(checkpoint=False)


@pytest.fixture(scope="module")
def recovery(tmp_path_factory):
    """A log of three segments and a torn last record, recovered under
    the profiler through ``backend="device"``."""
    import shutil

    from yjs_tpu.persistence.records import KIND_UPDATE, encode_record

    tmp = tmp_path_factory.mktemp("recover_spans")
    prov = TpuProvider(
        4, wal_dir=str(tmp / "wal"),
        wal_config=WalConfig(
            fsync="interval", fsync_interval=FSYNC_EVERY, segment_bytes=1024
        ),
    )
    updates = keystrokes(70, 19)
    drive(prov, updates)
    crashed = tmp / "crashed"
    shutil.copytree(tmp / "wal", crashed)  # no close(): the process died
    files = sorted(crashed.glob("wal-*.log"))
    with open(files[-1], "ab") as f:
        torn = encode_record(KIND_UPDATE, "room", updates[0])
        f.write(torn[: len(torn) // 2])

    def go():
        return TpuProvider.recover(str(crashed), n_docs=4, backend="device")

    new, events = traced(tmp, go)
    stats = dict(new.last_recovery)
    ring = new.engine.obs.tracer.trace_events()
    same = new.text("room") == prov.text("room")
    new.close(checkpoint=False)
    prov.close(checkpoint=False)
    return {
        "events": events, "ring": ring, "files": len(files), "stats": stats,
        "records": len(updates), "same": same,
    }


def test_a_recovery_counts_its_files_bytes_and_phases(recovery):
    s = recovery["stats"]
    assert recovery["same"] and recovery["files"] >= 3
    assert (s["files"], s["records_applied"], s["torn_truncations"]) == (
        recovery["files"], recovery["records"], 1
    )
    assert s["records_max_a_room"] == recovery["records"]
    assert s["bytes_read"] > 14 * recovery["records"]
    phases = [s[f"t_{p}_s"] for p in ("construct", "read", "validate", "queue", "flush")]
    assert all(t > 0 for t in phases)
    assert sum(phases[1:]) <= s["duration_s"] + 1e-6


@pytest.mark.parametrize("name", sorted(RECOVER_SPANS))
def test_recovery_span_once_a_recovery_or_once_a_file(recovery, name):
    """``obs.trace.RECOVER_SPANS``: bare names on the profiler's clock,
    each inside its parent on one thread; the recovery and its
    construction once, the three passes once a file, none once a
    record; the ring is the new provider's and holds the passes."""
    mine = [e for e in recovery["events"] if e[2] == name]
    once = name in ("ytpu.recover", "ytpu.recover.construct")
    assert len(mine) == (1 if once else recovery["files"]) < recovery["records"]
    parent = RECOVER_SPANS[name]
    ring = [e for e in recovery["ring"] if e["name"] == name and e["ph"] == "X"]
    assert len(ring) == (0 if once else recovery["files"])
    if parent is None:
        # the closing flush is inside the recovery, with its own spans
        (a, b) = (mine[0][3], mine[0][3] + mine[0][4])
        flushes = [e for e in recovery["events"] if e[2] == "ytpu.flush"]
        assert flushes and all(a <= e[3] and e[3] + e[4] <= b for e in flushes)
        return
    (around,) = [e for e in recovery["events"] if e[2] == parent]
    assert (around[0], around[1]) == (mine[0][0], mine[0][1])  # one thread
    assert all(
        around[3] <= e[3] and e[3] + e[4] <= around[3] + around[4] for e in mine
    )


def test_importing_obs_does_not_load_jax():
    """The annotation is jax's, and is imported by the first Tracer:
    sessions, the lint and the CLIs import ``yjs_tpu.obs`` without it."""
    import subprocess

    code = (
        "import sys, yjs_tpu.obs, yjs_tpu.obs.trace, yjs_tpu.sync.session\n"
        "assert 'jax' not in sys.modules, 'jax loaded'\n"
        "yjs_tpu.obs.trace.Tracer(enabled=False)\n"
        "assert 'jax.profiler' in sys.modules\n"
    )
    done = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=120,
    )
    assert done.returncode == 0, done.stderr[-2000:]


def test_staging_spans_open_once_a_block(tmp_path):
    """A compaction stages its rooms in width classes, one block a class:
    ``ytpu.compact.alloc``, ``.rebuild``, ``.put`` and ``.scatter`` each
    open once a block, inside the flush's one ``ytpu.compact``, and
    ``rows_staged_blocks`` counts the blocks."""
    prov = provider(tmp_path)
    prov.engine.compact_min_rows = 1 << 30  # no compaction but the one asked for
    # a short room and one more than an octave wider: two width classes
    last = {}
    for guid, n, client in (("short", 30, 11), ("long", 200, 12)):
        *typed, last[guid] = keystrokes(n, client)
        for u in typed:
            assert prov.receive_update(guid, u)
    prov.flush()
    eng = prov.engine
    docs = [prov.doc_id("short"), prov.doc_id("long")]
    assert eng.mirrors[docs[0]].n_rows <= 64 and eng.mirrors[docs[1]].n_rows > 128
    before = len(eng.obs.tracer.trace_events())
    eng.compact_min_rows = 8
    for guid, u in last.items():  # the next keystrokes' flush compacts both
        assert prov.receive_update(guid, u)
    prov.flush()
    assert len(eng.last_compaction) == 2
    assert eng.last_flush_metrics["rows_staged_blocks"] == 2
    ring = eng.obs.tracer.trace_events()[before:]
    got = collections.Counter(e["name"] for e in ring if e["ph"] == "X")
    assert got["ytpu.compact"] == 1
    for name in ("alloc", "rebuild", "put", "scatter"):
        assert got[f"ytpu.compact.{name}"] == 2
    prov.close(checkpoint=False)


def ring_spans(prov, since=0):
    return [
        e for e in prov.engine.obs.tracer.trace_events()[since:]
        if e["ph"] == "X"
    ]


def inside(child, parents):
    return any(
        p["ts"] <= child["ts"]
        and child["ts"] + child["dur"] <= p["ts"] + p["dur"]
        for p in parents
    )


@pytest.mark.parametrize("policy", ["always", "interval", "never"])
def test_fsync_span_opens_only_when_the_journal_fsyncs(tmp_path, policy):
    """``ytpu.wal.fsync`` is the ``os.fsync`` the policy asks for and
    nothing else: as many spans as ``ytpu_wal_fsyncs_total`` counted,
    each inside the append that paid it; the record's write never
    reaches the ring."""
    prov = provider(tmp_path, fsync=policy)
    counted = prov.wal.metrics.fsyncs.value
    updates = keystrokes(40, 19)
    fsyncs = {
        "always": len(updates), "interval": len(updates) // FSYNC_EVERY,
        "never": 0,
    }[policy]
    for u in updates:
        assert prov.receive_update("room", u)
    spans = ring_spans(prov)
    appends = [e for e in spans if e["name"] == "ytpu.wal.append"]
    synced = [e for e in spans if e["name"] == "ytpu.wal.fsync"]
    assert len(appends) == len(updates) and len(synced) == fsyncs
    assert prov.wal.metrics.fsyncs.value - counted == fsyncs
    assert all(inside(e, appends) and "args" not in e for e in synced)
    assert not [e for e in spans if e["name"] == "ytpu.wal.write"]
    prov.close(checkpoint=False)


class Recorded:
    """A ``TraceAnnotation`` that writes its enter and exit into a
    list: what the profiler would see, in order, without a profiler."""

    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("open", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("close", self.name))
        return False


@pytest.mark.parametrize("branch", ["admit", "queue", "reject"])
def test_receive_update_is_one_span_from_its_first_statement(
    tmp_path, monkeypatch, branch
):
    """The admission gate, the trace context's mint and ``doc_id`` run
    inside ``ytpu.provider.receive_update`` in the branch that
    integrates and in the one that queues; an update the gate rejects
    (its tenant's bucket and the queue are full) closes the span on its
    way out and changes no state."""
    prov = provider(tmp_path, admission_config=AdmissionConfig(
        enabled=True, tenant_rate=0.0, tenant_burst=1, doc_rate=0.0,
        doc_burst=1, queue_max=1,
    ))
    updates = keystrokes(3, 21)
    sent = {"admit": 0, "queue": 1, "reject": 2}[branch]
    for u in updates[:sent]:
        assert prov.receive_update("t/room", u)
    log = Recorded.log = []
    monkeypatch.setattr(prov.engine.obs.tracer, "_annotation", Recorded)
    for obj, attr in (
        (prov.admission, "admit_update"), (prov, "_trace_ingress"),
        (prov, "doc_id"),
    ):
        def wrapped(*a, _real=getattr(obj, attr), _attr=attr, **kw):
            log.append(("call", _attr))
            return _real(*a, **kw)
        monkeypatch.setattr(obj, attr, wrapped)
    before = (
        len(prov.engine.obs.tracer), dict(prov._guids),
        prov.wal.metrics.bytes.value, prov._m_updates_rx.value,
        prov.admission.snapshot()["queued"], prov._dirty,
    )
    if branch == "reject":
        with pytest.raises(AdmissionRejected):
            prov.receive_update("t/other", updates[sent])
        assert log == [
            ("open", "ytpu.provider.receive_update"),
            ("call", "admit_update"),
            ("close", "ytpu.provider.receive_update"),
        ]
        # no slot, no journal record, no counter, nothing queued; the
        # ring's record of the refused call is all that is new
        assert (len(prov.engine.obs.tracer) - 1, *before[1:]) == before
        assert ring_spans(prov)[-1]["args"] == {"guid": "t/other"}
    else:
        assert prov.receive_update("t/room", updates[sent])
        assert log[0] == ("open", "ytpu.provider.receive_update")
        assert log[-1] == ("close", "ytpu.provider.receive_update")
        calls = [what for kind, what in log if kind == "call"]
        assert calls == ["admit_update", "_trace_ingress"] + (
            ["doc_id"] if branch == "admit" else []
        )
        mine = [
            e for e in ring_spans(prov)[before[0]:]
            if e["name"] == "ytpu.provider.receive_update"
        ]
        assert len(mine) == (1 if branch == "admit" else 0)
    prov.close(checkpoint=False)


@pytest.mark.parametrize("sample, traced", [("1", True), ("0", False)])
def test_receive_span_takes_its_trace_after_it_opens(
    tmp_path, monkeypatch, sample, traced
):
    """The ring record keeps ``guid`` and, for a sampled context, the
    ``trace`` that ``scripts/check_trace.py`` follows to visibility."""
    from yjs_tpu.obs.dist import mint_for_update

    monkeypatch.setenv("YTPU_TRACE_SAMPLE", sample)
    prov = provider(tmp_path)
    (u,) = keystrokes(1, 23)
    assert prov.receive_update("room", u)
    (rec,) = [
        e for e in ring_spans(prov)
        if e["name"] == "ytpu.provider.receive_update"
    ]
    want = {"guid": "room"}
    if traced:
        want["trace"] = mint_for_update(u).trace_hex
    assert rec["args"] == want
    prov.close(checkpoint=False)


@pytest.mark.parametrize("chunk, cache, native, want", [
    # walk, keys, stage, native, finish
    ("256", "1", True, (1, 1, 1, 1, 1)),
    ("2", "1", True, (1, 3, 3, 3, 3)),
    ("256", "0", True, (1, 0, 1, 1, 1)),
    ("256", "1", False, (1, 0, 0, 0, 0)),
])
def test_plan_steps_open_once_a_chunk_never_once_a_room(
    tmp_path, monkeypatch, chunk, cache, native, want
):
    """Six rooms in one flush: ``ytpu.plan.walk`` once, the steps around
    the native call once a chunk, ``ytpu.plan.keys`` not at all with the
    plan cache off; the Python planner's lane walks and plans in one
    loop, and opens ``ytpu.plan.walk`` around it."""
    monkeypatch.setenv("YTPU_FLUSH_CHUNK", chunk)
    monkeypatch.setenv("YTPU_PLAN_CACHE", cache)
    if not native:
        monkeypatch.setenv("YTPU_NO_NATIVE_PLAN", "1")
    prov = TpuProvider(8)
    prov.on_update(lambda guid, update: None)
    for k in range(6):
        for u in keystrokes(4, 30 + k):
            assert prov.receive_update(f"room{k}", u)
    since = len(prov.engine.obs.tracer.trace_events())
    prov.flush()
    spans = ring_spans(prov, since)
    plans = [e for e in spans if e["name"] == "ytpu.plan"]
    got = collections.Counter(e["name"] for e in spans)
    steps = ("walk", "keys", "stage", "native", "finish")
    assert tuple(got[f"ytpu.plan.{s}"] for s in steps) == want
    assert all(
        inside(e, plans) for e in spans
        if e["name"].startswith("ytpu.plan.")
    )
    m = prov.engine.last_flush_metrics
    assert m["n_docs_flushed"] == 6 and m["rooms_dirty"] == 6
    prov.close(checkpoint=False)
