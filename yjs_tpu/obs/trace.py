"""The program's one span API, and its host ring with Chrome-trace export.

``Tracer.span(name)`` is the only way the program opens a span.  Every
span opens a ``jax.profiler.TraceAnnotation(name)``, which the profiler
records while a trace is being captured (``jax.profiler.start_trace``)
on the same clock as the device's ``XLA Ops``, and which costs next to
nothing otherwise.  An enabled tracer also records the span into an
in-memory bounded ring, always available, profiler attached or not,
and exports it as the Chrome ``traceEvents`` JSON that Perfetto /
chrome://tracing load directly.  Instants and flow arrows live in the
ring only.

``YTPU_TRACE_PATH=<file>`` makes every tracer created while the variable
is set register for an atexit dump: all their events merge into one
Chrome-trace JSON at interpreter exit.  ``Tracer.save(path)`` writes one
tracer's trace explicitly.
"""

from __future__ import annotations

import atexit
import contextlib
import json
import os
import threading
import time
from collections import deque

DEFAULT_MAX_EVENTS = 200_000

# The sync handshake's spans, each with the span it opens inside (None:
# its caller's).  ``ytpu.sync.step1`` is one a connection and goes to the
# profiler alone.  tests/test_span_clock.py holds the program to these
# names and the benchmark's ``sync_*`` readers read them.
SYNC_SPANS = {
    "ytpu.sync.step1_batch": None,
    "ytpu.sync.decode": "ytpu.sync.step1_batch",
    "ytpu.sync.encode": "ytpu.sync.step1_batch",
    "ytpu.sync.step1": None,
}

# The inner spans of the leaves the device idles under, each with the
# span it opens inside.  The journal's: ``ytpu.wal.write`` is one a
# record (the file's write and flush) and goes to the profiler alone;
# ``ytpu.wal.fsync`` opens only around an fsync the policy asks for.
# The plan phase's: each at most once a chunk, never once a room
# (``walk``: the dirty rooms and their state vectors; ``keys``: plan
# keys and cache probes, not opened with the cache off; ``stage``:
# ``prepare_many``'s marshalling; ``finish``: ``_finish_prepare``,
# clones and cache inserts), beside ``ytpu.plan.native``.
# tests/test_span_clock.py holds the program to these names and the
# benchmark's ``wal_write_share`` ... ``plan_finish_share`` read them.
LEAF_SPANS = {
    "ytpu.wal.write": "ytpu.wal.append",
    "ytpu.wal.fsync": "ytpu.wal.append",
    "ytpu.plan.walk": "ytpu.plan",
    "ytpu.plan.keys": "ytpu.plan",
    "ytpu.plan.stage": "ytpu.plan",
    "ytpu.plan.finish": "ytpu.plan",
}

# The formatting clean-up after a remote transaction (PR 40,
# ``BatchEngine._format_cleanup``): the look at what each planned room
# brought, once a flush, and the walk of the texts a format item came to
# or left, under a ``ytpu.plan`` span of its own; the deletions it finds
# are planned by a second round of the same flush, under that round's
# own phase spans.  The benchmark's ``cleanup_share`` reads it.  (A table
# of its own: ``tests/bench/test_leaf_spans.py`` holds ``LEAF_SPANS`` to
# PR 38's six.)
FORMAT_SPANS = {
    "ytpu.plan.cleanup": "ytpu.plan",
}

# A recovery (``TpuProvider.recover``, ``persistence.replay_wal``), each
# with the span it opens inside.  ``ytpu.recover`` and its ``construct``
# (the provider's construction and the WAL's opening) are once a
# recovery and go to the profiler alone: the ring is the new provider's,
# which does not exist when they open.  ``read`` (a file's read, record
# decode and CRC), ``validate`` (``validate_updates`` of a file's
# update and snapshot records: one native structural walk) and
# ``queue`` (``doc_id``, ``queue_update``, releases and the other record
# kinds, in the log's order) are once a file, never once a record; the
# closing flush opens its own spans.  tests/test_span_clock.py holds the
# program to these names and the benchmark's ``recover_*_share`` read
# them.
RECOVER_SPANS = {
    "ytpu.recover": None,
    "ytpu.recover.construct": "ytpu.recover",
    "ytpu.recover.read": "ytpu.recover",
    "ytpu.recover.validate": "ytpu.recover",
    "ytpu.recover.queue": "ytpu.recover",
}

# The pack phase by write path (PR 46), each at most once a chunk inside
# that chunk's ``ytpu.pack``: ``rows`` stages the rooms loaded whole into
# empty slots as row blocks (``BatchEngine._stage_row_loads``), ``lanes``
# sizes, keys and packs the element lanes of the rooms that held rows
# (``_covering_key``, ``pack_apply_lanes``).  A chunk that holds only one
# kind of room opens only that span; what is left of ``ytpu.pack`` is the
# capacity look and the staging-slot wait.  tests/test_span_clock.py
# holds the program to these names and the benchmark's
# ``pack_lanes_share`` reads ``ytpu.pack.lanes``.
PACK_SPANS = {
    "ytpu.pack.rows": "ytpu.pack",
    "ytpu.pack.lanes": "ytpu.pack",
}

_NO_SPAN = contextlib.nullcontext()


def no_span(name: str, _ring: bool = True, **args):
    """``Tracer.span`` for code that was handed no tracer (a journal or
    a ``prepare_many`` of a test's own): a context that records nothing."""
    return _NO_SPAN


class _Span:
    """One span on both clocks: the profiler's annotation around one
    complete ("X") ring event."""

    __slots__ = ("_tracer", "_name", "_args", "_t0", "_ann")

    def __init__(self, tracer, name, args, ann):
        self._tracer = tracer
        self._name = name
        self._args = args
        self._ann = ann

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def note(self, **args) -> None:
        """Arguments the ring record takes that the body learns only
        after the span has opened."""
        self._args = {**(self._args or {}), **args}

    def unring(self) -> None:
        """Keep this span out of the ring after all: the profiler's
        annotation still closes at the body's end."""
        self._tracer = None

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        tr = self._tracer
        if tr is not None:
            tr._events.append((
                self._name,
                "X",
                (self._t0 - tr._t0) * 1e6,
                (t1 - self._t0) * 1e6,
                threading.get_ident(),
                self._args,
                None,
            ))
        self._ann.__exit__(exc_type, exc, tb)
        return False


class Tracer:
    """Bounded in-memory span/event recorder (oldest events evicted)."""

    def __init__(self, enabled: bool = True, max_events: int | None = None):
        # imported here, not with the module: yjs_tpu.obs stays
        # importable (sessions, the lint, the CLIs) without loading jax
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        self.enabled = enabled
        if max_events is None:
            try:
                max_events = int(
                    os.environ.get("YTPU_TRACE_EVENTS", DEFAULT_MAX_EVENTS)
                )
            except ValueError:
                max_events = DEFAULT_MAX_EVENTS
        # (name, ph, ts_us, dur_us, tid, args, flow_id) tuples
        self._events: deque = deque(maxlen=max(16, max_events))
        self._t0 = time.perf_counter()
        self.pid = os.getpid()
        self.process_name = "ytpu"
        self._thread_names: dict[int, str] = {}
        if enabled and os.environ.get("YTPU_TRACE_PATH"):
            _register_for_exit_dump(self)

    def span(self, name: str, _ring: bool = True, **args):
        """Context manager around one span's body.  The profiler sees
        the span under its bare ``name`` whenever a trace is being
        captured, enabled tracer or not; ``args`` go to the ring.
        ``_ring=False`` keeps a per-update child span out of the ring,
        which already takes that update's span, flow start and journal
        record (costs measured: PERF.md 6, PR 25).  What ``with ... as``
        binds is a ``_Span`` exactly when the tracer is enabled and
        ``_ring`` is left on: its body may still ``note`` arguments or
        ``unring`` it."""
        ann = self._annotation(name)
        if not (_ring and self.enabled):
            return ann
        return _Span(self, name, args or None, ann)

    def instant(self, name: str, **args) -> None:
        """Record a zero-duration marker (demotion, pool event, ...)."""
        if not self.enabled:
            return
        self._events.append((
            name,
            "i",
            (time.perf_counter() - self._t0) * 1e6,
            0.0,
            threading.get_ident(),
            args or None,
            None,
        ))

    def flow_start(self, name: str, flow_id: int, **args) -> None:
        """Open a flow arrow (Perfetto ``ph="s"``): call inside the span
        the arrow should leave from (e.g. a provider receive span)."""
        self._flow(name, "s", flow_id, args)

    def flow_end(self, name: str, flow_id: int, **args) -> None:
        """Close a flow arrow (``ph="f"``, ``bp="e"`` so it binds to the
        enclosing slice): call inside the span the arrow lands on (the
        flush that applied the update)."""
        self._flow(name, "f", flow_id, args)

    def _flow(self, name, ph, flow_id, args) -> None:
        if not self.enabled:
            return
        self._events.append((
            name,
            ph,
            (time.perf_counter() - self._t0) * 1e6,
            0.0,
            threading.get_ident(),
            args or None,
            int(flow_id),
        ))

    def name_thread(self, name: str) -> None:
        """Label the calling thread in exported traces (a ``thread_name``
        metadata event; unnamed threads render as ``host-<tid>``)."""
        self._thread_names[threading.get_ident()] = name

    def __len__(self) -> int:
        return len(self._events)

    def clear(self) -> None:
        self._events.clear()

    def trace_events(self) -> list[dict]:
        """Chrome ``traceEvents`` list: ``pid``/``tid`` metadata ("M")
        events first, then recorded events sorted by timestamp."""
        if not self._events:
            return []
        out = []
        tids = sorted({e[4] for e in self._events})
        meta = [{
            "name": "process_name", "ph": "M", "ts": 0.0,
            "pid": self.pid, "tid": tids[0], "cat": "__metadata",
            "args": {"name": self.process_name},
        }]
        for tid in tids:
            meta.append({
                "name": "thread_name", "ph": "M", "ts": 0.0,
                "pid": self.pid, "tid": tid, "cat": "__metadata",
                "args": {
                    "name": self._thread_names.get(tid, f"host-{tid}")
                },
            })
        for name, ph, ts, dur, tid, args, flow_id in sorted(
            self._events, key=lambda e: e[2]
        ):
            ev = {
                "name": name,
                "ph": ph,
                "ts": ts,
                "pid": self.pid,
                "tid": tid,
                "cat": "ytpu",
            }
            if ph == "X":
                ev["dur"] = dur
            elif ph == "i":  # instant events: thread scope
                ev["s"] = "t"
            if flow_id is not None:
                ev["id"] = flow_id
            if ph == "f":
                # bind the arrow to the ENCLOSING slice, not the next one
                ev["bp"] = "e"
            if args:
                ev["args"] = args
            out.append(ev)
        return meta + out

    def chrome_trace(self) -> dict:
        """The full Chrome-trace JSON object (loadable by Perfetto)."""
        return {
            "traceEvents": self.trace_events(),
            "displayTimeUnit": "ms",
        }

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path


# -- YTPU_TRACE_PATH atexit dump --------------------------------------------

_EXIT_TRACERS: list[Tracer] = []
_EXIT_REGISTERED = False


def _register_for_exit_dump(tracer: Tracer) -> None:
    global _EXIT_REGISTERED
    _EXIT_TRACERS.append(tracer)
    if not _EXIT_REGISTERED:
        atexit.register(_dump_exit_traces)
        _EXIT_REGISTERED = True


def _dump_exit_traces() -> None:
    path = os.environ.get("YTPU_TRACE_PATH")
    if not path or not _EXIT_TRACERS:
        return
    events: list[dict] = []
    for tr in _EXIT_TRACERS:
        events.extend(tr.trace_events())
    events.sort(key=lambda e: e["ts"])
    try:
        with open(path, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    except OSError:
        pass  # tracing must never take the process down at exit
