"""NativeMirror: DocMirror's interface served by the C++ plan core.

The flush hot path (ingest -> prepare_step -> lane pack) runs entirely
inside yjs_tpu/native/plancore.cpp — the per-item Python interpreter cost
that dominated the distinct-doc benchmark drops to one ctypes call per flush.  Everything *outside* the hot
path — exports, wire encodes, event payloads — is served by lazily syncing
the C++ columns into a shadow :class:`DocMirror` and delegating to its
(pure-read) methods, so the two implementations cannot drift in behavior:
the shadow IS the reference implementation operating on the same data.

Scope fallbacks keep semantics identical to the Python mirror:
- subdocuments (ContentDoc) raise :class:`UnsupportedUpdate`, demoting the
  doc to the CPU core exactly like the Python path (engine policy seam);
- payloads the native scanner will not carry (legacy ContentJSON inside a
  V2 update) also raise UnsupportedUpdate — the engine's CPU fallback
  serves them;
- malformed updates raise the same decode errors as the Python path
  (re-validated through decode_update_refs so the error type matches).
"""

from __future__ import annotations

import ctypes
import os
import time

import numpy as np

from ..lib0.decoding import Decoder
from ..lib0 import decoding
from ..lib0.u16 import utf8_decode_u16
from ..obs.trace import no_span
from ..native import (
    SRC_ANYS,
    SRC_DELETED,
    SRC_FRAMED,
    SRC_JSONS,
    SRC_NONE,
    SRC_UTF8,
    SRC_V2LAZY,
    load,
)
from .columns import (
    NULL,
    DocMirror,
    LazyContent,
    LazyContentV2,
    UnsupportedUpdate,
    decode_update_refs,
)
from . import plan_cache as _pc

_u8p = ctypes.POINTER(ctypes.c_uint8)
_i32p = ctypes.POINTER(ctypes.c_int32)
_i64p = ctypes.POINTER(ctypes.c_int64)


def native_plan_available() -> bool:
    # env opt-out first: load() may trigger the g++ build
    return not os.environ.get("YTPU_NO_NATIVE_PLAN") and load() is not None


def plan_segment_stats() -> tuple[int, int]:
    """Cumulative (chain-run adoptions, fragment-search lookups) across
    every native prepare in the process; callers diff around a flush.
    (0, 0) when the core is unavailable."""
    if not native_plan_available():
        return (0, 0)
    out = np.zeros(2, np.int64)
    load().ymx_plan_segment_stats(_p64(out))
    return (int(out[0]), int(out[1]))


def _p64(a: np.ndarray):
    return a.ctypes.data_as(_i64p)


def _p32(a: np.ndarray):
    return a.ctypes.data_as(_i32p)


class NativePlan:
    """Array-backed step plan (the C++ twin of :class:`StepPlan`).

    ``splits``/``sched``/``delete_rows`` are numpy arrays (use ``len()``,
    not truthiness); ``applied_ds`` is a plain list of tuples, fetched
    when someone reads it (events, the per-room encode: the flush's
    batched encode reads the ranges in place, ``encode_steps_many``)."""

    def __init__(self, lib, h, counts, mirror):
        self.n_rows, n_splits, n_sched = (int(x) for x in counts[:3])
        n_del, self._n_ads = int(counts[6]), int(counts[7])
        n_links, n_heads = int(counts[12]), int(counts[13])
        # full counts row retained for the plan cache (insert after a
        # cold per-doc prepare needs it)
        self.counts = np.array(counts, np.int64, copy=True)
        self._lib, self._h = lib, h
        # staleness guard for lazy sections: the C++ plan buffers are
        # overwritten by the mirror's next prepare
        self._mirror = mirror
        self._seq = int(counts[15])
        # the mirror held no row before this step (plancore.cpp plan_shape)
        self.from_empty = bool(int(counts[14]) & 2)
        self._n_sched = n_sched
        # hot-path sections fetched eagerly (the bulk apply + split count)
        self.splits = np.empty((n_splits, 2), np.int64)
        self.delete_rows = np.empty(n_del, np.int64)
        self.link_rows = np.empty(n_links, np.int64)
        self.link_vals = np.empty(n_links, np.int64)
        self.head_segs = np.empty(n_heads, np.int64)
        self.head_vals = np.empty(n_heads, np.int64)
        if n_splits:
            lib.ymx_plan_splits(h, _p64(self.splits))
        if n_del:
            lib.ymx_plan_deletes(h, _p64(self.delete_rows))
        if n_links:
            lib.ymx_plan_links(h, _p64(self.link_rows), _p64(self.link_vals))
        if n_heads:
            lib.ymx_plan_heads(h, _p64(self.head_segs), _p64(self.head_vals))
        self._sched = self._applied = None

    def _fresh(self):
        if self._seq != self._mirror._plan_seq:
            raise RuntimeError(
                "stale NativePlan: the mirror ran another prepare_step"
            )

    @property
    def sched(self):
        if self._sched is None:
            self._fresh()
            self._sched = np.empty((self._n_sched, 4), np.int64)
            if self._n_sched:
                self._lib.ymx_plan_sched(self._h, _p64(self._sched))
        return self._sched

    @property
    def applied_ds(self):
        if self._applied is None:
            self._fresh()
            ads = np.empty((self._n_ads, 3), np.int64)
            if self._n_ads:
                self._lib.ymx_plan_applied_ds(self._h, _p64(ads))
            self._applied = list(map(tuple, ads.tolist()))
        return self._applied


def _empty_v2_update() -> bytes:
    """The no-novelty V2 container (feature byte + nine empty streams +
    0-group 0-DS rest) — the V2 analogue of the V1 b"\\x00\\x00"."""
    from ..coding import UpdateEncoderV2
    from ..lib0 import encoding as lib0enc

    e = UpdateEncoderV2()
    lib0enc.write_var_uint(e.rest_encoder, 0)
    lib0enc.write_var_uint(e.rest_encoder, 0)
    return e.to_bytes()


_EMPTY_V2 = _empty_v2_update()


class NativeMirror:
    """Drop-in DocMirror replacement backed by the native plan core."""

    def __init__(self, root_name: str = "text"):
        lib = load()
        if lib is None:
            raise RuntimeError("native plan core unavailable")
        self._lib = lib
        self._h = lib.ymx_new()
        self.root_name = root_name
        self._incoming: list[tuple[bytes, bool]] = []
        # buf id -> (bytes, pinned nparray view) keeping pointers stable
        self._py_bufs: dict[int, tuple[bytes, np.ndarray]] = {}
        self._realized: dict[int, object] = {}
        self._py = DocMirror(root_name)
        # spill/encode paths realize through the descriptor columns
        self._py.realized_content = self.realized_content
        self._synced_gen = -1
        # mirrors counts[8] of the last prepare: lets the engine skip the
        # per-doc ymx_has_pending call when binning flush work
        self._had_pending = False
        # plan-cache digest chain (ISSUE 9): advances on every successful
        # prepare / deterministic compact, poisons on anything else
        self.plan_frontier = _pc.seed_frontier(root_name)
        # extra per-row source columns the shadow DocMirror has no slot for
        self._src_ofs2: list[int] = []
        self._src_end2: list[int] = []
        self._src_count: list[int] = []
        self._src_v2: list[int] = []
        # bytes the last encode_diff_update allocated for its output
        self.encode_buffer_bytes = 0

    def __del__(self):
        lib = getattr(self, "_lib", None)
        h = getattr(self, "_h", None)
        if lib is not None and h:
            lib.ymx_free(h)

    # -- hot path -----------------------------------------------------------

    def ingest(self, update: bytes, v2: bool = False) -> None:
        self._incoming.append((update, v2))

    @property
    def _plan_seq(self) -> int:
        """The number of the core's current plan: every prepare and every
        adopted snapshot overwrites the plan buffers and moves it.  A
        plan's own number rides in its counts row (``counts[15]``)."""
        return int(self._lib.ymx_plan_seq(self._h))

    def _stage_bufs(self):
        """Register the staged updates with the core; returns
        (staged, buf_ids, v2_flags) with the facade pins recorded."""
        lib, h = self._lib, self._h
        staged = self._incoming
        n_up = len(staged)
        ids = np.empty(max(1, n_up), np.int64)
        v2s = np.empty(max(1, n_up), np.int64)
        for j, (u, v2) in enumerate(staged):
            arr = np.frombuffer(u, np.uint8)
            bid = lib.ymx_add_buf(
                h, arr.ctypes.data_as(_u8p), ctypes.c_uint64(len(u))
            )
            self._py_bufs[int(bid)] = (u, arr)
            ids[j] = bid
            v2s[j] = 1 if v2 else 0
        return staged, ids, v2s

    def plan_key(self, want_sched: bool = True):
        """Plan-cache key for the staged work: kind + frontier + staged
        content digest + plan-shape flag (the flag changes the cloned
        ``plan`` member, not the integrated state)."""
        return (
            "n",
            self.plan_frontier,
            _pc.staged_digest(self._incoming),
            bool(want_sched),
        )

    def adopt_cached(self, entry) -> np.ndarray:
        """Replay a cached post-prepare snapshot onto this doc's handle
        instead of planning: one deep state clone, then the same
        bookkeeping a real prepare would do.  ``entry`` is anything with
        ``h`` (source handle), ``counts``, ``pins`` and
        ``frontier_after`` — a cache entry or a just-planned leader
        mirror wrapped by the engine."""
        self._lib.ymx_clone_state(self._h, entry.h)
        self._incoming = []
        self._had_pending = bool(entry.counts[8])
        # the clone's borrowed buffer pointers reference the source's
        # pinned update payloads; share the pins to keep them alive
        self._py_bufs = dict(entry.pins)
        self._realized.clear()
        self._synced_gen = -1  # force a full shadow rebuild on next _sync
        self.plan_frontier = entry.frontier_after
        counts = np.array(entry.counts, np.int64, copy=True)
        counts[15] = self._plan_seq  # the clone's number, not the source's
        return counts

    def _finish_prepare(self, rc, staged, ids, counts) -> None:
        """Post-prepare bookkeeping shared by the per-doc and batched
        paths; raises exactly like the old inline prepare_step body."""
        lib, h = self._lib, self._h
        n_up = len(staged)
        self._incoming = []
        self._had_pending = bool(counts[8])
        if rc != 0:
            # the core may have merged a prefix before failing — this
            # state is not a deterministic function of the digest chain,
            # so no other mirror may ever alias it
            self.plan_frontier = _pc.poison_frontier()
            _pc.note_invalidation("plan-error")
        if rc == -9:
            raise UnsupportedUpdate("subdocument (content ref 9)")
        if rc != 0:
            # truly malformed bytes must raise the same error the Python
            # mirror would; anything the Python decoder accepts is a
            # native-scope limitation -> demote like other scope gaps
            try:
                for u, v2 in staged:
                    decode_update_refs(u, v2)
            except Exception:
                # scan-phase failure: nothing merged; unregister the staged
                # buffers so a catch-and-retry loop cannot accumulate pins
                if n_up:
                    first = int(ids[0])
                    lib.ymx_drop_bufs_from(h, first)
                    for j in range(n_up):
                        self._py_bufs.pop(int(ids[j]), None)
                self._incoming = staged
                raise
            raise UnsupportedUpdate(f"native plan: unsupported payload (rc={rc})")
        self._realized.clear()
        self.plan_frontier = _pc.fold(
            self.plan_frontier, b"u", _pc.staged_digest(staged)
        )

    def make_plan(self, counts) -> NativePlan:
        """Wrap the core's current plan (valid until the next prepare)."""
        return NativePlan(self._lib, self._h, counts, self)

    def prepare_step(self) -> NativePlan:
        lib, h = self._lib, self._h
        staged, ids, v2s = self._stage_bufs()
        counts = np.zeros(16, np.int64)
        rc = lib.ymx_prepare(
            h, _p64(ids), _p64(v2s), len(staged), _p64(counts)
        )
        self._finish_prepare(rc, staged, ids, counts)
        return NativePlan(lib, h, counts, self)

    def format_cleanup(self, rows_before: int):
        """``(ids, texts)`` as ``engine._cleanup_room`` gives them, from
        the core's own rows (no Python shadow is built), for the step
        the current plan is of; None where the core cannot say (a format
        item that came V2-framed): the caller walks the shadow."""
        cap = 256
        while True:
            out = np.empty((cap, 3), np.int64)
            texts = np.zeros(1, np.int64)
            n = int(self._lib.ymx_format_cleanup(
                self._h, rows_before, _p64(out), cap, _p64(texts)
            ))
            if n >= 0:
                return list(map(tuple, out[:n].tolist())), int(texts[0])
            if cap >= self.n_rows:
                return None
            cap = self.n_rows

    def content_gen(self) -> int:
        """Monotonic change counter (the C++ core's ``gen``): bumps on
        every integrated mutation AND at the end of every prepare, so
        delete-only flushes are visible to cached consumers."""
        return int(self._lib.ymx_gen(self._h))

    @property
    def n_rows(self) -> int:
        return int(self._lib.ymx_n_rows(self._h))

    @property
    def n_segs(self) -> int:
        return int(self._lib.ymx_n_segs(self._h))

    def has_pending(self) -> bool:
        return bool(self._lib.ymx_has_pending(self._h))

    def host_nbytes(self) -> int:
        """Rough host bytes this mirror holds (warm-tier accounting,
        ISSUE 7): pinned update payloads + a per-row core estimate."""
        return (
            sum(len(u) for u, _arr in self._py_bufs.values())
            + self.n_rows * 96
            + self.n_segs * 48
        )

    def deleted_ratio(self) -> float:
        """Deleted content length / total inserted length — the tier GC
        trigger (ISSUE 7).  Straight from the core's state/DS exports;
        no shadow sync, no device traffic."""
        lib, h = self._lib, self._h
        ns = int(lib.ymx_n_slots(h))
        if not ns:
            return 0.0
        state = np.empty(ns, np.int64)
        lib.ymx_state(h, _p64(state))
        total = int(state.sum())
        if not total:
            return 0.0
        nds = int(lib.ymx_ds_count(h))
        if not nds:
            return 0.0
        ds_slot = np.empty(nds, np.int64)
        ds_clock = np.empty(nds, np.int64)
        ds_len = np.empty(nds, np.int64)
        lib.ymx_ds(h, _p64(ds_slot), _p64(ds_clock), _p64(ds_len))
        return min(1.0, int(ds_len.sum()) / total)

    def pending_depth(self) -> int:
        return int(self._lib.ymx_pending_depth(self._h))

    def state_vector(self) -> dict[int, int]:
        lib, h = self._lib, self._h
        ns = int(lib.ymx_n_slots(h))
        if ns == 0:
            return {}
        clients = np.empty(ns, np.int64)
        state = np.empty(ns, np.int64)
        lib.ymx_clients(h, _p64(clients))
        lib.ymx_state(h, _p64(state))
        return {
            int(c): int(s) for c, s in zip(clients, state) if s > 0
        }

    def encode_state_vector(self) -> bytes:
        """The state vector as a sync step 1 carries it, written by the
        core from its own slots (``DocMirror.state_vector``'s order and
        filter, so the shadow's bytes); the shadow is neither read nor
        rebuilt."""
        cap = 256
        while True:
            out = ctypes.create_string_buffer(cap)
            n = int(self._lib.ymx_encode_state_vector(self._h, out, cap))
            if n <= cap:
                return out.raw[:n]
            cap = n

    def delete_set(self):
        """The doc's derived DeleteSet straight from the core — a cheap
        Snapshot capture (no shadow sync, no device I/O); the DocMirror
        twin is columns.py delete_set()."""
        from ..core import DeleteItem, DeleteSet

        lib, h = self._lib, self._h
        ds = DeleteSet()
        nds = int(lib.ymx_ds_count(h))
        if not nds:
            return ds
        ds_slot = np.empty(nds, np.int64)
        ds_clock = np.empty(nds, np.int64)
        ds_len = np.empty(nds, np.int64)
        lib.ymx_ds(h, _p64(ds_slot), _p64(ds_clock), _p64(ds_len))
        ns = int(lib.ymx_n_slots(h))
        clients = np.empty(max(1, ns), np.int64)
        lib.ymx_clients(h, _p64(clients))
        by_client: dict[int, list[tuple[int, int]]] = {}
        for s, c, ln in zip(
            ds_slot.tolist(), ds_clock.tolist(), ds_len.tolist()
        ):
            by_client.setdefault(int(clients[s]), []).append((c, ln))
        for cl, ranges in by_client.items():
            ds.clients[cl] = [
                DeleteItem(clock, ln)
                for clock, ln in DocMirror._union_ranges(ranges)
            ]
        return ds

    # -- compaction ---------------------------------------------------------

    def rebuild_compacted_self(self, gc: bool):
        """Compact from the mirror's own list state — no device read-back
        (the flush invariant keeps mirror links == device links)."""
        lib, h = self._lib, self._h
        n = self.n_rows
        nseg = self.n_segs
        new_right = np.full(max(1, n), NULL, np.int32)
        new_del = np.zeros(max(1, n), np.uint8)
        new_heads = np.full(max(1, nseg), NULL, np.int32)
        n_new = lib.ymx_compact_self(
            h, int(bool(gc)), _p32(new_right),
            new_del.ctypes.data_as(_u8p), _p32(new_heads),
            len(new_heads),
        )
        self._realized.clear()
        # compaction-from-self is a pure function of state already in
        # the chain: a deterministic fold, so two docs compacted at
        # the same point keep aliasing each other's cache entries
        self.plan_frontier = _pc.fold(
            self.plan_frontier, b"compact-self", b"g" if gc else b"-"
        )
        _pc.note_invalidation("compact")
        return (
            new_right[:n_new],
            new_del[:n_new].astype(bool),
            new_heads,
        )

    @staticmethod
    def compact_changes_many(mirrors, gc: bool) -> np.ndarray:
        """The question a compaction look asks before it rebuilds, of
        every candidate in one native call: ``out[k]`` is True where
        ``mirrors[k].rebuild_compacted_self(gc)`` would merge a row or
        turn a deleted row's content into a tombstone, False where it
        would hand back the rows, deleted bits and heads the room holds
        (``Mirror::compact_changes``: the tests ``compact`` decides
        with).  Reads the mirrors and writes none."""
        n = len(mirrors)
        out = np.zeros(n, np.uint8)
        if n:
            handles = (ctypes.c_void_p * n)(*[m._h for m in mirrors])
            mirrors[0]._lib.ymx_compact_changes_many(
                handles, n, int(bool(gc)), out.ctypes.data_as(_u8p)
            )
        return out.astype(bool)

    # -- native wire encodes -------------------------------------------------

    def encode_diff_update(
        self, target_sv: dict[int, int] | None, ds_ranges=None,
        v2: bool = False,
    ) -> bytes | None:
        """The doc's diff against ``target_sv`` encoded fully natively
        (reference encodeStateAsUpdate, encoding.js:490-526); ``ds_ranges``
        overrides the DS section (the flush-novelty form); ``v2`` selects
        the 9-stream columnar container.  Returns None when the native
        writer cannot serve the selection — for V1 output that is V2-framed
        embed/format/type payloads, for V2 output V1-framed ones, plus any
        Python-realized (spilled) content — and callers fall back to the
        shadow's encode."""
        lib, h = self._lib, self._h
        sv = target_sv or {}
        n_sv = len(sv)
        svc = np.fromiter(sv.keys(), np.int64, n_sv) if n_sv else np.zeros(1, np.int64)
        svk = np.fromiter(sv.values(), np.int64, n_sv) if n_sv else np.zeros(1, np.int64)
        if ds_ranges is None:
            ds = np.zeros(3, np.int64)
            n_ds, override = 0, 0
        else:
            n_ds = len(ds_ranges)
            ds = (
                np.asarray(ds_ranges, np.int64).reshape(-1)
                if n_ds
                else np.zeros(3, np.int64)
            )
            override = 1
        fn = lib.ymx_encode_diff_v2 if v2 else lib.ymx_encode_diff
        cap = int(lib.ymx_encode_bound(h))
        self.encode_buffer_bytes = 0
        for _attempt in range(2):
            out = np.empty(cap, np.uint8)
            self.encode_buffer_bytes += cap
            rc = int(
                fn(
                    h, _p64(svc), _p64(svk), n_sv, _p64(ds), n_ds,
                    override, out.ctypes.data_as(_u8p),
                    ctypes.c_uint64(len(out)),
                )
            )
            if rc < -100:  # overflow: the V2 writer reports the exact
                # size needed (the bound is V1-derived) — retry once with
                # an exact buffer rather than degrading to Python
                cap = -rc
                continue
            if rc < 0:
                return None
            return out[:rc].tobytes()
        return None

    def encode_state_as_update(self, target_sv=None, v2: bool = False) -> bytes:
        u = self.encode_diff_update(target_sv, v2=v2)
        if u is not None:
            return u
        self._sync()
        return DocMirror.encode_state_as_update(self._py, target_sv, v2=v2)

    def encode_step_update(self, pre_sv, plan, v2: bool = False) -> bytes | None:
        u = self.encode_diff_update(pre_sv, ds_ranges=plan.applied_ds, v2=v2)
        if u is not None:
            # a no-novelty update means the flush changed nothing visible —
            # match the None contract (V1: 0 groups + 0 DS clients; the V2
            # container's empty form is longer, compare against it)
            if not v2 and u == b"\x00\x00":
                return None
            if v2 and u == _EMPTY_V2:
                return None
            return u
        self._sync()
        return DocMirror.encode_step_update(self._py, pre_sv, plan, v2=v2)

    # -- content realization -------------------------------------------------

    def realized_content(self, row: int):
        c = self._realized.get(row)
        if c is not None:
            return c
        self._sync()
        py = self._py
        kind = py.row_src_kind[row]
        ref = py.row_content_ref[row]
        if kind == SRC_NONE:
            return None
        buf = py._bufs[py.row_src_buf[row]] if py.row_src_buf[row] >= 0 else b""
        ofs, end = py.row_src_ofs[row], py.row_src_end[row]
        if kind == SRC_DELETED:
            from ..core import ContentDeleted

            c = ContentDeleted(py.row_len[row])
        elif kind == SRC_UTF8:
            from ..core import ContentString

            c = ContentString(utf8_decode_u16(buf[ofs:end]))
        elif kind == SRC_FRAMED:
            c = LazyContent(buf, ofs, ref, end).realize()
        elif kind in (SRC_ANYS, SRC_JSONS):
            # synthesize the V1 framing (varuint count + elements) and use
            # the reference read path so element semantics cannot drift
            from ..lib0 import encoding as lib0enc

            enc = lib0enc.Encoder()
            lib0enc.write_var_uint(enc, self._src_count[row])
            synth = enc.to_bytes() + buf[ofs:end]
            c = LazyContent(synth, 0, ref, len(synth)).realize()
        elif kind == SRC_V2LAZY:
            c = LazyContentV2(
                buf, ref, ofs, end,
                self._src_ofs2[row], self._src_end2[row],
                self._src_count[row],
            ).realize()
        else:  # SRC_SPILL never originates here
            raise AssertionError(f"unexpected src kind {kind}")
        self._realized[row] = c
        return c

    # -- shadow sync + delegation -------------------------------------------

    def _sync(self) -> None:
        lib, h = self._lib, self._h
        gen = int(lib.ymx_gen(h))
        if gen == self._synced_gen:
            return
        py = self._py
        n = self.n_rows
        cols = {k: np.empty(n, np.int64) for k in (
            "slot", "clock", "len", "oslot", "oclock", "rslot", "rclock",
            "is_gc", "countable", "ref", "seg", "src_kind", "src_buf",
            "src_ofs", "src_end", "src_ofs2", "src_end2", "src_count",
            "src_v2", "host_deleted", "lww_deleted",
        )}
        if n:
            lib.ymx_rows(h, 0, *(_p64(cols[k]) for k in cols))
        # numpy-backed shadow columns: the fetch is pure memcpy (no per-row
        # Python boxing), and every DocMirror read path accepts sequence
        # indexing — a 100k-row sync is a few MB of memcpy, not 2M tolist()
        # boxings (r3 review finding)
        py.row_slot = cols["slot"]
        py.row_clock = cols["clock"]
        py.row_len = cols["len"]
        py.row_origin_slot = cols["oslot"]
        py.row_origin_clock = cols["oclock"]
        py.row_right_slot = cols["rslot"]
        py.row_right_clock = cols["rclock"]
        py.row_is_gc = cols["is_gc"]
        py.row_countable = cols["countable"]
        py.row_content = [None] * n
        py.row_content_ref = cols["ref"]
        py.row_seg = cols["seg"]
        py.row_src_kind = cols["src_kind"]
        py.row_src_buf = cols["src_buf"]
        py.row_src_ofs = cols["src_ofs"]
        py.row_src_end = cols["src_end"]
        self._src_ofs2 = cols["src_ofs2"]
        self._src_end2 = cols["src_end2"]
        self._src_count = cols["src_count"]
        self._src_v2 = cols["src_v2"]
        py._host_deleted_rows = set(
            np.flatnonzero(cols["host_deleted"]).tolist()
        )
        py._lww_deleted = set(np.flatnonzero(cols["lww_deleted"]).tolist())

        ns = int(lib.ymx_n_slots(h))
        clients = np.empty(max(1, ns), np.int64)
        state = np.empty(max(1, ns), np.int64)
        if ns:
            lib.ymx_clients(h, _p64(clients))
            lib.ymx_state(h, _p64(state))
        py.client_of_slot = clients[:ns].tolist()
        py.slot_of_client = {c: i for i, c in enumerate(py.client_of_slot)}
        py.state = state[:ns].tolist()
        # host list state (the device right_link/starts mirror)
        links = np.empty(max(1, n), np.int64)
        if n:
            lib.ymx_links(h, _p64(links))
        py.list_next = links[:n]
        # fragment index: straight memcpy of the C++ index (already sorted)
        counts = np.zeros(max(1, ns), np.int64)
        if ns:
            lib.ymx_frag_counts(h, _p64(counts))
        py.frag_clock = []
        py.frag_row = []
        for s in range(ns):
            k = int(counts[s])
            fc = np.empty(max(1, k), np.int64)
            fr = np.empty(max(1, k), np.int64)
            if k:
                lib.ymx_frag(h, s, _p64(fc), _p64(fr))
            py.frag_clock.append(fc[:k])
            py.frag_row.append(fr[:k])

        # segments + interned strings
        nseg = self.n_segs
        blob_len = int(lib.ymx_strings_len(h))
        blob = np.empty(max(1, blob_len), np.uint8)
        if blob_len:
            lib.ymx_strings(h, blob.ctypes.data_as(_u8p))
        py._strings = bytearray(blob[:blob_len].tobytes())
        segc = {k: np.empty(max(1, nseg), np.int64) for k in
                ("name_ofs", "name_len", "sub_ofs", "sub_len", "parent")}
        if nseg:
            lib.ymx_segs(h, *(_p64(segc[k]) for k in segc))
        heads = np.empty(max(1, nseg), np.int64)
        if nseg:
            lib.ymx_heads(h, _p64(heads))
        py.head_of_seg = heads[:nseg]
        py.seg_name_ofs = segc["name_ofs"][:nseg].tolist()
        py.seg_name_len = segc["name_len"][:nseg].tolist()
        py.seg_sub_ofs = segc["sub_ofs"][:nseg].tolist()
        py.seg_sub_len = segc["sub_len"][:nseg].tolist()
        sb = bytes(py._strings)
        seg_info = []
        for i in range(nseg):
            no, nl = py.seg_name_ofs[i], py.seg_name_len[i]
            so, sl = py.seg_sub_ofs[i], py.seg_sub_len[i]
            name = utf8_decode_u16(sb[no : no + nl]) if no >= 0 else None
            sub = utf8_decode_u16(sb[so : so + sl]) if so >= 0 else None
            seg_info.append((name, sub, int(segc["parent"][i])))
        py.seg_info = seg_info
        py.segments = {key: i for i, key in enumerate(seg_info)}
        py._segs_of_parent = {}
        for i, (_n, _s, p) in enumerate(seg_info):
            if p != NULL:
                py._segs_of_parent.setdefault(p, []).append(i)
        py.map_chain = {}
        for i, (_n, sub, _p) in enumerate(seg_info):
            if sub is None:
                continue
            cl = int(lib.ymx_chain_len(h, i))
            if cl:
                chain = np.empty(cl, np.int64)
                lib.ymx_chain(h, i, _p64(chain))
                py.map_chain[i] = chain.tolist()

        # delete-set ranges in slot first-note order
        nds = int(lib.ymx_ds_count(h))
        ds_slot = np.empty(max(1, nds), np.int64)
        ds_clock = np.empty(max(1, nds), np.int64)
        ds_len = np.empty(max(1, nds), np.int64)
        if nds:
            lib.ymx_ds(h, _p64(ds_slot), _p64(ds_clock), _p64(ds_len))
        py.ds = {}
        for s, c, ln in zip(
            ds_slot[:nds].tolist(), ds_clock[:nds].tolist(),
            ds_len[:nds].tolist()
        ):
            py.ds.setdefault(s, []).append((c, ln))

        # buffer table: Python-origin bytes + arena chunks fetched once
        nb = int(lib.ymx_n_bufs(h))
        bufs: list[bytes] = []
        for i in range(nb):
            known = self._py_bufs.get(i)
            if known is not None:
                bufs.append(known[0])
            else:
                ln = int(lib.ymx_buf_len(h, i))
                chunk = np.empty(max(1, ln), np.uint8)
                if ln:
                    lib.ymx_copy_bytes(
                        h, i, 0, ln, chunk.ctypes.data_as(_u8p)
                    )
                b = chunk[:ln].tobytes()
                self._py_bufs[i] = (b, chunk)
                bufs.append(b)
        py._bufs = bufs

        py._gen = gen
        py._np_gen = -1
        py._ds_gen = gen
        py._ds_np_gen = -1
        self._synced_gen = gen

    def __getattr__(self, name):
        if name.startswith("__") or "_py" not in self.__dict__:
            raise AttributeError(name)
        self._sync()
        return getattr(self.__dict__["_py"], name)


# ymx_prepare_many's out_times in order, under the flush-metric keys a
# flush reports them as (seconds): the longest single doc's prepare, the
# sum over docs, that sum by phase (scan; merge + fixpoint; the cuts;
# rows + deletes + LWW; finalize), and what the pool cost the calling
# thread: handing the call to its workers, and the wait from the last
# thread's finding the queue empty to the caller's running again (both
# 0 on the serial branch)
PLAN_TIMES = (
    "plan_room_max_s", "plan_pool_s",
    "plan_scan_s", "plan_merge_s", "plan_cuts_s", "plan_rows_s",
    "plan_finalize_s", "plan_pool_start_s", "plan_pool_join_s",
)
# its out_pool, likewise: the threads the call planned on (the caller
# included), the workers it woke, the threads it had to construct
PLAN_POOL_COUNTS = ("plan_threads", "plan_pool_woken", "plan_pool_started")


def prepare_many(work, want_sched: bool = True, obs=None):
    """Batched ymx_prepare over many NativeMirrors in ONE native call.

    ``work`` is a list of ``(doc_idx, NativeMirror)``.  Returns
    ``(counts, rcs, staged_info, pool_times)`` where ``counts`` is an
    ``(n, 16)`` int64 array (ymx_prepare layout: ``[14]`` is the step's
    shape, bit 0 dense links, bit 1 the mirror held no row before it;
    ``[15]`` numbers the plan, see ``NativeMirror._plan_seq``),
    ``rcs`` the per-doc return codes, ``staged_info`` the per-doc
    ``(staged, ids)`` needed by ``_finish_prepare``, and ``pool_times``
    the call's own clock and counts, a dict of seconds under
    ``PLAN_TIMES``' keys and of integers under ``PLAN_POOL_COUNTS``'.
    The pool takes the call's long docs first
    (four times its mean staged bytes or more, longest first), then the
    others in index order; every output is at its doc's index in
    ``work``.

    ``obs`` (an :class:`yjs_tpu.obs.EngineObs`) records each call's wall
    time and doc count into the ``ytpu_native_prepare_many_*`` histograms
    — the planner-pool visibility the engine's per-flush timers cannot
    give once flushes span multiple chunks — and puts the marshalling
    under the ``ytpu.plan.stage`` span and the one native call under
    ``ytpu.plan.native``.

    ``want_sched=False`` skips building each plan's sched section
    (``NativePlan.sched`` then reads back empty) — ONLY safe when no
    consumer will read it, e.g. the bulk-apply flush with no event
    listeners; ``ymx_prepare``/``prepare_step`` always build it.

    Replaces the per-doc ctypes round trip.
    """
    t0 = time.perf_counter()
    span = obs.tracer.span if obs is not None else no_span
    n = len(work)
    lib = work[0][1]._lib
    with span("ytpu.plan.stage"):
        handles = (ctypes.c_void_p * n)()
        buf_ofs = np.zeros(n + 1, np.int64)
        # batched staging: ONE native call registers every staged buffer.
        # The c_char_p array extracts each bytes object's pointer in C
        # (no per-buffer numpy view); the bytes stay pinned via _py_bufs.
        all_bytes: list[bytes] = []
        v2_list: list[int] = []
        buf_hs = []
        for k, (_i, m) in enumerate(work):
            staged = m._incoming
            buf_ofs[k + 1] = buf_ofs[k] + len(staged)
            for u, v2 in staged:
                all_bytes.append(u)
                v2_list.append(1 if v2 else 0)
                buf_hs.append(m._h)
            handles[k] = m._h
        nb_tot = len(all_bytes)
        ids_flat = np.zeros(max(1, nb_tot), np.int64)
        v2_flat = np.asarray(v2_list or [0], np.int64)
        if nb_tot:
            ptrs = (ctypes.c_char_p * nb_tot)(*all_bytes)
            lens = np.fromiter(
                (len(u) for u in all_bytes), np.uint64, nb_tot
            )
            bhs = (ctypes.c_void_p * nb_tot)(*buf_hs)
            lib.ymx_add_bufs_many(
                bhs, ptrs,
                lens.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                nb_tot,
                _p64(ids_flat),
            )
        staged_info = []
        o = 0
        for k, (_i, m) in enumerate(work):
            staged = m._incoming
            nb = len(staged)
            ids = ids_flat[o : o + nb]
            for j, (u, _v2) in enumerate(staged):
                m._py_bufs[int(ids[j])] = (u, None)
            staged_info.append((staged, np.asarray(ids, np.int64)))
            o += nb
        counts = np.zeros((n, 16), np.int64)
        rcs = np.zeros(n, np.int64)
        times = np.zeros(len(PLAN_TIMES), np.float64)
        pool = np.zeros(len(PLAN_POOL_COUNTS), np.int64)
    # the native call alone: what is left of ytpu.plan around the two
    # is the engine's (the walk, the plan cache, finish)
    with span("ytpu.plan.native"):
        lib.ymx_prepare_many(
            handles, n, _p64(buf_ofs), _p64(ids_flat), _p64(v2_flat),
            1 if want_sched else 0, _p64(counts), _p64(rcs),
            times.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            _p64(pool),
        )
    dt = time.perf_counter() - t0
    if obs is not None:
        obs.native_prepare(n, dt)
    from ..obs.prof import kernel_profiler

    kernel_profiler().record_host_op("prepare_many", dt)
    pool_times = dict(zip(PLAN_TIMES, times.tolist()))
    pool_times.update(zip(PLAN_POOL_COUNTS, pool.tolist()))
    return counts, rcs, staged_info, pool_times


def _encode_many(lib, handles, n, sv_ofs, svc, svk, modes, seqs, ds_ofs,
                 triples):
    """The one ``ymx_encode_steps_many`` call both callers below make:
    ``(arena, ofs, rcs)``, room ``k``'s bytes at ``arena + ofs[k]`` up to
    ``arena + ofs[k + 1]`` wherever ``rcs[k] >= 0``.  The arena is the
    calling thread's and its next call overwrites it."""
    flat = [
        np.array(a, np.int64)
        for a in (
            sv_ofs, svc or [0], svk or [0], modes, seqs, ds_ofs,
            triples or [0],
        )
    ]
    out_ofs = np.zeros(n + 1, np.int64)
    rcs = np.zeros(n, np.int64)
    lib.ymx_encode_steps_many(
        handles, n, *(_p64(a) for a in flat), _p64(out_ofs), _p64(rcs)
    )
    return lib.ymx_encode_arena(), out_ofs.tolist(), rcs


def encode_steps_many(work, pre_svs):
    """The step updates of many NativeMirrors from ONE native call
    (``ymx_encode_steps_many``): what ``encode_step_update`` gives room by
    room, byte for byte, V1.

    ``work`` is a list of ``(doc_idx, NativeMirror, ds)``; ``pre_svs`` maps
    ``doc_idx`` to the state vector the room's peers hold (missing or
    empty: the whole room).  ``ds`` names the delete-set section: an
    ``int`` is the number of the mirror's own current plan
    (``counts[15]``), whose applied delete set the core then reads in
    place; a sequence of ``(client, clock, len)`` triples is written as
    given; ``None`` is the room's whole derived delete set (the form
    ``encode_state_as_update`` sends; a handshake's many answers go
    through ``encode_diffs_many``, which keys nothing by room).

    Returns ``(updates, rcs)``, both as long as ``work``.  ``rcs[k] < 0``
    means the core wrote nothing for that room: ``-7`` a selected row
    needs the Python writer (V2-framed or spilled payloads), ``-8`` the
    mirror has planned again since the plan numbered ``ds``; the caller
    takes such a room through ``encode_step_update``.  ``updates[k]`` is
    ``None`` there, and where the update carries nothing (the V1 form of
    that is two zero bytes)."""
    n = len(work)
    lib = work[0][1]._lib
    handles = (ctypes.c_void_p * n)()
    sv_ofs, ds_ofs, modes, seqs = [0], [0], [], []
    svc: list[int] = []
    svk: list[int] = []
    triples: list = []
    for k, (i, m, ds) in enumerate(work):
        handles[k] = m._h
        sv = pre_svs.get(i)
        if sv:
            svc.extend(sv.keys())
            svk.extend(sv.values())
        sv_ofs.append(len(svc))
        mode = seq = 0
        if ds is None:
            mode = 2
        elif isinstance(ds, int):
            seq = ds
        else:
            mode = 1
            triples.extend(ds)
        modes.append(mode)
        seqs.append(seq)
        ds_ofs.append(len(triples))
    base, ofs, rcs = _encode_many(
        lib, handles, n, sv_ofs, svc, svk, modes, seqs, ds_ofs, triples
    )
    updates: list = [None] * n
    for k, rc in enumerate(rcs.tolist()):
        if rc < 0:
            continue
        u = ctypes.string_at(base + ofs[k], ofs[k + 1] - ofs[k])
        if u != b"\x00\x00":
            updates[k] = u
    return updates, rcs


# requests one ymx_encode_steps_many call answers: a tick of handshakes.
# A checkpoint's 4096 whole rooms in one arena would be 60 MB and more;
# sliced, the arena stays what a tick needs and the core's own release
# rule sees it as it sees a flush's
_DIFF_SLICE = 256


def encode_diffs_many(requests):
    """The answers to many sync step 1 from ONE native call a slice of
    ``_DIFF_SLICE`` requests: what ``encode_diff_update(sv)`` gives
    request by request, byte for byte, V1 (``ymx_encode_steps_many`` with
    every room's whole derived delete set, ``encodeStateAsUpdate``).

    ``requests`` is a list of ``(NativeMirror, state vector)``, one state
    vector a REQUEST: several sessions of one room ask with different
    ones, so nothing is keyed by room, and a mirror may appear any number
    of times (the core only reads it).  Missing or empty: the whole room.

    Returns ``(updates, arena_bytes)``.  ``updates[k]`` is ``None`` where
    the core wrote nothing (its ``rc < 0``: ``-7`` a selected row needs
    the Python writer) and the caller asks ``encode_diff_update``; an
    answer that carries nothing is its two zero bytes, which a step 2
    sends.  ``arena_bytes`` is what the calls wrote: the bytes of
    ``updates`` and no more."""
    updates: list = []
    arena_bytes = 0
    lib = requests[0][0]._lib if requests else None
    for lo in range(0, len(requests), _DIFF_SLICE):
        part = requests[lo : lo + _DIFF_SLICE]
        n = len(part)
        handles = (ctypes.c_void_p * n)()
        sv_ofs = [0]
        svc: list[int] = []
        svk: list[int] = []
        for k, (m, sv) in enumerate(part):
            handles[k] = m._h
            if sv:
                svc.extend(sv.keys())
                svk.extend(sv.values())
            sv_ofs.append(len(svc))
        base, ofs, rcs = _encode_many(
            lib, handles, n, sv_ofs, svc, svk, [2] * n, [0] * n,
            [0] * (n + 1), None,
        )
        arena_bytes += ofs[n]
        # copies, made before this thread's next encode reuses the arena
        updates.extend(
            None if rc < 0
            else ctypes.string_at(base + ofs[k], ofs[k + 1] - ofs[k])
            for k, rc in enumerate(rcs.tolist())
        )
    return updates, arena_bytes


def pack_apply_lanes(work, doc_ids, b_loc, n_shards, widths, oob_r, oob_s,
                     null_val, dtype=np.int32, out=None):
    """Fill the bulk-apply scatter lanes for ``work`` (post-prepare
    ``(doc_idx, NativeMirror)`` entries, rc==0) natively.  Returns
    ``(lanes, stats)`` with ``lanes`` shaped ``(n_shards, lane_w)`` and
    ``stats = [n_dense, n_sparse, n_heads, n_dels]`` real elements —
    the native twin of BatchEngine._flush_apply's pack loop.

    ``dtype=np.int16`` halves the transfer when every row/seg index fits
    16 bits (the caller checks capacity); the kernel widens on device.
    ``out`` reuses a caller-owned ``(n_shards, lane_w)`` staging buffer
    (the flush pipeline's double-buffered pair) instead of allocating."""
    k_dn, k_sp, k_h, k_d = widths
    n = len(work)
    lib = work[0][1]._lib
    handles = (ctypes.c_void_p * n)()
    for k, (_i, m, *_rest) in enumerate(work):
        handles[k] = m._h
    lane_w = 4 * b_loc + k_dn + 2 * k_sp + 2 * k_h + k_d
    if out is not None and out.shape == (n_shards, lane_w) and out.dtype == dtype:
        lanes = out
    else:
        lanes = np.empty((n_shards, lane_w), dtype)
    stats = np.zeros(4, np.int64)
    ids = np.ascontiguousarray(doc_ids, np.int64)
    fn = lib.ymx_pack_apply16 if dtype == np.int16 else lib.ymx_pack_apply
    fn(
        handles, _p64(ids), n, b_loc, n_shards, k_dn, k_sp, k_h, k_d,
        ctypes.c_int32(oob_r), ctypes.c_int32(oob_s),
        ctypes.c_int32(null_val),
        lanes.ctypes.data_as(ctypes.c_void_p), _p64(stats),
    )
    return lanes, stats


def pack_row_blocks(work, pos, right, deleted, starts, null_val):
    """Fill the rows ``pos`` of one staged block natively from ``work``
    (post-prepare ``(doc_idx, NativeMirror, ...)`` entries, each a room
    loaded whole into an empty slot): a room's dense links with
    ``null_val`` behind them, its tombstones, its list heads among
    ``null_val`` — every cell of its row.  ``right`` and ``starts`` are
    int16 or int32 alike, ``deleted`` bool, all C-contiguous; the native
    twin of ``BatchEngine._pack_chunk_py``'s ``fill_rows``."""
    n = len(work)
    lib = work[0][1]._lib
    handles = (ctypes.c_void_p * n)()
    for k, (_i, m, *_rest) in enumerate(work):
        handles[k] = m._h
    w, ws = right.shape[1], starts.shape[1]
    if not (
        right.dtype == starts.dtype and deleted.shape == right.shape
        and deleted.dtype == np.bool_ and starts.shape[0] == right.shape[0]
        and all(a.flags.c_contiguous for a in (right, deleted, starts))
        and 0 <= int(pos.min()) and int(pos.max()) < right.shape[0]
    ):
        raise ValueError("pack_row_blocks: the staged block is misshapen")
    fn = lib.ymx_pack_rows16 if right.dtype == np.int16 else lib.ymx_pack_rows
    outside = fn(
        handles, _p64(np.ascontiguousarray(pos, np.int64)), n, w, ws,
        ctypes.c_int32(null_val),
        right.ctypes.data_as(ctypes.c_void_p),
        deleted.ctypes.data_as(ctypes.c_void_p),
        starts.ctypes.data_as(ctypes.c_void_p),
    )
    if outside:
        raise RuntimeError(
            f"pack_row_blocks: {outside} writes outside a {w} x {ws} row"
        )
