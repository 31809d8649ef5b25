"""BatchEngine: batched TPU apply_update over many docs.

The device-side half of the `y-tpu` Provider described in BASELINE.json's
north star: pending binary updates from many docs are marshalled into
struct-of-arrays columns (:mod:`.columns`), integrated by the vmapped YATA
kernel (:mod:`.kernels`), and the persistent device state (links, segment
heads, deleted bits) lives across flushes.  Root text/list/map types and
arbitrarily nested shared types are all served on device (nested types are
parent-row-keyed segments, reference ContentType.js); only docs embedding
subdocuments transparently fall back to the CPU reference core — the
Provider gating seam.
"""

from __future__ import annotations

import contextlib
import math
import os
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from ..core import Doc
from ..lib0.u16 import from_u16
from ..obs import EngineObs, new_flush_metrics
from ..obs.prof import profiled
from ..resilience import DeadLetterQueue, HealthTracker
from ..updates import InvalidUpdate, validate_update
from ..updates import apply_update, apply_update_v2
from .columns import NULL, DocMirror, UnsupportedUpdate
from . import plan_cache
from .native_mirror import (
    PLAN_POOL_COUNTS,
    PLAN_TIMES,
    NativeMirror,
    NativePlan,
    encode_diffs_many,
    encode_steps_many,
    native_plan_available,
    pack_apply_lanes,
    pack_row_blocks,
    plan_segment_stats,
    prepare_many,
)
from . import kernels
from .compile_cache import ensure_compile_cache
from .host_heap import ensure_heap_kept


def _add_plan_times(total: dict, call: dict) -> None:
    """One ymx_prepare_many call's clock and counts (``PLAN_TIMES``,
    ``PLAN_POOL_COUNTS``) into a flush's: the longest room's prepare and
    the threads a call planned on are maxima, the others are sums."""
    for key, t in call.items():
        if key in ("plan_room_max_s", "plan_threads"):
            total[key] = max(total[key], t)
        else:
            total[key] += t


def make_mirror(root_name: str):
    """DocMirror served by the C++ plan core when available; the pure-
    Python mirror otherwise (no toolchain / YTPU_NO_NATIVE_PLAN)."""
    if native_plan_available():
        return NativeMirror(root_name)
    return DocMirror(root_name)


def visible_text(mirror, rows, deleted) -> str:
    """Materialize visible text from document-ordered rows + deleted flags.

    Content strings are UTF-16 code units (surrogate pairs may be split
    across runs, reference ContentString.js:51-66); recombine like
    YText.to_string does.  Shared by BatchEngine.text and bench.py.
    """
    out = []
    for r, d in zip(rows, deleted):
        if d or not mirror.row_countable[r]:
            continue
        content = mirror.realized_content(r)
        s = getattr(content, "str", None)
        if s is not None:
            out.append(s)
        else:
            out.append("".join(str(x) for x in getattr(content, "arr", [])))
    return from_u16("".join(out))


# one device: the widest lane key that counts as a served flush's, and
# the padding such a flush accepts before it has a new program compiled
# (engine._covering_key)
_SERVED_LANES = 4096
_SERVED_PAD = 512
# one device: the rooms, and their width, that one staged block of a
# served process's compactions holds (engine._scatter_rebuilt)
_SERVED_ROOMS = 8
_SERVED_WIDTH = 4096

_KIND_KEYS = (
    "rows_planned", "rows_nested", "rows_format", "rows_attr", "rows_type",
    "segs_created", "lww_overwritten", "format_deleted", "conflict_steps",
)
_KIND_BITS = 21  # plancore.cpp plan_kind_counts: three fields a word


def _kind_counts(counts: np.ndarray) -> dict:
    """What each native plan of ``counts`` integrated, by kind
    (``counts[:, 3:6]`` as ``plan_kind_counts`` packs them)."""
    mask = (1 << _KIND_BITS) - 1
    c3, c4, c5 = counts[:, 3], counts[:, 4], counts[:, 5]
    return {
        "rows_planned": c4 & mask,
        "rows_nested": (c4 >> _KIND_BITS) & mask,
        "rows_format": (c4 >> 2 * _KIND_BITS) & mask,
        "rows_attr": c5 & mask,
        "rows_type": c3 & mask,
        "segs_created": (c5 >> _KIND_BITS) & mask,
        "lww_overwritten": (c5 >> 2 * _KIND_BITS) & mask,
        "format_deleted": (c3 >> _KIND_BITS) & mask,
        "conflict_steps": (c3 >> 2 * _KIND_BITS) & mask,
    }


def _dense_links(p) -> bool:
    """A Python-lane plan writes every row its room has: links for rows
    ``0..k-1``, all of them (the core's twin: plancore.cpp
    ``plan_shape``, bit 0)."""
    k = len(p.link_rows)
    return bool(k) and k == p.n_rows and int(p.link_rows[-1]) == k - 1


def _kind_counts_py(m, p) -> dict:
    """The same of one Python plan, from the mirror's columns."""
    rows = [int(s[0]) for s in p.sched]
    info, seg, ref = m.seg_info, m.row_seg, m.row_content_ref
    segs = [info[seg[r]] for r in rows if seg[r] != NULL]
    return {
        "rows_planned": len(rows),
        "rows_nested": sum(1 for _n, _s, parent in segs if parent != NULL),
        "rows_format": sum(1 for r in rows if ref[r] == 6),
        "rows_attr": sum(1 for _n, sub, _p in segs if sub is not None),
        "rows_type": sum(1 for r in rows if ref[r] == 7),
        "segs_created": m.n_segs - p.segs_before,
        "lww_overwritten": p.lww_overwritten,
        "format_deleted": sum(1 for r in p.delete_rows if ref[int(r)] == 6),
        "conflict_steps": 0,  # the Python walk keeps no count
    }


# of a flush's metrics, those that are a level and not a sum: the second
# round of a flush (engine._finish_flush) keeps the larger
_FLUSH_LEVELS = (
    "n_rows_max", "schedule_occupancy",
    "n_fallback_docs", "n_pending_docs", "pending_depth", "plan_threads",
    "plan_room_max_s", "pipeline_depth", "n_segs_max", "seg_cap",
)


def _add_flush_round(metrics: dict, first: dict) -> None:
    """Fold the first round's metrics of a flush into its second's."""
    for key, value in first.items():
        if key in _FLUSH_LEVELS:
            metrics[key] = max(metrics[key], value)
        elif key == "flush_donated":
            metrics[key] = int(
                (metrics[key] or first[key])
                and not (metrics["realloc_bytes"] + first["realloc_bytes"])
            )
        else:
            metrics[key] += value


def _delete_only_update(ids) -> bytes:
    """A V1 update of no struct and the delete set ``ids``
    (``(client, clock, length)``, any order)."""
    from ..coding import DSEncoderV1
    from ..core import (
        add_to_delete_set, create_delete_set, sort_and_merge_delete_set,
        write_delete_set,
    )
    from ..lib0.encoding import write_var_uint

    ds = create_delete_set()
    for client, clock, length in ids:
        add_to_delete_set(ds, client, clock, length)
    sort_and_merge_delete_set(ds)
    encoder = DSEncoderV1()
    write_var_uint(encoder.rest_encoder, 0)  # no client's structs
    write_delete_set(encoder, ds)
    return encoder.to_bytes()


def _cleanup_room(m, rows_before: int, plan):
    """``(ids, texts)``: the format items ``(client, clock, length)``
    that ``BatchEngine._format_cleanup`` deletes in one room after the
    flush that ``plan`` is of, and the texts it walked.  ``rows_before``
    is the mirror's row count before that flush."""
    from .events import _coverage, _deleted_now

    info, row_seg, ref = m.seg_info, m.row_seg, m.row_content_ref
    dead = m._host_deleted_rows
    client_of, slot, clock = m.client_of_slot, m.row_slot, m.row_clock
    # the types the flush changed: it added an item of theirs (a row it
    # split is in a segment it added to or deleted from) ...
    changed, brought = set(), False
    for r in range(rows_before, m.n_rows):
        if ref[r] == 6 and r not in dead:
            brought = True
        if row_seg[r] != NULL:
            changed.add(int(row_seg[r]))
    # ... or deleted one (fragments of rows deleted by earlier flushes
    # ride in delete_rows too: the flush's own delete set tells them)
    cov = _coverage(plan.applied_ds)
    lost_format = set()
    for r in plan.delete_rows:
        r = int(r)
        sg = int(row_seg[r])
        if sg == NULL or not _deleted_now(cov, client_of[slot[r]], clock[r]):
            continue
        changed.add(sg)
        if ref[r] == 6:
            lost_format.add(sg)
    ids, texts = [], 0
    for sg in sorted(changed):
        _name, sub, parent = info[sg]
        if sub is not None or parent == NULL or not (
            brought or sg in lost_format
        ):
            continue
        parent = int(parent)
        if parent >= rows_before or parent in dead:
            continue  # a type of this flush's own, or one it deleted
        kind = type(getattr(m.realized_content(parent), "type", None))
        if kind.__name__ not in ("YText", "YXmlText"):
            continue
        texts += 1
        ids += [
            (client_of[slot[r]], int(clock[r]), int(m.row_len[r]))
            for r in _cleanup_text(m, sg)
        ]
    return ids, texts


def _cleanup_text(m, seg: int) -> list[int]:
    """``cleanupYTextFormatting`` (reference YText.js:412-437) over one
    text's rows in list order: the format rows to delete.  An attribute
    value that is an object equals itself alone, as in the reference
    (JS ``===``): a row's realized content is one object while this
    runs."""
    from ..types.ytext import _js_strict_eq, _or_null, update_current_attributes

    dead, nxt, ref = m._host_deleted_rows, m.list_next, m.row_content_ref
    order = []
    r = m.head_of_seg[seg]
    while r != NULL:
        order.append(int(r))
        r = nxt[int(r)]
    formats: dict[int, object] = {}
    doomed: list[int] = []
    start, start_attrs, attrs = 0, {}, {}
    for end, r in enumerate(order):
        if r in dead:
            continue
        if ref[r] == 6:
            formats[r] = content = m.realized_content(r)
            update_current_attributes(attrs, content)
        elif ref[r] in (4, 5):
            for at in order[start:end]:
                content = formats.get(at)
                if content is not None and (
                    not _js_strict_eq(
                        _or_null(attrs.get(content.key)), content.value
                    )
                    or _js_strict_eq(
                        _or_null(start_attrs.get(content.key)), content.value
                    )
                ):
                    doomed.append(at)
            formats.clear()
            start, start_attrs = end, dict(attrs)
    return doomed


def _bucket(n: int, minimum: int = 64) -> int:
    """Round up to the padding bucket (power of two) to bound recompiles."""
    b = minimum
    while b < n:
        b *= 2
    return b


# scatter-lane width quantization: 2**bits mantissa steps per power-of-two
# octave.  bits=3 (default) caps padding waste at 12.5% of the request
# (vs 50% for pure powers of two) while keeping the distinct compiled
# shapes bounded at 8 per octave.  bits=0 restores pure powers of two.
_PAD_BITS = max(0, min(6, int(os.environ.get("YTPU_PAD_BITS", "3"))))


def _bucket_lanes(n: int, minimum: int = 64) -> int:
    """Round a per-flush LANE width up to the next mantissa-quantized
    bucket.  Used only for transfer-lane widths (the occupancy metric);
    device STATE capacities keep plain powers of two, where fewer, larger
    growth steps amortize the on-device copy better."""
    if n <= minimum:
        return minimum
    bits = _PAD_BITS
    if bits == 0:
        return _bucket(n, minimum)
    e = max(0, (n - 1).bit_length() - 1 - bits)
    return ((n + (1 << e) - 1) >> e) << e


@jax.jit
def _done_token(table):
    """Completion marker of the dispatch that produced ``table``.

    It reads every doc row's first cell, so on a mesh it depends on all
    shards: it is ready only once that dispatch has finished everywhere,
    which also means every input the dispatch read (a staged host buffer
    included) has been consumed.  The resident tables themselves cannot
    serve: the next donating dispatch deletes them, and ``is_ready`` /
    ``block_until_ready`` raise on a deleted array."""
    return table[:, 0].sum()


def _pipeline_on() -> bool:
    """YTPU_FLUSH_PIPELINE knob: pipelined flush is the default; ``0`` /
    ``false`` / ``off`` restores the fully synchronous dispatch (the A/B
    lane — byte-identical output is the pipeline's correctness bar)."""
    return os.environ.get("YTPU_FLUSH_PIPELINE", "1").lower() not in (
        "0", "false", "off",
    )


class _StageSlot:
    """One half of the double-buffered staging pair: a reusable host lanes
    buffer plus the completion marker (``_done_token``) of the dispatch
    that last consumed it — the reuse fence.  ``jnp.asarray`` returns
    before the host buffer has been read: the TPU backend transfers it
    asynchronously and the CPU backend may alias it zero-copy, so the
    buffer must not be rewritten until that dispatch has finished."""

    __slots__ = ("buf", "marker")

    def __init__(self):
        self.buf = None
        self.marker = None


class _PackTimer:
    """Times one host pack and books it as overlapped when a device
    dispatch was still outstanding (dispatched this flush, not yet
    blocked on) at pack start — the numerator of the bench overlap
    fraction (t_pack_overlap_s / t_pack_s).  This is pack work the
    synchronous A/B lane would have serialized behind a blocking wait;
    it does not re-probe readiness, because an async backend that
    happens to finish early (CPU) still proves the host never waited —
    the honest wait time is t_device_wait_s."""

    __slots__ = ("_pl", "_t0", "_overlap")

    def __init__(self, pl):
        self._pl = pl
        self._t0 = 0.0
        self._overlap = False

    def __enter__(self):
        self._overlap = self._pl.outstanding > 0
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._overlap:
            self._pl.t_pack_overlap_s += time.perf_counter() - self._t0
        return False


class _FlushPipeline:
    """Pipelined flush state machine (ISSUE 12): stage N+1's host-side
    pack overlaps stage N's device execution.

    JAX dispatch is asynchronous — a jitted call returns as soon as the
    work is enqueued — so the pipeline needs no threads: it only has to
    (a) keep packing into a DIFFERENT staging buffer than the one the
    in-flight dispatch may still be reading (``acquire`` alternates the
    double-buffered pair and blocks — counted as t_device_wait_s — only
    when both halves are still feeding the device), and (b) account
    honestly for what overlapped (``_PackTimer``).  ``sync=True`` is the
    YTPU_FLUSH_PIPELINE=0 A/B lane: every dispatch blocks to completion
    before the host proceeds.

    One instance persists across flushes (the staging pair and in-flight
    markers carry over, so steady state reallocates nothing);
    ``begin_flush`` resets only the per-flush counters."""

    __slots__ = (
        "sync", "t_pack_overlap_s", "t_device_wait_s", "n_dispatches",
        "max_depth", "outstanding", "_slots", "_turn", "_inflight",
    )

    def __init__(self):
        self.sync = False
        self.t_pack_overlap_s = 0.0
        self.t_device_wait_s = 0.0
        self.n_dispatches = 0
        self.max_depth = 0
        # dispatches this flush the host has not blocked on (the
        # _PackTimer overlap predicate; reset per flush so read-backs
        # between flushes can't inflate it)
        self.outstanding = 0
        self._slots = (_StageSlot(), _StageSlot())
        self._turn = 0
        self._inflight: list = []

    def begin_flush(self, sync: bool) -> None:
        self.sync = sync
        self.t_pack_overlap_s = 0.0
        self.t_device_wait_s = 0.0
        self.n_dispatches = 0
        self.max_depth = 0
        self.outstanding = 0

    def _wait(self, marker) -> None:
        """Block until ``marker``'s dispatch has finished; a device error
        (out of memory, a failed transfer) surfaces here."""
        t0 = time.perf_counter()
        jax.block_until_ready(marker)
        self.t_device_wait_s += time.perf_counter() - t0
        self.outstanding = 0

    def acquire(self, shape, dtype) -> _StageSlot:
        """Next staging buffer of the pair, ready for host writes.  The
        slot's previous dispatch (two dispatches back in steady state)
        must have consumed the buffer before it is rewritten; any block
        here is real pipeline back-pressure, counted as device wait."""
        self._turn ^= 1
        slot = self._slots[self._turn]
        if slot.marker is not None:
            if not slot.marker.is_ready():
                self._wait(slot.marker)
            slot.marker = None
        buf = slot.buf
        if buf is None or buf.shape != tuple(shape) or buf.dtype != dtype:
            slot.buf = buf = np.empty(shape, dtype)
        return slot

    def pack(self) -> _PackTimer:
        return _PackTimer(self)

    def dispatched(self, marker, slot: _StageSlot | None = None) -> None:
        """Book one device dispatch.  ``marker`` is its ``_done_token``:
        ready implies every input (including ``slot``'s staging buffer)
        has been consumed."""
        self.n_dispatches += 1
        if slot is not None:
            slot.marker = marker
        if self.sync:
            self._wait(marker)
            self._inflight = []
            if slot is not None:
                slot.marker = None
            return
        self.outstanding += 1
        self._inflight = [a for a in self._inflight if not a.is_ready()]
        self._inflight.append(marker)
        if len(self._inflight) > self.max_depth:
            self.max_depth = len(self._inflight)


class BatchEngine:
    """Applies binary Yjs updates to a batch of docs on device.

    Parameters
    ----------
    n_docs: batch size.
    root_name: the default root type for text()/rows_in_order() when no
        name is passed; any number of root text/list/map types per doc —
        and the shared types nested inside them — are integrated on
        device (subdocs fall back to the CPU core per doc).
    """

    def __init__(
        self,
        n_docs: int,
        root_name: str = "text",
        mesh=None,
        gc: bool = False,
        compact_min_rows: int = 512,
        policy: str = "auto",
    ):
        if policy not in ("auto", "cpu", "device"):
            raise ValueError(f"unknown policy {policy!r}")
        ensure_heap_kept()
        if policy != "cpu":  # a CPU-served engine never touches JAX
            ensure_compile_cache()
        self.n_docs = n_docs
        self.root_name = root_name
        self.mesh = mesh
        self.gc = gc
        self.compact_min_rows = compact_min_rows
        # backend policy: "auto" demotes out-of-scope docs to the CPU core,
        # "cpu" serves every doc on the CPU core (lazily, no device work),
        # "device" records demotions as in auto (state stays consistent and
        # no data is lost) but the Provider raises while any exist
        self.policy = policy
        # export source: host list walk (default — zero device round trips)
        # or device rank kernel (the verification path; the test suite sets
        # YTPU_EXPORT_DEVICE=1 so every oracle comparison validates the
        # DEVICE state, and a dedicated test pins host==device)
        self.export_from_device = os.environ.get("YTPU_EXPORT_DEVICE") == "1"
        # per-doc row count at the last compaction (growth trigger)
        self._rows_at_compact = [0] * n_docs
        # per-doc stats of the most recent flush's compactions
        self.last_compaction: list[dict] | None = None
        # doc.on('update') seam: callbacks (doc_idx, update_bytes) invoked
        # after each flush with the flush's incremental update per doc
        self._update_listeners: list = []
        # typed-event seam: doc idx -> callbacks(doc, events) where events
        # are YEvent-shaped dicts computed from the step plan (reference
        # observe/observeDeep, AbstractType.js:360-389)
        self._event_listeners: dict[int, list] = {}
        self._metrics_dev: dict | None = None
        # cached sharded bulk-apply callables keyed by lane bucket shape
        # (jit's cache is per function identity — rebuilding retraces)
        self._sharded_apply: dict[tuple, object] = {}
        # the sharded row load: one jitted callable for every block shape
        self._sharded_load = None
        # lane keys the packer has issued and may issue again (_covering_key)
        self._mesh_keys: set[tuple] = set()
        # explicit placement: a meshed engine pins EVERY host->device
        # transfer to the mesh's devices so it can never touch the default
        # backend (the mesh may be a virtual CPU mesh while the default
        # platform is a real accelerator — the multichip dry-run context)
        self._ns_batch = None  # [B, ...] arrays, doc axis sharded
        self._ns_repl = None  # small aux arrays, replicated over the mesh
        if mesh is not None:
            doc_axis = mesh.axis_names[0]
            axis_size = mesh.shape[doc_axis]
            if n_docs % axis_size != 0:
                raise ValueError(
                    f"n_docs={n_docs} must be a multiple of the {doc_axis!r} "
                    f"axis size {axis_size}"
                )
            from jax.sharding import NamedSharding, PartitionSpec

            self._ns_batch = NamedSharding(mesh, PartitionSpec(doc_axis))
            self._ns_repl = NamedSharding(mesh, PartitionSpec())
        self.mirrors: list = [make_mirror(root_name) for _ in range(n_docs)]
        # CPU fallback docs (Provider gating): doc idx -> Doc
        self.fallback: dict[int, Doc] = {}
        # every demotion ever, with its reason — scope gaps are measurable,
        # not silent (each entry: {"doc", "reason"})
        self.demotions: list[dict] = []
        # observability bundle: metrics registry + flush-history ring +
        # host span tracer (host-side per-phase timers + batch stats of
        # every flush live in obs.history; last_flush_metrics is the
        # compatibility view of the newest entry)
        self.obs = EngineObs()
        # what the newest sync_step2_batch did (requests, buffer bytes, s)
        self.last_sync_metrics: dict | None = None
        # resilience (ISSUE 2): per-doc failure isolation.  Strict mode
        # (YTPU_RESILIENCE_DISABLED=1) restores the pre-resilience
        # contract — integration failures raise out of flush()
        self._strict = os.environ.get("YTPU_RESILIENCE_DISABLED") == "1"
        self.health = HealthTracker(obs=self.obs)
        self.dead_letters = DeadLetterQueue()
        # every transactional per-doc rollback, with its reason (the
        # rollback subset of self.demotions)
        self.rollbacks: list[dict] = []
        self._update_log: list[list[tuple[bytes, bool]]] = [[] for _ in range(n_docs)]
        # warm-promotion column scatters deferred to the next flush /
        # device read-back: doc -> (right, deleted, seg_heads) rows
        self._pending_hydration: dict[int, tuple] = {}
        # persistent device state (no left-link array: order is ranked from
        # right links with a host-known membership mask)
        self._cap = 0  # row capacity N (arrays are [B, N+1] with scratch row)
        self._seg_cap = 0  # segment capacity S (starts is [B, S+1])
        self._right = None
        self._deleted = None
        self._starts = None
        # pipelined flush state (ISSUE 12): double-buffered staging pair +
        # in-flight dispatch markers persist ACROSS flushes so steady
        # state neither reallocates nor stalls; per-flush counters reset
        # in _flush.  Sync (A/B) mode is re-read from YTPU_FLUSH_PIPELINE
        # at every flush.
        self._pl = _FlushPipeline()
        # device-table bytes (re)allocated during the current flush — 0 in
        # steady state, where every dispatch donates in place
        self._flush_realloc_bytes = 0
        # bytes the compactions and hydrations since the last flush's end
        # staged, and the bytes of rows those rooms hold (their ratio: how
        # full a staged block is)
        self._flush_rows_staged_bytes = 0
        self._flush_rows_held_bytes = 0
        self._flush_rows_staged_blocks = 0
        # rooms whose last plan brought or deleted a format item, each
        # with the rows it held before that plan: what _format_cleanup
        # looks at; and the first round's metrics of a flush whose
        # clean-up planned a second one
        self._cleanup_gate: list[tuple] = []
        self._flush_carry: dict | None = None
        # the device's rows of one room, read back once for an export
        # that walks many of its segments: (doc, right, deleted, starts)
        self._export_rows: tuple | None = None
        # bytes of device rows the releases since the last flush's end
        # blanked (reset_doc: one whole row of each table a slot)
        self._release_blanked_bytes = 0
        # slots that ever accepted traffic (cleared by reset_doc): feeds
        # the ytpu_prof_slot_occupancy gauge in O(1) per update
        self._active_docs: set[int] = set()
        # rooms that may need something from the next flush: fed by
        # queue_update (and hydrate_doc_columns, whose mirror may bring
        # parked structs).  The plan phase visits these slots and no
        # other; a room leaves when a flush took its _incoming and it
        # holds no pending structs
        self._dirty_docs: set[int] = set()
        # rooms planned since the last compaction look: only their
        # n_rows can have moved, so only they are read by the next look
        self._compact_look: set[int] = set()

    # -- update ingestion ---------------------------------------------------

    def queue_update(self, doc: int, update: bytes, v2: bool = False) -> bool:
        """Queue one update for ``doc``; returns True when accepted.

        False means the bytes were diverted to :attr:`dead_letters`
        instead of entering the pipeline: the doc is quarantined, or (on
        the CPU-served path, where apply is immediate) the update failed
        to apply.  Callers that track dirtiness should only mark dirty
        on True."""
        if (
            not self._strict
            and self.health.tracked
            and not self.health.admissible(doc)
        ):
            self._dead_letter(doc, update, v2, "quarantined")
            return False
        fb = self.fallback.get(doc)
        if fb is None and self.policy == "cpu":
            fb = self._cpu_serve(doc)
        if fb is not None:
            # CPU-served docs apply directly; the log is dead weight for them
            try:
                (apply_update_v2 if v2 else apply_update)(fb, update)
            except Exception as e:
                if self._strict:
                    raise
                reason = f"cpu-apply: {type(e).__name__}: {e}"
                self._dead_letter(doc, update, v2, reason)
                self.health.record_failure(doc, reason)
                return False
            if self.health.tracked:
                self.health.record_success(doc)
        else:
            self._update_log[doc].append((update, v2))
            self.mirrors[doc].ingest(update, v2)
            self._dirty_docs.add(doc)
        self._active_docs.add(doc)
        return True

    def _dead_letter(self, doc: int, update: bytes, v2: bool, reason: str) -> None:
        self.dead_letters.append(doc, update, v2, reason)
        self.obs.dead_lettered(
            reason, len(self.dead_letters), self.dead_letters.dropped
        )

    def _cpu_serve(self, doc: int) -> Doc:
        """Route a doc to the CPU reference core by configuration (policy
        'cpu') — not a demotion, so it is not recorded as one."""
        fb = Doc(gc=False)
        self.fallback[doc] = fb
        self.mirrors[doc] = DocMirror(self.root_name)  # dead mirror
        fb.on("update", lambda u, origin, d, i=doc: self._emit(i, u))
        if doc in self._event_listeners:
            self._attach_cpu_events(doc, fb)
        return fb

    def on_update(self, callback) -> None:
        """Register ``callback(doc_idx, update_bytes)`` — called after each
        flush with that flush's incremental update per changed doc (the
        reference doc.on('update') broadcast contract,
        Transaction.js:339-352).  Demoted docs keep emitting via their CPU
        Doc's own update events."""
        self._update_listeners.append(callback)

    def off_update(self, callback) -> None:
        self._update_listeners.remove(callback)

    def observe(self, doc: int, callback) -> None:
        """Register ``callback(doc_idx, events)`` for one doc: after each
        flush that changes it, ``events`` is a list of YEvent-shaped dicts
        ``{"path", "delta", "keys"}`` — path[0] is the root type name,
        deeper elements are map keys / list indices (reference
        YEvent.path + YEvent.changes).  Demoted docs deliver the same
        shape from the CPU core's transactions.

        Numeric list positions in ``path`` match the reference getPathTo
        (YEvent.js:207-228) exactly: one per undeleted ITEM before the
        target, with mirror rows grouped into CPU-merged-item runs so the
        count equals what a CPU doc reports even though the mirror merges
        lazily (ops/events.py _path_of / _rows_one_cpu_item; parity
        pinned by test_engine_events.py::test_event_path_parity_*)."""
        self._event_listeners.setdefault(doc, []).append(callback)
        fb = self.fallback.get(doc)
        if fb is not None:
            self._attach_cpu_events(doc, fb)

    def unobserve(self, doc: int, callback) -> None:
        self._event_listeners[doc].remove(callback)
        if not self._event_listeners[doc]:
            del self._event_listeners[doc]

    def _attach_cpu_events(self, doc: int, fb: Doc) -> None:
        if getattr(fb, "_ytpu_events_attached", False):
            return
        fb._ytpu_events_attached = True
        from ..ids import find_root_type_key
        from ..types.events import YEvent, get_path_to

        def after_transaction(transaction, d, i=doc):
            cbs = self._event_listeners.get(i)
            if not cbs:
                return
            events = []
            for typ in transaction.changed:
                root = typ
                while root._item is not None:
                    root = root._item.parent
                ev = YEvent(typ, transaction)
                changes = ev.changes
                if not changes["delta"] and not changes["keys"]:
                    continue
                events.append({
                    "path": [find_root_type_key(root)]
                    + get_path_to(root, typ),
                    "delta": changes["delta"],
                    "keys": changes["keys"],
                })
            if events:
                for cb in cbs:
                    cb(i, events)

        fb.on("afterTransaction", after_transaction)

    def _emit(self, doc: int, update: bytes) -> None:
        self.obs.update_emitted(len(update))
        for cb in self._update_listeners:
            cb(doc, update)

    def _demote(
        self,
        doc: int,
        pre_sv: dict[int, int] | None = None,
        reason: str = "unspecified",
    ) -> Doc:
        """Move a doc to the CPU reference path by replaying its update log.

        When the doc is observed, the CPU event bridge attaches at the
        point of the replay where the pre-flush state vector is covered
        (the log prefix reproduces it exactly), so the demoting flush's
        own changes still deliver typed events — only historical replay
        stays silent."""
        self.demotions.append({"doc": doc, "reason": reason})
        self.obs.demoted(doc, reason)
        fb = Doc(gc=False)
        observed = doc in self._event_listeners
        attached = False
        if observed and not pre_sv:
            self._attach_cpu_events(doc, fb)
            attached = True
        for update, v2 in self._update_log[doc]:
            if observed and not attached:
                from ..core import get_state_vector

                sv = get_state_vector(fb.store)
                if all(sv.get(c, 0) >= v for c, v in pre_sv.items()):
                    self._attach_cpu_events(doc, fb)
                    attached = True
            try:
                (apply_update_v2 if v2 else apply_update)(fb, update)
            except Exception as e:
                # a log entry even the CPU reference core rejects cannot
                # be replayed anywhere: keep the bytes recoverable and
                # finish the demotion with the entries that do apply
                if self._strict:
                    raise
                self._dead_letter(
                    doc, update, v2, f"replay: {type(e).__name__}: {e}"
                )
        self.fallback[doc] = fb
        self.mirrors[doc] = DocMirror(self.root_name)  # dead mirror
        self._dirty_docs.discard(doc)
        plan_cache.note_invalidation("demote")
        self._update_log[doc] = []
        if self._update_listeners:
            # emit the demoting flush's novelty, then live-forward the
            # fallback doc's own update events
            from ..updates import encode_state_as_update, encode_state_vector
            from ..coding import DSEncoderV1
            from ..updates import write_state_vector

            enc_sv = None
            if pre_sv:
                e = DSEncoderV1()
                write_state_vector(e, pre_sv)
                enc_sv = e.to_bytes()
            novelty = encode_state_as_update(fb, enc_sv)
            if novelty:
                self._emit(doc, novelty)
        fb.on("update", lambda u, origin, d, i=doc: self._emit(i, u))
        if doc in self._event_listeners:
            self._attach_cpu_events(doc, fb)
        return fb

    def _isolate_failure(self, doc: int, exc: Exception, pre_sv=None) -> None:
        """Transactional per-doc rollback: contain one doc's failed
        integration without touching the rest of the batch.

        The update log is the transaction journal — every entry is
        re-validated, malformed entries are stripped to the dead-letter
        queue (bytes + reason preserved), and :meth:`_demote` replays
        the surviving prefix into a fresh CPU doc.  That replay IS the
        rollback: it rebuilds the doc's last good state, and replacing
        the mirror discards whatever poison its ``_incoming`` held, so
        the failure cannot re-wedge later flushes."""
        reason = f"{type(exc).__name__}: {exc}"
        clean: list[tuple[bytes, bool]] = []
        for update, v2 in self._update_log[doc]:
            try:
                validate_update(update, v2)
            except InvalidUpdate as ve:
                self._dead_letter(doc, update, v2, f"invalid-update: {ve}")
            else:
                clean.append((update, v2))
        self._update_log[doc] = clean
        self.rollbacks.append({"doc": doc, "reason": reason})
        self.obs.rollback(doc, reason)
        self.health.record_failure(doc, reason)
        self._demote(doc, pre_sv, reason=f"rollback: {reason}")

    def replay_dead_letters(
        self, doc: int | None = None, seqs=None, repair=None,
        readmit: bool = False, max_letters: int | None = None,
    ) -> dict:
        """Re-inject dead letters through the normal ingestion path.

        ``repair`` is an optional ``callable(DeadLetter) -> bytes | None``
        applied first: return fixed bytes to replay, or None to leave
        the letter queued (counted as requeued).  ``readmit=True``
        clears the targeted docs' health records first (operator
        override of quarantine backoff).  Letters that still fail
        validation or admission are re-dead-lettered and counted as
        failed.  Work per invocation is bounded: at most ``max_letters``
        (``YTPU_DLQ_REPLAY_BATCH``, default 256; 0 = unbounded) letters
        are taken, the rest stay queued and are reported as
        ``truncated`` (metered by
        ``ytpu_resilience_dlq_replay_truncated_total``) so a deep DLQ
        cannot stall a flush tick or an admission drain.  Returns
        ``{"replayed", "requeued", "failed", "truncated"}``."""
        if readmit:
            self.health.reset(doc)
        if max_letters is None:
            try:
                max_letters = int(
                    os.environ.get("YTPU_DLQ_REPLAY_BATCH", "256")
                )
            except ValueError:
                max_letters = 256
        cap = max_letters if max_letters and max_letters > 0 else None
        replayed = requeued = failed = 0
        truncated = 0
        if cap is not None:
            matching = self.dead_letters.count_matching(doc=doc, seqs=seqs)
            truncated = max(0, matching - cap)
        for e in self.dead_letters.take(doc=doc, seqs=seqs, limit=cap):
            update = e.update
            if repair is not None:
                fixed = repair(e)
                if fixed is None:
                    self.dead_letters.append(e.doc, e.update, e.v2, e.reason)
                    requeued += 1
                    continue
                update = bytes(fixed)
            try:
                validate_update(update, e.v2)
            except InvalidUpdate as ve:
                self._dead_letter(e.doc, update, e.v2, f"replay-invalid: {ve}")
                failed += 1
                continue
            if self.queue_update(e.doc, update, e.v2):
                replayed += 1
            else:
                failed += 1  # inadmissible: re-dead-lettered by queue_update
        self.obs.replayed(replayed)
        if truncated:
            self.obs.replay_truncated(truncated)
        return {
            "replayed": replayed,
            "requeued": requeued,
            "failed": failed,
            "truncated": truncated,
        }

    def resilience_snapshot(self) -> dict:
        """JSON-able view of the failure-isolation state (bench/expo)."""
        return {
            "strict": self._strict,
            "health": self.health.summary(),
            "docs": self.health.records(),
            "dead_letters": self.dead_letters.snapshot(),
            "n_rollbacks": len(self.rollbacks),
            "n_demotions": len(self.demotions),
        }

    # -- device placement ---------------------------------------------------

    def _put_b(self, x):
        """Place a batch-leading [B, ...] array: doc-axis sharded over the
        mesh, or the default device when unmeshed."""
        if self._ns_batch is None:
            return jnp.asarray(x)
        return jax.device_put(np.asarray(x), self._ns_batch)

    def _put_r(self, x):
        """Place an auxiliary array replicated over the mesh (or default
        device when unmeshed)."""
        if self._ns_repl is None:
            return jnp.asarray(x)
        return jax.device_put(np.asarray(x), self._ns_repl)

    # -- device state management -------------------------------------------

    def _ensure_capacity(self, n_rows: int, n_segs: int) -> None:
        cap = _bucket(n_rows)
        seg_cap = _bucket(n_segs, 8)
        if (
            cap <= self._cap
            and seg_cap <= self._seg_cap
            and self._right is not None
        ):
            return
        b = self.n_docs
        old_cap, old_seg = self._cap, self._seg_cap
        self._cap = max(cap, self._cap)
        self._seg_cap = max(seg_cap, self._seg_cap)

        # allocate/grow ON DEVICE (a fill compiles to a tiny program; a
        # host np.full would ship B*(cap+1) cells over the host link).  On
        # a mesh the fill is created doc-sharded: built unsharded first,
        # the whole [B, cap+1] table would sit on the default device.
        def fresh(shape, fill, dtype):
            return jnp.full(shape, fill, dtype, device=self._ns_batch)

        def grow(old, old_w, new_w, fill, dtype):
            out = fresh((b, new_w), fill, dtype)
            if old_w:
                out = jax.lax.dynamic_update_slice(
                    out, old[:, :old_w].astype(dtype), (0, 0)
                )
            return out

        if self._right is None:
            self._right = fresh((b, self._cap + 1), NULL, jnp.int32)
            self._deleted = fresh((b, self._cap + 1), False, jnp.bool_)
            self._starts = fresh((b, self._seg_cap + 1), NULL, jnp.int32)
            grown = (self._right, self._deleted, self._starts)
        else:
            # the table whose width changed, and no other: a room that
            # gains segments widens the list heads (a few KB a room), not
            # the rows; old scratch column (index old_cap) resets to
            # padding
            grown = ()
            if self._cap != old_cap:
                self._right = grow(
                    self._right, old_cap, self._cap + 1, NULL, jnp.int32
                )
                self._deleted = grow(
                    self._deleted, old_cap, self._cap + 1, False, jnp.bool_
                )
                grown += (self._right, self._deleted)
            if self._seg_cap != old_seg:
                self._starts = grow(
                    self._starts, old_seg, self._seg_cap + 1, NULL, jnp.int32
                )
                grown += (self._starts,)
        # donation bookkeeping: a grown table is a fresh allocation, so
        # this flush cannot have updated device state purely in place
        self._flush_realloc_bytes += sum(int(t.nbytes) for t in grown)

    # -- formatting clean-up --------------------------------------------------

    def _format_cleanup(self, metrics: dict) -> int:
        """What a ``Y.Doc`` does to its texts after a remote transaction
        (reference ``YText._callObserver``, YText.js:803-856;
        ``types/ytext.py``), for the rooms this flush planned: a flush
        is the engine's transaction.  A text is looked at if it is a
        nested ``Y.Text`` / ``Y.XmlText`` that existed before the flush
        and lives after it (a root's kind is not on the wire, so a root
        text is not cleaned, as a ``Y.Doc`` that never called
        ``get_text`` on it does not clean it), the flush added or
        deleted an item of it, and the flush brought a live format item
        (anywhere in the room) or deleted one of the text's.  Such a
        text takes ``cleanupYTextFormatting``: between two live strings
        every format item goes that the attributes in force after the
        gap do not owe to it, or that repeats what was in force before
        it.  (The reference's other branch, the contextless gap
        clean-up, reads the clean-up transaction's own, empty delete
        set and deletes nothing.)

        The deletions are queued as one delete-only update a room, which
        the flush's second round plans, dispatches and broadcasts like a
        peer's; they are in no journal (a recovery that replays the
        journal cleans again).  Only rooms whose plan says it brought or
        deleted a format item are looked at (``_cleanup_gate``).
        Returns the rooms an update was queued for."""
        gate, self._cleanup_gate = self._cleanup_gate, []
        queued = 0
        for doc, rows_before, plan in gate:
            if doc in self.fallback:
                continue
            m = self.mirrors[doc]
            found = None
            if isinstance(plan, np.ndarray):  # a native plan's counts row
                found = m.format_cleanup(rows_before)
                if found is None:
                    plan = m.make_plan(plan)
            ids, n_texts = found or _cleanup_room(m, rows_before, plan)
            metrics["format_cleanup_texts"] += n_texts
            if ids:
                metrics["format_cleanup_deleted"] += len(ids)
                if self.queue_update(doc, _delete_only_update(ids)):
                    queued += 1
        return queued

    # -- compaction ---------------------------------------------------------

    def _maybe_compact(self) -> tuple[int, int]:
        """Amortized run-merge + GC: when a doc's table doubles since its
        last compaction, rebuild the mirror + device state with adjacent
        runs merged (the engine-side analogue of the reference's
        per-transaction merge/GC passes,
        Transaction.js:165-238,299-332), if there is anything to merge.
        Keeps row count bounded by the doc's true run structure instead
        of its edit history.

        The look reads ``n_rows`` of the rooms planned since the last
        look (``_compact_look``) and of no other slot: a room's rows move
        only in a flush that planned it, in its own rebuild and in
        ``hydrate_doc_columns``, and the last two set
        ``_rows_at_compact`` themselves.  So a room compacts at the head
        of the first flush after the one that doubled it, whatever the
        number of slots.  ``compact_min_rows`` is read at the time of the
        look; one case differs from a scan of every slot: lowering it
        under a room that was NOT planned since the last look does not
        catch that room, which is looked at after its next update.

        The rooms that have doubled are then asked, in one native call,
        whether a rebuild would change them
        (``NativeMirror.compact_changes_many``: a row whose content
        ``gc`` would drop, two neighbours that merge).  A room that
        answers no is not rebuilt: the rebuild would write the rows,
        deleted bits and heads the tables already hold, under the same
        row numbers.  It gets the ``_rows_at_compact`` such a rebuild
        would have left it, so it is asked again when it has doubled from
        here; nothing else of it is touched (its rows keep their numbers,
        so its realized contents, its plan frontier and the plan cache's
        entries stay good).  A room loaded whole from one encoded state
        answers no, the encoder having merged its runs; a room that has
        been typed in answers yes at the first two keystrokes that merge.
        A room on a Python mirror is not asked and is rebuilt.  ``last_compaction`` is
        assigned only by a look that rebuilt a room.
        Returns the number of slots looked at (``rooms_compact_looked``)
        and of rooms asked and not rebuilt (``rooms_compact_skipped``)."""
        skipped = 0
        with self._phase_ctx("compact.scan"):
            looked = sorted(self._compact_look)
            self._compact_look.clear()
            floor = self.compact_min_rows
            n_rows = {
                i: self.mirrors[i].n_rows
                for i in looked
                if i not in self.fallback
            }
            todo = [
                i
                for i, n in n_rows.items()
                if n >= max(floor, 2 * self._rows_at_compact[i])
            ]
            if self._right is None:
                todo = []  # no table yet: nothing a rebuild could write
            ask = [
                i for i in todo if isinstance(self.mirrors[i], NativeMirror)
            ]
            if ask:
                changes = NativeMirror.compact_changes_many(
                    [self.mirrors[i] for i in ask], self.gc
                )
                same = {i for i, c in zip(ask, changes.tolist()) if not c}
                for i in same:
                    self._rows_at_compact[i] = n_rows[i]
                todo = [i for i in todo if i not in same]
                skipped = len(same)
        if todo:
            self.last_compaction = self._compact_rows(todo, self.gc)
        return len(looked), skipped

    def _compact_rows(self, todo: list[int], gc: bool) -> list[dict]:
        """Rebuild ``todo``'s mirrors compacted and scatter the new rows
        into the device tables; returns per-doc row stats, in ``todo``'s
        order.  Every room of ``todo`` is rebuilt, whether or not it has
        anything to merge: the forced pass (``compact_docs``) and the
        look's rooms that answered yes (``_maybe_compact``, which is
        where a room with nothing to merge is left out).

        The mirror's host list/deleted state equals the device arrays by
        flush invariant (YTPU_EXPORT_DEVICE pins it), so merges are
        decided WITHOUT any device read-back; the device gets the
        rebuilt rows in write-only scatters, each room staged as wide as
        it is BEFORE its rebuild (``rows_before``): the cells a room has
        written since its slot was last blanked are its first
        ``rows_before``, so that width blanks the stale tail behind the
        shorter rebuilt table, and every cell beyond it is at fill
        already.  Rooms of one width share a block
        (``_scatter_rebuilt``)."""
        stats = {}

        def rebuild(i):
            # a fresh rebuild supersedes any still-pending hydration
            self._pending_hydration.pop(i, None)
            m = self.mirrors[i]
            old_n = m.n_rows
            r, d, h = m.rebuild_compacted_self(gc)
            self._rows_at_compact[i] = len(r)
            stats[i] = {"doc": i, "rows_before": old_n, "rows_after": len(r)}
            return r, d, h

        mirrors = [self.mirrors[i] for i in todo]
        self._scatter_rebuilt(
            todo, rebuild,
            [m.n_rows for m in mirrors], [m.n_segs for m in mirrors],
        )
        return [stats[i] for i in todo]

    def _block_shapes(self, n_rows, n_segs):
        """The blocks that rooms holding ``n_rows[j]`` rows and
        ``n_segs[j]`` list heads are staged in, narrowest first, as
        ``(w, ws, k_min, members)``: a block ``w`` wide (its heads
        ``ws``) of ``k_min`` rows at least, for the rooms ``members``
        (indices into ``n_rows``).  The one staging rule of every whole-row write:
        compactions and hydrations (``_scatter_rebuilt``) and the load
        of rooms into empty slots (``_stage_row_loads``).

        The rooms are staged in width classes, one block a class.  A
        room's own width is ``_bucket(n_rows[j])`` (the power-of-two
        rule of the table widths, never more than the table's
        ``cap + 1``); a class is anchored at the widest room not yet in
        one and takes the rooms of that width and of half of it, so a
        block is at most twice as wide as any room in it, rooms that
        differ by a row across a power of two still share one (the
        widths a process meets, and the programs it compiles, stay few),
        and a long room widens the block of no room but its like.  A
        block's heads are as wide as its own rooms need.

        One device, a handful of short rooms (``_SERVED_ROOMS`` at most,
        none wider than ``_SERVED_WIDTH``: the amortized compactions of a
        served process, a room now and then as it doubles, or a room
        bound while others type): one block of exactly that shape, its
        spare rows aimed past the last slot, where the write drops them,
        its heads as wide as the table's.  A block's shape is a program,
        and which rooms double or arrive in one flush, and how long they
        are, is the traffic's: a process would go on meeting new shapes
        for as long as it serves (some hundred KB staged where some
        dozen would do, against seconds of compiling)."""
        own = [min(_bucket(n), self._cap + 1) for n in n_rows]
        if (
            self.mesh is None and len(own) <= _SERVED_ROOMS
            and max(own) <= _SERVED_WIDTH <= self._cap
        ):
            yield (
                _SERVED_WIDTH, self._seg_cap + 1, _SERVED_ROOMS,
                list(range(len(own))),
            )
            return
        width_of: dict[int, int] = {}
        for w in sorted(set(own), reverse=True):
            # half an anchor's width joins it; anything else anchors
            width_of[w] = 2 * w if width_of.get(2 * w) == 2 * w else w
        classes: dict[int, list[int]] = {}
        for j, w in enumerate(own):
            classes.setdefault(width_of[w], []).append(j)
        for w in sorted(classes):
            members = classes[w]
            ws = min(
                _bucket(max(n_segs[j] for j in members), 8),
                self._seg_cap + 1,
            )
            yield w, ws, 0, members

    def _scatter_rebuilt(self, todo, rebuild, n_rows, n_segs) -> None:
        """Stage rebuilt rooms and scatter them into the device tables:
        the one staging path of compactions and hydrations.

        ``rebuild(doc)`` gives a doc of ``todo`` its ``(right, deleted,
        heads)`` as ``rebuild_compacted_self`` does; ``n_rows[j]`` and
        ``n_segs[j]`` bound the rows and list heads slot ``todo[j]`` has
        held since it was last blanked.  The rooms are staged in the
        blocks ``_block_shapes`` gives, a row a room (the served block:
        ``k_min`` rows).  Each block is
        allocated, rebuilt, put and scattered by itself, narrowest
        first: host allocation, transfer and device scatter scale with
        what each room holds.  A room's block is at least as wide as the
        cells it has written, whatever class the others are in, so the
        tables come out as one block as wide as the widest room would
        leave them."""
        span = self._phase_ctx
        for w, ws, k_min, members in self._block_shapes(n_rows, n_segs):
            docs = [todo[j] for j in members]
            k = max(k_min, len(docs))
            with span("compact.alloc"):
                new_right = np.full((k, w), NULL, np.int32)
                new_deleted = np.zeros((k, w), bool)
                new_starts = np.full((k, ws), NULL, np.int32)
            held = 0
            with span("compact.rebuild"):
                for j, i in enumerate(docs):
                    r, d, h = rebuild(i)
                    new_right[j, : len(r)] = r
                    new_deleted[j, : len(d)] = d
                    new_starts[j, : len(h)] = h
                    held += r.nbytes + d.nbytes + h.nbytes
            self._flush_rows_staged_bytes += (
                new_right.nbytes + new_deleted.nbytes + new_starts.nbytes
            )
            self._flush_rows_held_bytes += held
            self._flush_rows_staged_blocks += 1
            with span("compact.put"):
                # a served block's spare rows: past the last slot
                docs = docs + [self.n_docs] * (k - len(docs))
                rows = (
                    self._put_r(np.asarray(docs, np.int32)),
                    self._put_r(new_right), self._put_r(new_deleted),
                    self._put_r(new_starts),
                )
            with span("compact.scatter"):
                self._dispatch("rows", *rows)

    def compact_docs(self, docs, gc: bool = True) -> list[dict]:
        """Forced tombstone/GC compaction of specific docs (the tier GC
        pass, ISSUE 7): rebuild their packed columns with gc'able
        deleted runs dropped NOW, regardless of the table-doubling
        heuristic — long-lived hot docs accumulate tombstones that the
        amortized pass never reaches.  Docs on the CPU fallback, with
        queued (unflushed) updates, or with no rows are skipped.
        Returns the same per-doc row stats as ``last_compaction``."""
        todo = [
            i
            for i in docs
            if i not in self.fallback
            and not self.mirrors[i]._incoming
            and self.mirrors[i].n_rows > 0
        ]
        if not todo or self._right is None:
            return []
        stats = self._compact_rows(todo, gc)
        self.last_compaction = stats
        return stats

    # -- doc eviction / tiering ---------------------------------------------

    def export_doc_columns(self, doc: int):
        """Detach and return slot ``doc``'s host mirror for warm tiering
        (ISSUE 7).  The mirror is self-contained host state — packed
        struct-of-arrays columns plus interned payloads, no engine
        references — so the caller can park it off-slot and re-install
        it later with :meth:`hydrate_doc_columns`.  Pair with
        :meth:`reset_doc` to actually free the slot.  Flush first:
        queued updates would stay behind in the slot's log."""
        if doc in self.fallback:
            raise ValueError(
                f"doc {doc} is CPU-served; its columns live in the "
                "fallback doc, not the packed tables"
            )
        if self.mirrors[doc]._incoming:
            raise RuntimeError(
                f"doc {doc} has un-integrated updates; flush before "
                "exporting"
            )
        return self.mirrors[doc]

    def hydrate_doc_columns(self, doc: int, mirror) -> dict:
        """Re-install an exported mirror into the (reset) slot ``doc``
        with NO decode round-trip (warm promotion, ISSUE 7): the host
        columns are rebuilt compacted and the device scatter is
        DEFERRED — it batches into the next flush dispatch (or the next
        device read-back) alongside any other pending hydrations, so
        the promotion itself is host-only work.  Statics lazily
        re-upload from row 0 on the next flush that needs them."""
        if doc in self.fallback:
            raise ValueError(f"doc {doc} is CPU-served; reset it first")
        if self.mirrors[doc].n_rows or self._update_log[doc]:
            raise RuntimeError(f"slot {doc} is not empty; reset_doc first")
        self.mirrors[doc] = mirror
        self._update_log[doc] = []
        r, d, h = mirror.rebuild_compacted_self(self.gc)
        self._ensure_capacity(max(1, len(r)), max(1, len(h)))
        self._pending_hydration[doc] = (r, d, h)
        self._rows_at_compact[doc] = len(r)
        if len(r):
            self._active_docs.add(doc)
        # the mirror may hold parked structs: the next flush looks
        self._dirty_docs.add(doc)
        return {"rows": len(r), "segs": len(h)}

    def _apply_pending_hydrations(self) -> None:
        """Scatter every deferred hydration into the device tables in
        ONE write-only pass, through ``_compact_rows``' staging.  Called
        at the top of flush and before any device read-back; a no-op
        when nothing is pending.

        ``reset_doc`` left each of these slots at fill (``hydrate_doc_
        columns`` refuses any other), so a room's block is as wide as
        the room it brings."""
        if not self._pending_hydration:
            return
        pend = self._pending_hydration
        self._pending_hydration = {}
        todo = sorted(pend)
        if self._right is None:
            self._ensure_capacity(1, 1)
        # hydrations land as stage-0 dispatches of the flush pipeline (or
        # immediately before a device read-back): the donating row scatter
        # sequences ahead of this flush's integrate dispatches on the
        # device stream, so the integrate kernels always see hydrated rows
        with self._phase_ctx("compact"):
            self._scatter_rebuilt(
                todo, pend.__getitem__,
                [len(pend[i][0]) for i in todo],
                [len(pend[i][2]) for i in todo],
            )

    def reset_doc(self, doc: int) -> None:
        """Return one slot to its just-constructed state (provider
        release_doc, ISSUE 3): fresh mirror, empty update log, cleared
        health record, and the device rows blanked to the same fills a
        new engine allocates — the slot's next tenant starts from
        nothing.  The dead-letter queue is NOT touched here (the caller
        decides whether the slot's letters travel with the evicted
        doc)."""
        if not 0 <= doc < self.n_docs:
            # the host list would wrap a negative index and the device
            # write clamps one out of range: neither may pick a row
            raise IndexError(f"reset_doc: no slot {doc} of {self.n_docs}")
        self.mirrors[doc] = make_mirror(self.root_name)
        plan_cache.note_invalidation("reset")
        self.fallback.pop(doc, None)
        self._pending_hydration.pop(doc, None)
        self._update_log[doc] = []
        self._rows_at_compact[doc] = 0
        self._active_docs.discard(doc)
        self._dirty_docs.discard(doc)
        self._compact_look.discard(doc)
        self._event_listeners.pop(doc, None)
        self.health.reset(doc)
        if self._right is not None:
            # blank the slot's whole device rows in place (same fills as
            # the initial allocation, scratch column included): one
            # donated program writes three rows, the tables are not
            # copied.  Dispatched here, not at the next flush: the slot
            # may be re-let as soon as this returns
            with self._phase_ctx("release.blank"):
                # np.int32: a traced scalar to jit and to the kernel
                # profiler alike (a Python int is a new signature a slot)
                self._right, self._deleted, self._starts = kernels.blank_rows(
                    self._right, self._deleted, self._starts, np.int32(doc)
                )
            self._release_blanked_bytes += (
                self._right.nbytes + self._deleted.nbytes + self._starts.nbytes
            ) // self.n_docs

    # -- flush: run one device integration step ----------------------------

    def _phase_ctx(self, name: str):
        """One flush phase or a part of one: a span of the program's one
        span API (obs/trace.py: the profiler's clock, and the host ring
        behind export_chrome_trace)."""
        return self.obs.tracer.span(f"ytpu.{name}")

    def _finish_flush(self, metrics: dict) -> None:
        """The single exit point of every flush path: append to the flush
        ring (which serves last_flush_metrics) + update the registry.
        Pipeline bookkeeping lands here so EVERY exit — bulk,
        replay, and the empty flush — emits the full shared schema."""
        pl = self._pl
        metrics["t_pack_overlap_s"] = pl.t_pack_overlap_s
        metrics["t_device_wait_s"] = pl.t_device_wait_s
        metrics["pipeline_depth"] = pl.max_depth
        # donated: every dispatch this flush updated resident tables in
        # place (no B*cap growth allocation anywhere in the flush)
        metrics["flush_donated"] = int(
            pl.n_dispatches > 0 and self._flush_realloc_bytes == 0
        )
        metrics["realloc_bytes"] = self._flush_realloc_bytes
        metrics["rows_staged_bytes"] = self._flush_rows_staged_bytes
        metrics["rows_held_bytes"] = self._flush_rows_held_bytes
        metrics["rows_staged_blocks"] = self._flush_rows_staged_blocks
        self._flush_rows_staged_bytes = self._flush_rows_held_bytes = 0
        self._flush_rows_staged_blocks = 0
        metrics["release_blanked_bytes"] = self._release_blanked_bytes
        self._release_blanked_bytes = 0
        metrics["seg_cap"] = self._seg_cap
        carry, self._flush_carry = self._flush_carry, None
        if carry is not None:
            # the second round of a flush whose clean-up deleted format
            # items: one record a flush, the rounds' counts and seconds
            # added up
            self._cleanup_gate = []
            _add_flush_round(metrics, carry)
        else:
            with self._phase_ctx("plan"), self._phase_ctx("plan.cleanup"):
                queued = self._format_cleanup(metrics)
            if queued:
                self._flush_carry = metrics
                try:
                    self._flush()
                finally:  # a round that raised has not taken it
                    self._flush_carry = None
                return
        self.obs.record_flush(metrics, row_capacity=self._cap)
        if self.obs.enabled:
            self._record_device_memory()

    def _record_device_memory(self) -> None:
        """Refresh the ytpu_prof device-memory gauges from the persistent
        device buffers (ISSUE 4 cost attribution).  Reads array metadata
        only — no device sync."""
        right = self._right
        if right is None:
            return
        tables = {
            "right_link": int(right.nbytes),
            "deleted": int(self._deleted.nbytes),
            "starts": int(self._starts.nbytes),
        }
        self.obs.device_memory(
            tables,
            next(iter(right.devices())).platform,
            len(self._active_docs) / max(1, self.n_docs),
        )

    def flush(self) -> None:
        with self.obs.tracer.span("ytpu.flush"):
            self._flush()
            # one flush = one health-clock tick (quarantine backoff is
            # counted in flushes, keeping re-admission deterministic)
            self.health.tick()

    def _flush(self) -> None:
        """One flush: compaction look, plan, pack, dispatch, emit.

        A flush looks at the rooms that took an update and at no other
        slot, so its fixed cost grows with the traffic and not with
        ``n_docs``: the plan phase visits ``_dirty_docs`` (fed by
        ``queue_update``; ``rooms_dirty`` in the metrics) in ascending
        slot order, and the compaction look reads the rooms planned
        since it last ran and rebuilds those of them that have doubled
        and have something to merge (``_maybe_compact``;
        ``rooms_compact_looked``, ``rooms_compact_skipped``).
        A room leaves the dirty set when a flush has taken its staged
        updates and it parks no struct; one that waits for a missing
        struct is visited by every flush until the struct arrives, and
        the rooms of a flush that raises stay for the retry."""
        t_start = time.perf_counter()
        # per-flush pipeline counters reset; the staging pair + in-flight
        # markers persist across flushes.  Sync (A/B) mode is re-read per
        # flush so tests can flip YTPU_FLUSH_PIPELINE between flushes.
        self._pl.begin_flush(sync=not _pipeline_on())
        self._flush_realloc_bytes = 0
        # deferred warm-promotion scatters land before anything reads or
        # integrates on top of the device link tables (pipeline stage 0)
        self._apply_pending_hydrations()
        with self._phase_ctx("compact"):
            n_looked, n_skipped = self._maybe_compact()
        t_compact = time.perf_counter()
        plans = {}
        pre_svs: dict[int, dict[int, int]] = {}
        demoted_now = 0
        rolled_back = 0
        cache_hits = cache_misses = cache_admitted = 0
        t_plan_cached = t_plan_cold = 0.0
        emitting = bool(self._update_listeners)
        observing = self._event_listeners
        # ONE device write path: the planner's final link values go up in
        # one conflict-free scatter (_flush_bulk).  With the native planner
        # ONE ymx_prepare_many call plans every staged doc; the Python
        # mirror keeps the doc loop.  Gate on planner availability, not any
        # particular doc's mirror: a demoted doc 0 must not silently
        # disable the fast path fleet-wide
        use_batch = native_plan_available() and any(
            isinstance(m, NativeMirror) for m in self.mirrors
        )
        work: list = []  # batched path: (doc, mirror)
        # the slots this flush visits, in ascending order: work (and with
        # it the chunks and their apply_plan2 lane keys) comes out as a
        # walk over every slot would build it
        dirty = sorted(self._dirty_docs)
        with self._phase_ctx("plan"):
            if use_batch:
                with self._phase_ctx("plan.walk"):
                    for i in dirty:
                        m = self.mirrors[i]
                        if i in self.fallback:
                            self._dirty_docs.discard(i)
                            continue
                        if not isinstance(m, NativeMirror):
                            continue  # the Python lane's room: kept for it
                        if not m._incoming and not m._had_pending:
                            self._dirty_docs.discard(i)
                            continue  # idle doc: nothing to plan or emit
                        if emitting or i in observing:
                            pre_svs[i] = m.state_vector()
                        work.append((i, m))
                plans = dict(work)  # presence for the empty-flush check
                self._compact_look.update(plans)
            else:
                # the Python planner's lane: a room at a time
                cache = plan_cache.get_cache()
                with self._phase_ctx("plan.walk"):
                    for i in dirty:
                        m = self.mirrors[i]
                        if i in self.fallback or (
                            not m._incoming and not m.has_pending()
                        ):
                            self._dirty_docs.discard(i)
                            continue  # idle doc: nothing to plan, upload, or emit
                        self._compact_look.add(i)
                        if emitting or i in observing:
                            pre_svs[i] = m.state_vector()
                        key = ent = None
                        if cache is not None:
                            key = m.plan_key()
                            ent = cache.lookup(key)
                        t_d0 = time.perf_counter()
                        if ent is not None:
                            # hit: replay the cached post-prepare snapshot
                            # onto this mirror instead of re-planning
                            if isinstance(m, NativeMirror):
                                plans[i] = m.make_plan(m.adopt_cached(ent))
                            else:
                                m2, plans[i] = ent.clone()
                                # keep the mirror's object identity (engine
                                # internals and tests may hold references)
                                m.__dict__.clear()
                                m.__dict__.update(m2.__dict__)
                            cache_hits += 1
                            t_plan_cached += time.perf_counter() - t_d0
                            continue
                        try:
                            plans[i] = m.prepare_step()
                        except UnsupportedUpdate as e:
                            self._demote(i, pre_svs.get(i), reason=str(e))
                            demoted_now += 1
                        except Exception as e:
                            # malformed bytes (or any integration fault):
                            # roll back and contain THIS doc; the rest of
                            # the batch flushes normally
                            if self._strict:
                                raise
                            self._isolate_failure(i, e, pre_svs.get(i))
                            demoted_now += 1
                            rolled_back += 1
                        else:
                            if key is not None:
                                cache_misses += 1
                                if isinstance(m, NativeMirror):
                                    cache_admitted += cache.insert_native(
                                        key, m, plans[i].counts
                                    )
                                else:
                                    cache_admitted += cache.insert_py(
                                        key, m, plans[i]
                                    )
                        t_plan_cold += time.perf_counter() - t_d0
        t_plan = time.perf_counter()
        # ONE schema (obs.FLUSH_METRICS_SCHEMA) for every exit: each path
        # overwrites only the fields it measures, so the key set cannot
        # drift between the batched, per-doc and empty-flush paths
        metrics = new_flush_metrics(
            n_demoted=demoted_now,
            n_rolled_back=rolled_back,
            n_fallback_docs=len(self.fallback),
            t_compact_s=t_compact - t_start,
            t_plan_s=t_plan - t_compact,
            t_plan_cached_s=t_plan_cached,
            t_plan_cold_s=t_plan_cold,
            plan_cache_hits=cache_hits,
            plan_cache_misses=cache_misses,
            plan_cache_admitted=cache_admitted,
            rooms_dirty=len(dirty),
            rooms_compact_looked=n_looked,
            rooms_compact_skipped=n_skipped,
            plan_fastpath_structs=sum(
                getattr(p, "fastpath_structs", 0) or 0
                for p in plans.values()
                if p is not None and not isinstance(p, NativeMirror)
            ),
            plan_segment_fast=sum(
                getattr(p, "segment_fast", 0) or 0
                for p in plans.values()
                if p is not None and not isinstance(p, NativeMirror)
            ),
            plan_segment_residue=sum(
                getattr(p, "segment_residue", 0) or 0
                for p in plans.values()
                if p is not None and not isinstance(p, NativeMirror)
            ),
        )
        if plans:
            self._flush_bulk(
                work if use_batch else sorted(plans.items()),
                pre_svs, emitting, metrics, t_start,
                observed=set(observing), native=use_batch,
            )
        else:
            metrics["t_total_s"] = time.perf_counter() - t_start
            self._finish_flush(metrics)
        # a planned room whose staged updates were taken and that parks
        # no struct asks nothing more of a flush; one a listener has just
        # fed, or one still waiting for a missing struct, stays.  (A
        # flush that raised never gets here: its rooms stay too.)
        for i in self._dirty_docs.intersection(plans):
            m = self.mirrors[i]
            if not m._incoming and not (
                m._had_pending if use_batch else m.has_pending()
            ):
                self._dirty_docs.discard(i)

    def _encode_steps(self, plans, pre_svs, counts, metrics) -> list:
        """The flush's broadcast updates as ``(doc, bytes | None)`` in
        ``plans`` order.  Every room whose mirror is a NativeMirror is
        encoded by ONE native call (``encode_steps_many``), its plan's
        applied delete set read where the planner left it; a room the
        native writer refuses (V2-framed or spilled payloads) and every
        room of the Python planner take ``encode_step_update``, as all did
        before.  Which is read off the room (its mirror's type, the
        call's return code), as ``use_batch`` is for plans."""
        batch = []
        for i, p in plans.items():
            m = self.mirrors[i]
            if isinstance(m, NativeMirror) and (
                p is None or isinstance(p, NativePlan)
            ):
                c = counts[i] if p is None else p.counts
                batch.append((i, m, int(c[15])))
        encoded: dict = {}
        if batch:
            updates, rcs = encode_steps_many(batch, pre_svs)
            encoded = {
                i: u
                for (i, _m, _s), u, rc in zip(batch, updates, rcs.tolist())
                if rc >= 0
            }
        out = []
        n_bytes = 0
        for i, p in plans.items():
            if i in encoded:
                u = encoded[i]
            else:
                m = self.mirrors[i]
                if p is None:
                    p = m.make_plan(counts[i])
                u = m.encode_step_update(pre_svs[i], p)
            if u is not None:
                n_bytes += len(u)
            out.append((i, u))
        metrics["emit_batched"] = len(encoded)
        metrics["emit_fallback"] = len(plans) - len(encoded)
        metrics["emit_bytes"] = n_bytes
        return out

    def _emit_phase(
        self, plans, pre_svs, emitting, metrics, observed=None, counts=None,
    ) -> None:
        """Post-dispatch host work shared by both dispatch paths: update-log
        compaction + doc.on('update') novelty emission (overlaps the async
        device execution).  ``observed`` restricts event computation to a
        prepare-time listener snapshot (the batched path may not have
        built plan.sched for docs unobserved at prepare).  ``counts`` maps
        a doc to its native plan's counts row where ``plans`` holds None
        for it (the native path builds plan objects for observed docs
        only)."""
        if self.health.tracked:
            # every doc that reached emit integrated cleanly this flush
            for i in plans:
                self.health.record_success(i)
        with self._phase_ctx("emit.fold"):
            for i in plans:
                m = self.mirrors[i]
                if len(self._update_log[i]) > 64 and not m.has_pending():
                    self._update_log[i] = [(m.encode_state_as_update(), False)]
        if emitting:
            # encode all, then fan out in order: no listener runs between
            # two rooms' encodes
            for i, u in self._encode_steps(plans, pre_svs, counts, metrics):
                if u is not None:
                    self._emit(i, u)
        if self._event_listeners:
            from .events import compute_flush_events

            for i, p in plans.items():
                if observed is not None and i not in observed:
                    continue
                cbs = self._event_listeners.get(i)
                if not cbs:
                    continue
                events = compute_flush_events(
                    self.mirrors[i], p, pre_svs[i]
                )
                if events:
                    for cb in cbs:
                        cb(i, events)

    def _dispatch(self, kind, *args, slot=None):
        """THE one flush dispatch path (ISSUE 12): every device mutation of
        the resident tables — bulk lanes (per-doc python plans, native
        batched plans, and cached-plan replay alike) and whole-row rebuild
        scatters (compaction, deferred hydration) — funnels through here,
        so the pipeline bookkeeping (in-flight markers, staging-buffer
        fences, sync A/B mode) and any future kernel change land exactly
        once.

        kinds:
          "lanes"   (lanes, key)                    bulk-apply scatter
          "load"    (idx, right, deleted, starts, sums)
                                                    bulk apply of rooms
                                                    loaded into empty
                                                    slots, as rows
          "rows"    (idx, right, deleted, starts)   whole-row rebuild

        ``slot`` ties the dispatch to the staging buffer it consumes (the
        double-buffered pair's reuse fence).  A "rows" block is
        device-placed by the caller (_put_r); lanes and "load" blocks
        are placed here, each shard's part on its own device."""
        dyn = (self._right, self._deleted, self._starts)
        if kind == "lanes":
            lanes, key = args
            k_dn, k_sp, k_h, k_d = key
            self._metrics_dev = None
            if self.mesh is not None:
                fn = self._sharded_apply.get(key)
                if fn is None:
                    from ..parallel.mesh import sharded_apply_plan

                    fn = sharded_apply_plan(
                        self.mesh, self.mesh.axis_names[0], *key
                    )
                    self._sharded_apply[key] = fn
                dyn, self._metrics_dev = fn(dyn, self._put_b(lanes))
            else:
                dyn = kernels.apply_plan2(
                    dyn, self._put_r(lanes[0]), k_dn, k_sp, k_h, k_d
                )
        elif kind == "load":
            self._metrics_dev = None
            if self.mesh is not None:
                if self._sharded_load is None:
                    from ..parallel.mesh import sharded_load_rows

                    self._sharded_load = sharded_load_rows(
                        self.mesh, self.mesh.axis_names[0]
                    )
                dyn, self._metrics_dev = self._sharded_load(
                    dyn, *(self._put_b(a) for a in args)
                )
            else:
                dyn = kernels.apply_plan2_rows(
                    dyn, *(self._put_r(a) for a in args[:4])
                )
        elif kind == "rows":
            idx, new_right, new_deleted, new_starts = args
            dyn = kernels.scatter_rows(
                *dyn, idx, new_right, new_deleted, new_starts
            )
        else:  # pragma: no cover - programming error
            raise ValueError(f"unknown dispatch kind {kind!r}")
        self._right, self._deleted, self._starts = dyn
        self._pl.dispatched(_done_token(self._starts), slot)

    def _flush_bulk(
        self, items, pre_svs, emitting, metrics, t_start,
        observed=frozenset(), native=True,
    ):
        """ONE bulk flush driver (tentpole, ISSUE 12): native batched
        plans (ymx_prepare_many / ymx_pack_apply), per-doc python plans,
        and cached-plan replay all stream through the same chunked
        pack -> dispatch pipeline.  Chunk k+1's host-side work (plan +
        pack into the double-buffered staging pair) overlaps chunk k's
        asynchronous device execution, and the donating apply kernels
        update the resident tables in place — steady-state flushes
        neither reallocate B*cap buffers nor block the host on the
        device.  YTPU_FLUSH_PIPELINE=0 restores the synchronous A/B
        lane (every dispatch blocks); output is byte-identical either
        way.

        ``items``: ``(doc, NativeMirror)`` pairs when ``native`` (planned
        here, chunk by chunk), ``(doc, plan)`` pairs otherwise (planned
        by _flush's plan phase, already doc-ordered)."""
        pl = self._pl
        chunk_sz = int(os.environ.get("YTPU_FLUSH_CHUNK", "256"))
        b = self.n_docs
        n_shards = 1 if self.mesh is None else self.mesh.shape[
            self.mesh.axis_names[0]
        ]
        b_loc = b // n_shards
        t_plan_acc = t_pack_acc = t_disp_acc = 0.0
        seg_base = plan_segment_stats() if native else (0, 0)
        stats_tot = np.zeros(4, np.int64)
        lanes_padded_tot = 0
        rooms_row_loaded = row_links = row_block_bytes = 0
        row_real = row_cells = 0  # what the row blocks hold, of their cells
        work_ok: list = []  # native: (doc, mirror, counts); py: (doc, plan)
        max_rows_all = 0
        acc = SimpleNamespace(
            cache=plan_cache.get_cache() if native else None,
            # events read plan.sched; skip building it otherwise
            want_sched=bool(self._event_listeners),
            cache_hits=0,
            cache_misses=0,
            cache_admitted=0,
            t_cached=0.0,
            t_cold=0.0,
            # ymx_prepare_many's own clock and counts, over the flush's
            # calls: a flush that planned nothing cold planned on one
            # thread and woke nobody
            times={
                **dict.fromkeys(PLAN_TIMES, 0.0),
                **dict.fromkeys(PLAN_POOL_COUNTS, 0),
                "plan_threads": 1,
            },
            demoted=metrics["n_demoted"],
            rolled_back=metrics["n_rolled_back"],
        )
        for c0 in range(0, len(items), chunk_sz):
            chunk = items[c0 : c0 + chunk_sz]
            t0 = time.perf_counter()
            if native:
                with self._phase_ctx("plan"):
                    chunk_ok = self._plan_chunk_native(chunk, pre_svs, acc)
            else:
                chunk_ok = chunk
            t1 = time.perf_counter()
            t_plan_acc += t1 - t0
            if not chunk_ok:
                continue
            with self._phase_ctx("pack"), pl.pack():
                pack = (
                    self._pack_chunk_native if native else self._pack_chunk_py
                )
                slot, key, stats, max_rows, loads = pack(
                    chunk_ok, b_loc, n_shards
                )
                stats_tot += stats
                max_rows_all = max(max_rows_all, max_rows)
                # capacity is per shard; real lane counts (stats) sum across
                # shards, so the denominator must too or meshed runs report
                # occupancy inflated by n_shards (ADVICE r4)
                lanes_padded_tot += n_shards * sum(key or ())
                for idx, *tables, sums in loads:
                    rooms_row_loaded += int((idx < b_loc).sum())
                    row_links += int(sums[:, 0].sum())
                    row_real += int(sums.sum())
                    row_cells += sum(t.size for t in tables)
                    row_block_bytes += sum(t.nbytes for t in tables)
                work_ok.extend(chunk_ok)
            t2 = time.perf_counter()
            t_pack_acc += t2 - t1
            # async dispatch: the device consumes this chunk's staged lanes
            # and row blocks (they write different rooms) while the next
            # loop iteration plans and packs on the host (the staging slot
            # fences its buffer against premature reuse; a row block is
            # staged anew a chunk)
            with self._phase_ctx("dispatch"):
                if slot is not None:
                    self._dispatch("lanes", slot.buf, key, slot=slot)
                for block in loads:
                    self._dispatch("load", *block)
            t_disp_acc += time.perf_counter() - t2
        metrics["n_demoted"] = acc.demoted
        metrics["n_rolled_back"] = acc.rolled_back
        t_dispatch = time.perf_counter()
        with self._phase_ctx("emit"):
            if native:
                # real plan objects only where the emit phase will read
                # them: observed docs, for events (the batched encode
                # reads a plan's delete set in the core, by its counts
                # row); the log-compaction walk touches keys only.  The
                # observed set is the PREPARE-TIME snapshot: a
                # listener registered mid-flush (e.g. from an update
                # callback) sees events from the next flush — plan.sched
                # for this one may not have been built (want_sched gate)
                plans = {
                    i: (m.make_plan(c) if i in observed else None)
                    for i, m, c in work_ok
                }
                self._emit_phase(
                    plans, pre_svs, emitting, metrics, observed=observed,
                    counts={i: c for i, _m, c in work_ok},
                )
            else:
                self._emit_phase(dict(work_ok), pre_svs, emitting, metrics)
        t_emit = time.perf_counter()

        if native:
            counts = (
                np.stack([c for _, _, c in work_ok])
                if work_ok
                else np.zeros((0, 16), np.int64)
            )
            n_flushed = int(
                ((counts[:, 12] > 0) | (counts[:, 13] > 0)
                 | (counts[:, 6] > 0)).sum()
            )
            pending_mask = counts[:, 8] == 1
            n_pending = int(pending_mask.sum())
            pending_depth = int(counts[pending_mask, 9].sum())
            kinds = _kind_counts(counts)
            n_segs_max = int(counts[:, 11].max(initial=0))
            rows_before = counts[:, 0] - kinds["rows_planned"] - counts[:, 1]
            self._cleanup_gate = [
                (work_ok[k][0], int(rows_before[k]), work_ok[k][2])
                for k in np.flatnonzero(
                    (kinds["rows_format"] > 0) | (kinds["format_deleted"] > 0)
                )
            ]
            kinds = {k: int(v.sum()) for k, v in kinds.items()}
        else:
            kinds = dict.fromkeys(_KIND_KEYS, 0)
            n_segs_max = 0
            self._cleanup_gate = []
            for i, p in work_ok:
                m = self.mirrors[i]
                if isinstance(p, NativePlan):  # a native room in this lane
                    one = {
                        k: int(v[0])
                        for k, v in _kind_counts(p.counts[None]).items()
                    }
                    held = p.counts
                else:
                    one, held = _kind_counts_py(m, p), p
                for k, v in one.items():
                    kinds[k] += v
                n_segs_max = max(n_segs_max, m.n_segs)
                if one["rows_format"] or one["format_deleted"]:
                    self._cleanup_gate.append((
                        i, p.n_rows - one["rows_planned"] - len(p.splits), held
                    ))
            n_flushed = sum(
                1
                for _, p in work_ok
                if len(p.link_rows) or len(p.head_segs) or len(p.delete_rows)
            )
            pending = [
                i for i, _ in work_ok if self.mirrors[i].has_pending()
            ]
            n_pending = len(pending)
            pending_depth = sum(
                self.mirrors[i].pending_depth() for i in pending
            )
        n_dense, n_sparse, n_heads, n_dels = (int(x) for x in stats_tot)
        lanes_real = n_dense + n_sparse + n_heads + n_dels
        metrics.update({
            "n_docs_flushed": n_flushed,
            "n_rows_max": max_rows_all,
            # real links, whichever way they went: lanes or row blocks
            "n_sched_entries": n_dense + n_sparse + row_links,
            "lane_links": n_dense + n_sparse,
            "row_links": row_links,
            "lanes_dispatched": lanes_padded_tot,
            "rooms_row_loaded": rooms_row_loaded,
            "row_block_bytes": row_block_bytes,
            # bulk path: fraction of what was staged that is real: of
            # the scatter lanes, and of the row blocks' cells the links,
            # tombstones and heads they hold
            "schedule_occupancy": (
                (lanes_real + row_real) / (lanes_padded_tot + row_cells)
                if lanes_padded_tot + row_cells else 0.0
            ),
            "n_pending_docs": n_pending,
            "pending_depth": pending_depth,
            "t_pack_s": t_pack_acc,
            "t_dispatch_s": t_disp_acc,
            "t_emit_s": t_emit - t_dispatch,
            "t_total_s": t_emit - t_start,
            "n_segs_max": n_segs_max,
            **kinds,
        })
        if native:
            metrics.update({
                "t_plan_s": t_plan_acc,
                "t_plan_cached_s": acc.t_cached,
                "t_plan_cold_s": acc.t_cold,
                "plan_cache_hits": acc.cache_hits,
                "plan_cache_misses": acc.cache_misses,
                "plan_cache_admitted": acc.cache_admitted,
                # plan_threads among them: the most threads one native
                # call of this flush planned on, the caller included (the
                # core's own rule, plancore.cpp); 1 when every doc was
                # served from the plan cache
                **acc.times,
            })
            seg_now = plan_segment_stats()
            metrics["plan_segment_fast"] = max(0, seg_now[0] - seg_base[0])
            metrics["plan_segment_residue"] = max(
                0, seg_now[1] - seg_base[1]
            )
        self._finish_flush(metrics)

    def _plan_chunk_native(self, chunk, pre_svs, acc):
        """Plan one chunk of ``(doc, NativeMirror)`` work: cache hits
        adopt the cached post-prepare snapshot, cold group leaders plan
        via ONE ymx_prepare_many call, trailing same-key members clone
        their leader.  Per-doc error policy (demote / rollback) matches
        the python plan loop exactly; ``acc`` accumulates plan-phase
        bookkeeping across chunks.  Returns the surviving
        ``(doc, mirror, counts)`` triples in ascending doc order."""
        cache = acc.cache
        want_sched = acc.want_sched
        chunk_ok: list = []
        hits: list = []    # (doc, mirror, entry)
        cold: list = []    # (doc, mirror, key) — group leaders
        groups: dict = {}  # key -> trailing same-key members
        if cache is not None:
            with self._phase_ctx("plan.keys"):
                for i, m in chunk:
                    key = m.plan_key(want_sched)
                    g = groups.get(key)
                    if g is not None:
                        # intra-chunk duplicate (broadcast fan-out):
                        # cloned from the leader after it plans
                        g.append((i, m))
                        continue
                    ent = cache.lookup(key)
                    if ent is not None:
                        hits.append((i, m, ent))
                    else:
                        groups[key] = []
                        cold.append((i, m, key))
        else:
            cold = [(i, m, None) for i, m in chunk]
        th0 = time.perf_counter()
        for i, m, ent in hits:
            chunk_ok.append((i, m, m.adopt_cached(ent)))
        acc.cache_hits += len(hits)
        acc.t_cached += time.perf_counter() - th0
        retry: list = []  # members whose leader failed
        if cold:
            tc0 = time.perf_counter()
            acc.cache_misses += len(cold)
            counts_all, rcs, staged_info, pool_times = prepare_many(
                [(i, m) for i, m, _k in cold],
                want_sched=want_sched,
                obs=self.obs,
            )
            _add_plan_times(acc.times, pool_times)
            with self._phase_ctx("plan.finish"):
                for k, (i, m, key) in enumerate(cold):
                    try:
                        m._finish_prepare(
                            int(rcs[k]), staged_info[k][0],
                            staged_info[k][1], counts_all[k],
                        )
                    except UnsupportedUpdate as e:
                        self._demote(i, pre_svs.get(i), reason=str(e))
                        acc.demoted += 1
                        retry.extend(groups.get(key, ()))
                    except Exception as e:
                        if self._strict:
                            raise
                        self._isolate_failure(i, e, pre_svs.get(i))
                        acc.demoted += 1
                        acc.rolled_back += 1
                        retry.extend(groups.get(key, ()))
                    else:
                        chunk_ok.append((i, m, counts_all[k]))
                        members = groups.get(key)
                        if members:
                            # identical frontier + staged bytes plan
                            # identically: clone the leader's live
                            # post-prepare state instead of
                            # re-walking each member
                            th1 = time.perf_counter()
                            src = SimpleNamespace(
                                h=m._h,
                                counts=counts_all[k],
                                pins=m._py_bufs,
                                frontier_after=m.plan_frontier,
                            )
                            for j, mj in members:
                                chunk_ok.append(
                                    (j, mj, mj.adopt_cached(src))
                                )
                            acc.cache_hits += len(members)
                            plan_cache.note_hits(len(members))
                            acc.t_cached += time.perf_counter() - th1
                        if key is not None:
                            # post-prepare, pre-pack: the snapshot a
                            # future hit adopts before running the
                            # pack/dispatch phases itself (taken from a
                            # key's second sighting on)
                            acc.cache_admitted += cache.insert_native(
                                key, m, counts_all[k]
                            )
            acc.t_cold += time.perf_counter() - tc0
        if retry:
            # a leader's demote/isolate says nothing about its
            # members under the per-doc error policy — plan each
            # individually, exactly as a cache-off flush would
            tc0 = time.perf_counter()
            acc.cache_misses += len(retry)
            plan_cache.note_misses(len(retry))
            counts2, rcs2, staged2, pool_times = prepare_many(
                retry, want_sched=want_sched, obs=self.obs,
            )
            _add_plan_times(acc.times, pool_times)
            with self._phase_ctx("plan.finish"):
                for k, (i, m) in enumerate(retry):
                    try:
                        m._finish_prepare(
                            int(rcs2[k]), staged2[k][0], staged2[k][1],
                            counts2[k],
                        )
                    except UnsupportedUpdate as e:
                        self._demote(i, pre_svs.get(i), reason=str(e))
                        acc.demoted += 1
                    except Exception as e:
                        if self._strict:
                            raise
                        self._isolate_failure(i, e, pre_svs.get(i))
                        acc.demoted += 1
                        acc.rolled_back += 1
                    else:
                        chunk_ok.append((i, m, counts2[k]))
            acc.t_cold += time.perf_counter() - tc0
        # hit/leader/member completion order is cache-dependent;
        # pack and emit must see the same doc order either way
        chunk_ok.sort(key=lambda t: t[0])
        return chunk_ok

    def _covering_key(self, key):
        """The lane widths a chunk is packed and dispatched at: a key
        this packer has issued before whose lanes cover the chunk's is
        issued again before a new one, which the dispatcher would have
        to compile (seconds on a chip against microseconds of padding).

        On a mesh a width is the widest shard's sum, and which rooms
        share a shard changes whenever freed slots are re-let, so the
        same rooms wander over a bucket's edge from one flush to the
        next: an issued key covers at no more than a quarter more lanes
        (two steps of ``_bucket_lanes``).  On one device the same rooms
        always give the same key, and since PR 44 a room loaded whole
        goes as a row block and has no key at all: a bulk flush's lanes
        (more than ``_SERVED_LANES``) are returning sessions' offline
        histories merged into rooms that hold rows.  A width is a sum
        over the rooms of that flush, and the next flush of the kind
        sums other histories.  Links and deletes, sums of thousands a
        room, come out within half a percent of the last and keep their
        step of ``_bucket_lanes``; the list heads (the inserts that
        landed before a room's first row: 23 to 30 in ten such flushes,
        5 to 9 in the flush after) scatter as a Poisson count does,
        over several steps, and each new key is a program of 10-26 s to
        compile at these widths.  So there too an issued key covers at
        a quarter more lanes, and a key that has to be new leaves its
        list heads six times their square root of room.
        A served flush (``_SERVED_LANES`` lanes or fewer: some dozens
        of rooms' keystrokes) wanders too, in each of its four widths by
        itself (links with the characters typed, list heads with the
        nodes split, deletes with the backspaces), and the product of
        their buckets is more programs than a process should meet: there
        a width that has to be new is a power of two, and an issued key
        covers at a quarter or ``_SERVED_PAD`` lanes more, whichever is
        more."""
        if key in self._mesh_keys:
            return key
        lanes = sum(key)
        bulk = self.mesh is None and lanes > _SERVED_LANES
        if self.mesh is None and not bulk:
            # a new width is a power of two: the widest flush a process
            # has met keeps being outdone by a lane or two for as long
            # as it runs, and every such record would be a program
            key = tuple(_bucket(k, 8) for k in key)
            if key in self._mesh_keys:
                return key
            lanes = sum(key)
        most = 5 * lanes // 4
        if self.mesh is None:
            most = max(most, lanes + _SERVED_PAD)
        fits = [
            k for k in self._mesh_keys
            if all(a >= b for a, b in zip(k, key)) and sum(k) <= most
        ]
        if fits:
            return min(fits, key=sum)
        if bulk:
            k_h = key[2]
            key = (*key[:2], _bucket_lanes(k_h + 6 * math.isqrt(k_h), 8), key[3])
        self._mesh_keys.add(key)
        return key

    def _stage_row_loads(self, doc_idx, sizes, b_loc, n_shards, fill):
        """Stage the rooms a chunk loads whole into empty slots as row
        blocks: what ``_dispatch("load", ...)`` writes, one block a width
        class of ``_block_shapes`` (so a 100,000-row room widens no
        block but its like).

        A room is taken this way only where its plan says both that it
        writes every row the room has (dense links: rows ``0..k-1``) and
        that the mirror held no row before the step (``from_empty``: a
        room bound, reloaded after ``reset_doc``, or recovered).  A slot
        whose mirror holds no row is at fill on the device in every cell
        (``kernels.apply_plan2_rows`` says why), so the ``NULL`` a block
        carries behind a room's rows changes nothing, and the tombstones
        of the plan are all the room has.  A room that had rows keeps
        the element lanes: a row write would wipe its older tombstones.

        ``doc_idx``: ascending slots; ``sizes``: a room a row, its rows,
        segments, links, tombstones and heads; ``fill(members, pos, right,
        deleted, starts)`` writes every cell of rows ``pos`` from
        the rooms ``members`` (indices into ``doc_idx``).  A block holds a part of
        ``k`` rows for each shard (one device: one part), ``k`` the
        widest shard's rooms rounded up as lane widths are
        (``_bucket_lanes``: the shapes a process meets stay few where a
        chunk's long rooms come to one more or less); its links and
        heads travel int16 where it is no wider than 32767 (each is a
        row of its own room).  Returns ``(idx, right, deleted, starts,
        sums)`` per block: ``idx`` the slots local to their shard, a
        spare row aimed past the shard's last; ``sums`` ``[n_shards,
        3]``, the links, tombstones and heads of each shard's part."""
        blocks = []
        shapes = self._block_shapes(sizes[:, 0], sizes[:, 1])
        for w, ws, k_min, members in shapes:
            members = np.asarray(members)
            shard, local = np.divmod(doc_idx[members], b_loc)
            per = np.bincount(shard, minlength=n_shards)
            k = max(k_min, _bucket_lanes(int(per.max()), 1))
            # a room's row: its shard's part, then its rank in the part
            first = np.cumsum(per) - per
            pos = shard * k + np.arange(len(members)) - first[shard]
            idx = np.full(n_shards * k, b_loc, np.int32)
            idx[pos] = local
            dtype = np.int16 if w <= 32767 else np.int32
            right = np.empty((n_shards * k, w), dtype)
            deleted = np.empty((n_shards * k, w), bool)
            starts = np.empty((n_shards * k, ws), dtype)
            fill(members, pos, right, deleted, starts)
            sums = np.zeros((n_shards, 3), np.int32)
            np.add.at(sums, shard, sizes[members, 2:])
            blocks.append((idx, right, deleted, starts, sums))
        return blocks

    def _pack_chunk_native(self, chunk_ok, b_loc, n_shards):
        """Stage one planned native chunk: grow capacity, stage the
        rooms loaded whole into empty slots as row blocks
        (``_stage_row_loads``), and for the others size the per-shard
        lane widths, pick the int16 downshift, and run the native pack
        (ymx_pack_apply) writing straight into the acquired staging
        buffer.  Returns ``(slot, key, stats, max_rows, loads)``;
        ``slot`` and ``key`` are None where every room went as a row."""
        counts = np.stack([c for _, _, c in chunk_ok])
        doc_idx = np.asarray([i for i, _, _ in chunk_ok], np.int64)
        max_rows = int(counts[:, 0].max(initial=0))
        self._ensure_capacity(
            max_rows, int(counts[:, 11].max(initial=0))
        )
        # the step's shape (plancore.cpp plan_shape): 3 is dense links
        # into a mirror that held no row
        whole = counts[:, 14] == 3
        loads = []
        if whole.any():
            rooms = np.flatnonzero(whole)

            def fill_rows(members, pos, right, deleted, starts):
                pack_row_blocks(
                    [chunk_ok[j] for j in rooms[members]], pos,
                    right, deleted, starts, int(NULL),
                )

            with self._phase_ctx("pack.rows"):
                loads = self._stage_row_loads(
                    doc_idx[rooms], counts[rooms][:, [0, 11, 12, 6, 13]],
                    b_loc, n_shards, fill_rows,
                )
            if whole.all():
                return None, None, np.zeros(4, np.int64), max_rows, loads
            rest = np.flatnonzero(~whole)
            chunk_ok = [chunk_ok[j] for j in rest]
            counts, doc_idx = counts[rest], doc_idx[rest]
        with self._phase_ctx("pack.lanes"):
            slot, key, stats = self._stage_lanes_native(
                chunk_ok, counts, doc_idx, b_loc, n_shards
            )
        return slot, key, stats, max_rows, loads

    def _stage_lanes_native(self, chunk_ok, counts, doc_idx, b_loc, n_shards):
        """The element lanes of the rooms of a native chunk that held
        rows: sized, keyed and packed into a staging buffer.  Returns
        ``(slot, key, stats)``."""
        oob_r = int(self._cap + 1)
        oob_s = int(self._seg_cap + 1)
        shard = doc_idx // b_loc
        link = counts[:, 12]
        dense = (counts[:, 14] & 1).astype(bool)

        def shard_max(values, mask, minimum, shard=shard):
            sums = np.bincount(
                shard[mask], weights=values[mask].astype(np.float64),
                minlength=n_shards,
            )
            return _bucket_lanes(int(sums.max(initial=0)), minimum)

        all_mask = np.ones(len(chunk_ok), bool)
        k_dn = shard_max(link, dense, 64)
        k_sp = shard_max(link, ~dense, 64)
        k_h = shard_max(counts[:, 13], all_mask, 8)
        k_d = shard_max(counts[:, 6], all_mask, 64)
        # int16 lanes when every index/count fits: half the flush
        # bytes over the host->device link
        lane_dtype = (
            np.int16
            if max(oob_r, oob_s, int(link.max(initial=0))) <= 32767
            else np.int32
        )
        key = k_dn, k_sp, k_h, k_d = self._covering_key(
            (k_dn, k_sp, k_h, k_d)
        )
        lane_w = 4 * b_loc + k_dn + 2 * k_sp + 2 * k_h + k_d
        slot = self._pl.acquire((n_shards, lane_w), lane_dtype)
        lanes, stats = pack_apply_lanes(
            chunk_ok, doc_idx, b_loc, n_shards, key,
            oob_r, oob_s, int(NULL), lane_dtype, out=slot.buf,
        )
        slot.buf = lanes
        return slot, key, stats

    def _pack_chunk_py(self, chunk_ok, b_loc, n_shards):
        """Python-mirror twin of :meth:`_pack_chunk_native`: bin one
        chunk of ``(doc, plan)`` pairs into the same counts-header +
        lanes layout (host-resolved YATA; see DocMirror._list_insert /
        plancore.cpp list_insert), packing into the acquired staging
        buffer; the rooms loaded whole into empty slots go as row blocks
        there too.  Returns ``(slot, key, stats, max_rows, loads)``.

        Per-doc counts ride in the lanes header; doc ids and dense row
        indices are derived ON DEVICE (kernels.apply_plan2), so the
        transfer carries the minimum: full-table ("dense") link loads
        ship values only.  One binning "shard" on a single device; the
        mesh path bins per device shard so each scatters its own lanes
        locally."""
        max_rows = max((p.n_rows for _, p in chunk_ok), default=0)
        max_segs = max(
            (self.mirrors[i].n_segs for i, _ in chunk_ok), default=0
        )
        self._ensure_capacity(max_rows, max_segs)
        is_whole = [p.from_empty and _dense_links(p) for _, p in chunk_ok]
        whole = [t for t, yes in zip(chunk_ok, is_whole) if yes]
        loads = []
        if whole:

            def fill_rows(members, pos, right, deleted, starts):
                right[pos] = NULL
                deleted[pos] = False
                starts[pos] = NULL
                for j, at in zip(members, pos):
                    p = whole[j][1]
                    right[at, : len(p.link_vals)] = p.link_vals
                    deleted[at, np.asarray(p.delete_rows, np.int64)] = True
                    starts[at, np.asarray(p.head_segs, np.int64)] = (
                        p.head_vals
                    )

            with self._phase_ctx("pack.rows"):
                loads = self._stage_row_loads(
                    np.asarray([i for i, _ in whole], np.int64),
                    np.asarray([
                        (p.n_rows, self.mirrors[i].n_segs, len(p.link_rows),
                         len(p.delete_rows), len(p.head_segs))
                        for i, p in whole
                    ], np.int64),
                    b_loc, n_shards, fill_rows,
                )
            if len(whole) == len(chunk_ok):
                return None, None, np.zeros(4, np.int64), max_rows, loads
            chunk_ok = [t for t, yes in zip(chunk_ok, is_whole) if not yes]
        with self._phase_ctx("pack.lanes"):
            slot, key, stats = self._stage_lanes_py(chunk_ok, b_loc, n_shards)
        return slot, key, stats, max_rows, loads

    def _stage_lanes_py(self, chunk_ok, b_loc, n_shards):
        """Python-mirror twin of :meth:`_stage_lanes_native`."""
        oob_r = np.int32(self._cap + 1)
        counts = np.zeros((n_shards, 4, b_loc), np.int32)
        dense = [[] for _ in range(n_shards)]
        sp_r = [[] for _ in range(n_shards)]
        sp_v = [[] for _ in range(n_shards)]
        hd_s = [[] for _ in range(n_shards)]
        hd_v = [[] for _ in range(n_shards)]
        dl_r = [[] for _ in range(n_shards)]
        for i, p in chunk_ok:
            s, li = divmod(i, b_loc)
            k = len(p.link_rows)
            rows = np.asarray(p.link_rows, np.int32)
            vals = np.asarray(p.link_vals, np.int32)
            if _dense_links(p):
                counts[s, 0, li] = k
                dense[s].append(vals)
            elif k:
                counts[s, 1, li] = k
                sp_r[s].append(rows)
                sp_v[s].append(vals)
            hn = len(p.head_segs)
            if hn:
                counts[s, 2, li] = hn
                hd_s[s].append(np.asarray(p.head_segs, np.int32))
                hd_v[s].append(np.asarray(p.head_vals, np.int32))
            dn = len(p.delete_rows)
            if dn:
                counts[s, 3, li] = dn
                dl_r[s].append(np.asarray(p.delete_rows, np.int32))

        def widths(parts_by_shard, minimum):
            return _bucket_lanes(
                max(
                    (sum(len(a) for a in parts) for parts in parts_by_shard),
                    default=0,
                ),
                minimum,
            )

        k_dn = widths(dense, 64)
        k_sp = widths(sp_r, 64)
        k_h = widths(hd_s, 8)
        k_d = widths(dl_r, 64)
        k_dn, k_sp, k_h, k_d = self._covering_key((k_dn, k_sp, k_h, k_d))
        oob_s = np.int32(self._seg_cap + 1)

        def fill(out, parts, pad_val):
            flat = (
                np.concatenate(parts) if parts else np.zeros(0, np.int32)
            )
            out[: len(flat)] = flat
            out[len(flat):] = pad_val
            return len(flat)

        lane_w = 4 * b_loc + k_dn + 2 * k_sp + 2 * k_h + k_d
        slot = self._pl.acquire((n_shards, lane_w), np.int32)
        lanes = slot.buf
        n_dense = n_sparse = n_heads = n_dels = 0
        for s in range(n_shards):
            o = 0
            lanes[s, : 4 * b_loc] = counts[s].ravel()
            o = 4 * b_loc
            n_dense += fill(lanes[s, o : o + k_dn], dense[s], NULL)
            o += k_dn
            n_sparse += fill(lanes[s, o : o + k_sp], sp_r[s], oob_r)
            fill(lanes[s, o + k_sp : o + 2 * k_sp], sp_v[s], NULL)
            o += 2 * k_sp
            n_heads += fill(lanes[s, o : o + k_h], hd_s[s], oob_s)
            fill(lanes[s, o + k_h : o + 2 * k_h], hd_v[s], NULL)
            o += 2 * k_h
            n_dels += fill(lanes[s, o : o + k_d], dl_r[s], oob_r)
        stats = np.asarray([n_dense, n_sparse, n_heads, n_dels], np.int64)
        return slot, (k_dn, k_sp, k_h, k_d), stats

    @property
    def last_flush_metrics(self) -> dict | None:
        """Host-side per-phase timers + batch stats of the newest flush —
        the compatibility view over the obs flush-history ring (the SAME
        dict object as ``obs.history.latest``; the ring keeps the last
        ``YTPU_OBS_HISTORY`` flushes)."""
        return self.obs.history.latest

    @property
    def last_metrics(self) -> dict | None:
        """Global psum'd counters of the last sharded dispatch (syncs)."""
        if self._metrics_dev is None:
            return None
        return {k: int(v) for k, v in self._metrics_dev.items()}

    # -- observability exposition -------------------------------------------

    def metrics_text(self) -> str:
        """Prometheus exposition-format dump of the engine registry merged
        with the process-global one (sync protocol counters)."""
        return self.obs.metrics_text()

    def metrics_snapshot(self) -> dict:
        """JSON-able snapshot: registry contents + newest flush metrics +
        the full flush-history ring."""
        return self.obs.snapshot()

    def export_chrome_trace(self) -> dict:
        """Chrome-trace JSON of recorded host spans — loadable by Perfetto
        / chrome://tracing.  Complements jax.profiler device traces."""
        return self.obs.tracer.chrome_trace()

    def save_trace(self, path: str) -> str:
        """Write export_chrome_trace() to ``path``; returns the path."""
        return self.obs.tracer.save(path)

    # -- exports ------------------------------------------------------------

    def state_vector(self, doc: int) -> dict[int, int]:
        fb = self.fallback.get(doc)
        if fb is not None:
            from ..core import get_state_vector

            return {c: v for c, v in get_state_vector(fb.store).items()}
        return self.mirrors[doc].state_vector()

    def _order(self, doc: int, seg: int) -> tuple[np.ndarray, np.ndarray]:
        """Segment-order row ids + deleted flags for one doc's segment.

        Host path (default): walk the planner's linked list — no device
        round trip (the r2 "per-doc dispatches in exports" weakness).
        Device path (export_from_device): rank the doc's resident right
        links with the pointer-doubling kernel and read back — exports
        then PROVE the device state, which is how the test suite runs.
        """
        m = self.mirrors[doc]
        if not self.export_from_device:
            rows_l: list = []
            dele_l: list = []
            host_deleted = m._host_deleted_rows
            nxt = m.list_next
            r = m.head_of_seg[seg] if seg < len(m.head_of_seg) else NULL
            while r != NULL:
                r = int(r)
                rows_l.append(r)
                dele_l.append(r in host_deleted)
                r = nxt[r]
            return np.asarray(rows_l, np.int64), np.asarray(dele_l, bool)
        if self._right is None:
            return np.zeros(0, np.int64), np.zeros(0, bool)
        held = self._export_rows
        if held is not None and held[0] == doc:
            # a whole room's export (a tree of many segments): the
            # room's rows came back once, and each segment is walked
            # from its head along the device's own links
            _doc, right, deleted, starts = held
            rows_l = []
            r = int(starts[seg]) if seg < len(starts) else NULL
            while r != NULL and len(rows_l) <= len(right):
                rows_l.append(r)
                r = int(right[r])
            rows = np.asarray(rows_l, np.int64)
            return rows, deleted[rows]
        self._apply_pending_hydrations()  # device read-back must see them
        valid_host = np.zeros(self._right.shape[1], bool)
        n = m.n_rows
        if n:
            valid_host[:n] = np.asarray(m.row_seg[:n], np.int32) == seg
        d = np.asarray(
            kernels.list_ranks(
                self._right[doc : doc + 1], self._put_r(valid_host[None])
            )
        )[0]
        # slice on the device: the whole [B, cap+1] table is no read-back
        deleted = np.asarray(self._deleted[doc])
        rows = np.nonzero(d >= 0)[0]
        # larger distance-to-tail = earlier in the document
        rows = rows[np.argsort(-d[rows], kind="stable")]
        return rows, deleted[rows]

    def rows_in_order(
        self, doc: int, name: str | None = None
    ) -> list[tuple[int, int, int, bool]]:
        """(client, clock, length, deleted) per row in list order of one root
        type — the convergence-oracle view (mirrors compare_struct_stores)."""
        name = name or self.root_name
        fb = self.fallback.get(doc)
        if fb is not None:
            out = []
            item = fb.get_text(name)._start
            while item is not None:
                out.append((item.id.client, item.id.clock, item.length, item.deleted))
                item = item.right
            return out
        m = self.mirrors[doc]
        seg = m.segments.get((name, None, NULL))
        if seg is None:
            return []
        rows, dels = self._order(doc, seg)
        return [
            (
                m.client_of_slot[m.row_slot[r]],
                m.row_clock[r],
                m.row_len[r],
                bool(d),
            )
            for r, d in zip(rows, dels)
        ]

    def text(self, doc: int, name: str | None = None) -> str:
        """Materialize the content of one root text/list type."""
        name = name or self.root_name
        fb = self.fallback.get(doc)
        if fb is not None:
            return fb.get_text(name).to_string()
        m = self.mirrors[doc]
        seg = m.segments.get((name, None, NULL))
        if seg is None:
            return ""
        rows, dels = self._order(doc, seg)
        return visible_text(m, rows, dels)

    def to_delta(
        self,
        doc: int,
        name: str | None = None,
        snapshot=None,
        prev_snapshot=None,
        compute_ychange=None,
    ) -> list:
        """Attributed rich-text delta of one root text type, straight from
        the mirror (reference YText.toDelta, YText.js:936-1030): format
        runs toggle current_attributes, strings/embeds emit insert ops —
        no CPU-doc replay needed for rich-text consumers.

        With ``snapshot`` (and optionally ``prev_snapshot``), renders the
        point-in-time / two-snapshot diff view with ``ychange``
        attribution (reference YText.js:936-1030 toDelta(snapshot,
        prevSnapshot, computeYChange)) for DEVICE-RESIDENT rooms — the
        mirror keeps deleted runs' content (engine default gc=False), so
        history renders without demoting the doc."""
        name = name or self.root_name
        fb = self.fallback.get(doc)
        if fb is not None:
            return fb.get_text(name).to_delta(
                snapshot, prev_snapshot, compute_ychange
            )
        m = self.mirrors[doc]
        seg = m.segments.get((name, None, NULL))
        if seg is None:
            return []
        if snapshot is None and prev_snapshot is None:
            return self._delta_of_seg(doc, seg)
        return self._delta_of_seg_snapshot(
            doc, seg, snapshot, prev_snapshot, compute_ychange
        )

    # -- relative positions (cursors) from mirror columns -------------------

    def relative_position_from_index(self, doc: int, index: int,
                                     name: str | None = None):
        """Stable cursor for one root type of a device-resident room,
        computed from mirror columns alone — no CPU-doc materialization,
        no device round trip (reference RelativePosition.js:85-104
        createRelativePositionFromTypeIndex).  Returns a standard
        :class:`~yjs_tpu.utils.relative_position.RelativePosition`
        (encode/decode/JSON interop with JS peers applies)."""
        from ..ids import create_id
        from ..utils.relative_position import (
            RelativePosition,
            create_relative_position_from_type_index,
        )

        name = name or self.root_name
        fb = self.fallback.get(doc)
        if fb is not None:
            # type-agnostic root access: the mirror branch below walks
            # segment rows without caring about the root's kind, so the
            # demoted branch must too (get_text on an already-typed
            # array/xml root raises)
            return create_relative_position_from_type_index(
                fb.get(name), index
            )
        m = self.mirrors[doc]
        seg = m.segments.get((name, None, NULL))
        if seg is not None:
            rows, dels = self._order(doc, seg)
            for r, d in zip(rows, dels):
                r = int(r)
                if d or not m.row_countable[r]:
                    continue
                ln = int(m.row_len[r])
                if ln > index:
                    client = m.client_of_slot[int(m.row_slot[r])]
                    return RelativePosition(
                        None, name, create_id(client, int(m.row_clock[r]) + index)
                    )
                index -= ln
        return RelativePosition(None, name, None)

    def _row_of_id(self, m, client: int, clock: int) -> int | None:
        """Row containing (client, clock) via the mirror fragment index,
        or None when that clock is not integrated yet (reference
        getItem/findIndexSS semantics against columnar state)."""
        slot = m.slot_of_client.get(client)
        if slot is None or m.state[slot] <= clock:
            return None
        fi = m._frag_containing(slot, clock)
        return None if fi is None else int(m.frag_row[slot][fi])

    def absolute_index_from_relative(self, doc: int, rpos) -> int | None:
        """Resolve a cursor back to a list index against the room's
        CURRENT state, from mirror columns alone (reference
        RelativePosition.js:214-262
        createAbsolutePositionFromRelativePosition).  Returns None when
        the anchor is unknown (not yet integrated / garbage collected),
        exactly like the reference.

        Deviation (documented): the return value is the index alone —
        on the engine path the type handle is the (doc, root-name) pair
        the caller already holds, not a live Y type object.  ``redone``
        chains are a CPU-replica concept (the pointers are local to the
        undoing replica and never on the wire), so the mirror path has
        none to follow; rooms with server-side undo enabled resolve
        through their replica instead (see TpuProvider
        .resolve_relative_position), which runs the reference
        follow-redone walk verbatim."""
        from ..utils.relative_position import (
            create_absolute_position_from_relative_position,
        )

        fb = self.fallback.get(doc)
        if fb is not None:
            a = create_absolute_position_from_relative_position(rpos, fb)
            return None if a is None else a.index
        m = self.mirrors[doc]

        def visible_len(seg: int) -> int:
            rows, dels = self._order(doc, seg)
            tot = 0
            for r, d in zip(rows, dels):
                r = int(r)
                if not d and m.row_countable[r]:
                    tot += int(m.row_len[r])
            return tot

        if rpos.item is not None:
            r = self._row_of_id(m, rpos.item.client, rpos.item.clock)
            if r is None or m.row_is_gc[r]:
                # unknown clock or GC'd anchor: reference returns null
                # (followRedone landed on a GC struct)
                return None
            seg = int(m.row_seg[r])
            _name, _sub, parent = m.seg_info[seg]
            if parent != NULL and parent in m._host_deleted_rows:
                # parent type deleted: reference keeps index 0
                return 0
            deleted = r in m._host_deleted_rows
            index = (
                0
                if (deleted or not m.row_countable[r])
                else rpos.item.clock - int(m.row_clock[r])
            )
            rows, dels = self._order(doc, seg)
            for rr, dd in zip(rows, dels):
                rr = int(rr)
                if rr == r:
                    return index
                if not dd and m.row_countable[rr]:
                    index += int(m.row_len[rr])
            return None  # anchor row not reachable in its segment
        if rpos.tname is not None:
            seg = m.segments.get((rpos.tname, None, NULL))
            # absent root = empty type (reference doc.get(tname)._length)
            return 0 if seg is None else visible_len(seg)
        if rpos.type is not None:
            r = self._row_of_id(m, rpos.type.client, rpos.type.clock)
            if r is None or m.row_is_gc[r] or int(m.row_content_ref[r]) != 7:
                return None
            seg = m.segments.get((None, None, r))
            return 0 if seg is None else visible_len(seg)
        raise ValueError("invalid relative position")

    def snapshot(self, doc: int):
        """Point-in-time capture (state vector + delete set) of one room,
        straight from the mirror — no CPU-doc materialization, no device
        round trip (reference Snapshot.js:118-121 snapshot()).  The
        result is a standard :class:`~yjs_tpu.utils.snapshot.Snapshot`:
        encode/decode/equality and createDocFromSnapshot interop apply."""
        from ..utils.snapshot import create_snapshot
        from ..utils.snapshot import snapshot as cpu_snapshot

        fb = self.fallback.get(doc)
        if fb is not None:
            return cpu_snapshot(fb)
        m = self.mirrors[doc]
        return create_snapshot(m.delete_set(), m.state_vector())

    def create_doc_from_snapshot(self, doc: int, snap, new_doc=None) -> Doc:
        """Rewind one room to ``snap`` as a standalone CPU :class:`Doc`
        (reference Snapshot.js:162-202 createDocFromSnapshot).  The room
        itself stays device-resident and untouched; the engine's full
        state is materialized host-side (gc=False history is retained by
        default) and truncated to the snapshot."""
        from ..updates import apply_update
        from ..utils.snapshot import create_doc_from_snapshot as _cdfs

        fb = self.fallback.get(doc)
        if fb is not None:
            return _cdfs(fb, snap, new_doc)
        if self.gc:
            raise RuntimeError("originDoc must not be garbage collected")
        origin = Doc(gc=False)
        apply_update(origin, self.encode_state_as_update(doc))
        return _cdfs(origin, snap, new_doc)

    def _delta_of_seg_snapshot(self, doc, seg, snap, prev, compute_ychange):
        """Snapshot-scoped delta from the mirror columns: each run is cut
        into element sub-ranges of uniform visibility under (sv, ds) of
        both snapshots, so no struct pre-splitting is needed — the exact
        twin of YText.js:936-1030 / types/ytext.py to_delta (the parity
        test pins them op-for-op)."""
        from bisect import bisect_right

        from ..core import ContentEmbed, ContentFormat, ContentString, is_deleted
        from ..ids import create_id
        from ..types.ytext import update_current_attributes

        if self.gc:
            # compaction GC'd deleted runs' content: historical views are
            # unrenderable, exactly like the reference's
            # createDocFromSnapshot guard (Snapshot.js:165)
            raise RuntimeError(
                "snapshot-scoped to_delta requires engine gc=False"
            )
        m = self.mirrors[doc]
        ops: list = []
        cur: dict = {}
        parts: list[str] = []
        # per-snapshot, per-client sorted (start, end) edge tables: the
        # row loop bisects instead of scanning the whole DeleteSet
        edge_tables: dict[int, dict[int, tuple[list, list]]] = {}
        for si, sn in enumerate((snap, prev)):
            if sn is None:
                continue
            tab: dict[int, tuple[list, list]] = {}
            for cl, items in sn.ds.clients.items():
                tab[cl] = (
                    [it.clock for it in items],
                    [it.clock + it.len for it in items],
                )
            edge_tables[si] = tab

        def pack_str():
            if parts:
                op = {"insert": from_u16("".join(parts))}
                if cur:
                    op["attributes"] = dict(cur)
                ops.append(op)
                parts.clear()

        def vis(sn, client, clk):
            # element-level twin of Snapshot.js:133-135 isVisible (the
            # reference checks post-split item starts; elements subsume)
            if sn is None:
                return None
            return (
                client in sn.sv
                and sn.sv.get(client, 0) > clk
                and not is_deleted(sn.ds, create_id(client, clk))
            )

        rows, dels = self._order(doc, seg)
        for r, dl in zip(rows, dels):
            r = int(r)
            if m.row_is_gc[r]:
                continue  # GC'd runs carry no content; see gc caveat
            client = m.client_of_slot[m.row_slot[r]]
            clock = int(m.row_clock[r])
            ln = int(m.row_len[r])
            # visibility boundaries inside this run: sv bounds + ds edges
            # (bisected — ds lists are sorted and disjoint)
            cuts = {clock, clock + ln}
            for si, sn in enumerate((snap, prev)):
                if sn is None:
                    continue
                b = sn.sv.get(client, 0)
                if clock < b < clock + ln:
                    cuts.add(b)
                starts_ends = edge_tables[si].get(client)
                if starts_ends is None:
                    continue
                starts, ends = starts_ends
                j = bisect_right(ends, clock)
                while j < len(starts) and starts[j] < clock + ln:
                    if clock < starts[j]:
                        cuts.add(starts[j])
                    if ends[j] < clock + ln:
                        cuts.add(ends[j])
                    j += 1
            content = None
            pts = sorted(cuts)
            for a, b in zip(pts, pts[1:]):
                v_now = vis(snap, client, a)
                if snap is None:
                    v_now = not dl  # plain visibility when only prev given
                v_prev = vis(prev, client, a)
                if not (v_now or (prev is not None and v_prev)):
                    continue
                if content is None:
                    content = m.realized_content(r)
                if isinstance(content, ContentString):
                    cy = cur.get("ychange")
                    if snap is not None and not v_now:
                        if (
                            cy is None
                            or cy.get("user") != client
                            or cy.get("state") != "removed"
                        ):
                            pack_str()
                            cur["ychange"] = (
                                compute_ychange("removed", create_id(client, a))
                                if compute_ychange
                                else {"type": "removed"}
                            )
                    elif prev is not None and not v_prev:
                        if (
                            cy is None
                            or cy.get("user") != client
                            or cy.get("state") != "added"
                        ):
                            pack_str()
                            cur["ychange"] = (
                                compute_ychange("added", create_id(client, a))
                                if compute_ychange
                                else {"type": "added"}
                            )
                    elif cy is not None:
                        pack_str()
                        cur.pop("ychange", None)
                    parts.append(content.str[a - clock : b - clock])
                elif isinstance(content, ContentEmbed):
                    pack_str()
                    op = {"insert": content.embed}
                    if cur:
                        op["attributes"] = dict(cur)
                    ops.append(op)
                elif isinstance(content, ContentFormat):
                    if v_now:
                        pack_str()
                        update_current_attributes(cur, content)
        pack_str()
        return ops

    def _delta_of_seg(self, doc: int, seg: int) -> list:
        from ..core import ContentEmbed, ContentFormat, ContentString
        from ..types.ytext import update_current_attributes

        m = self.mirrors[doc]
        ops: list = []
        cur: dict = {}
        parts: list[str] = []

        def pack_str():
            if parts:
                op = {"insert": from_u16("".join(parts))}
                if cur:
                    op["attributes"] = dict(cur)
                ops.append(op)
                parts.clear()

        rows, dels = self._order(doc, seg)
        for r, dl in zip(rows, dels):
            if dl:
                continue
            c = m.realized_content(int(r))
            if isinstance(c, ContentString):
                parts.append(c.str)
            elif isinstance(c, ContentEmbed):
                pack_str()
                op = {"insert": c.embed}
                if cur:
                    op["attributes"] = dict(cur)
                ops.append(op)
            elif isinstance(c, ContentFormat):
                pack_str()
                update_current_attributes(cur, c)
        pack_str()
        return ops

    @contextlib.contextmanager
    def _room_rows(self, doc: int):
        """While it is open, ``_order`` walks ``doc``'s segments out of
        one read-back of the room's device rows (``export_from_device``
        only; the host path reads no device)."""
        if (
            not self.export_from_device or self._right is None
            or doc in self.fallback or self._export_rows is not None
        ):
            yield
            return
        self._apply_pending_hydrations()
        self._export_rows = (
            doc, np.asarray(self._right[doc]),
            np.asarray(self._deleted[doc]), np.asarray(self._starts[doc]),
        )
        try:
            yield
        finally:
            self._export_rows = None

    def xml_string(self, doc: int, name: str | None = None) -> str:
        """Serialize a root XML fragment from the mirror (reference
        YXmlFragment/YXmlElement/YXmlText toString — sorted attributes,
        nested formatting tags), no CPU-doc replay."""
        with self._room_rows(doc):
            return self._xml_string(doc, name)

    def _xml_string(self, doc: int, name: str | None) -> str:
        name = name or self.root_name
        fb = self.fallback.get(doc)
        if fb is not None:
            from ..types.yxml import YXmlHook

            def render(t):
                # YXmlHook inherits YMap and has no serialization in the
                # reference; emit the same stable "" as the mirror path
                if isinstance(t, YXmlHook):
                    return ""
                return t.to_string()

            frag = fb.get_xml_fragment(name)
            return "".join(render(t) for t in frag.to_array())
        seg = self.mirrors[doc].segments.get((name, None, NULL))
        if seg is None:
            return ""
        return self._xml_children(doc, seg)

    def _xml_children(self, doc: int, seg: int) -> str:
        m = self.mirrors[doc]
        rows, dels = self._order(doc, seg)
        parts: list[str] = []
        for r, dl in zip(rows, dels):
            r = int(r)
            if dl or not m.row_countable[r]:
                continue
            c = m.realized_content(r)
            if getattr(c, "REF", None) == 7:
                parts.append(self._xml_node(doc, r, c))
            else:
                parts.extend(str(v) for v in c.get_content())
        return "".join(parts)

    def _xml_node(self, doc: int, row: int, content) -> str:
        m = self.mirrors[doc]
        t = content.type
        kind = type(t).__name__
        child_seg = m.segments.get((None, None, row))
        if kind == "YXmlElement":
            # sorted-attribute serialization (reference YXmlElement.js:97-113)
            attrs = self._map_json_of(doc, None, row)
            attrs_string = " ".join(
                f'{key}="{attrs[key]}"' for key in sorted(attrs.keys())
            )
            node_name = t.node_name.lower()
            inner = (
                self._xml_children(doc, child_seg)
                if child_seg is not None
                else ""
            )
            sep = " " + attrs_string if attrs_string else ""
            return f"<{node_name}{sep}>{inner}</{node_name}>"
        if kind == "YXmlText":
            # delta attributes as nested sorted tags (YXmlText.js:65-97)
            if child_seg is None:
                return ""
            out = []
            for delta in self._delta_of_seg(doc, child_seg):
                names = sorted(delta.get("attributes", {}))
                s = ""
                for node_name in names:
                    s += f"<{node_name}"
                    a = delta["attributes"][node_name]
                    for key in sorted(a):
                        s += f' {key}="{a[key]}"'
                    s += ">"
                s += str(delta["insert"])
                for node_name in reversed(names):
                    s += f"</{node_name}>"
                out.append(s)
            return "".join(out)
        if kind == "YXmlFragment":
            return (
                self._xml_children(doc, child_seg)
                if child_seg is not None
                else ""
            )
        # YXmlHook: a YMap with a hook name — the reference's toString
        # falls through Object.prototype; serialize it as the stable empty
        # form on BOTH paths (the CPU fallback goes through xml_string's
        # own renderer below, so modes agree)
        return ""

    def map_json(self, doc: int, name: str | None = None) -> dict:
        """The visible {key: value} content of one root YMap (LWW winners,
        reference typeMapGet / YMap.toJSON); nested shared types render
        recursively (dicts / lists / strings)."""
        name = name or self.root_name
        fb = self.fallback.get(doc)
        if fb is not None:
            return fb.get_map(name).to_json()
        return self._map_json_of(doc, name, NULL)

    def _map_json_of(self, doc: int, name: str | None, parent_row: int) -> dict:
        m = self.mirrors[doc]
        if parent_row != NULL:
            # nested: the reverse index lists exactly this type's segments
            segs = [
                (m.seg_info[s][1], s)
                for s in m._segs_of_parent.get(parent_row, ())
                if m.seg_info[s][1] is not None
            ]
        else:
            segs = [
                (sub, seg)
                for (n, sub, p), seg in m.segments.items()
                if n == name and sub is not None and p == NULL
            ]
        out = {}
        for sub, seg in segs:
            # the map-key chain is a device segment like any list: its
            # visible value is the last undeleted entry of the chain in
            # list order (LWW keeps only the final tail undeleted)
            rows, dels = self._order(doc, seg)
            if not len(rows) or dels[-1]:
                continue
            out[sub] = self._value_of_row(doc, int(rows[-1]))
        return out

    def _value_of_row(self, doc: int, row: int):
        """A row's visible value (reference typeMapGet: the last content
        element), recursing into nested shared types."""
        m = self.mirrors[doc]
        content = m.realized_content(row)
        if getattr(content, "REF", None) == 7:
            return self._type_json(doc, row)
        return content.get_content()[-1]

    def _list_json(self, doc: int, seg: int) -> list:
        """One list segment's visible values in document order, recursing
        into nested shared types (reference YArray.toJSON)."""
        m = self.mirrors[doc]
        rows, dels = self._order(doc, seg)
        out = []
        for r, dl in zip(rows, dels):
            if dl or not m.row_countable[r]:
                continue
            content = m.realized_content(r)
            if getattr(content, "REF", None) == 7:
                out.append(self._type_json(doc, r))
            else:
                out.extend(content.get_content())
        return out

    def _type_json(self, doc: int, row: int):
        """Materialize a nested shared type held by ``row``'s ContentType:
        maps render as dicts, text as strings, lists as JSON arrays
        (reference YMap/YText/YArray .toJSON)."""
        m = self.mirrors[doc]
        kind = type(m.realized_content(row).type).__name__
        if kind in ("YMap", "YXmlHook"):
            return self._map_json_of(doc, None, row)
        seg = m.segments.get((None, None, row))
        if seg is None:
            return "" if kind in ("YText", "YXmlText") else []
        if kind in ("YText", "YXmlText"):
            rows, dels = self._order(doc, seg)
            return visible_text(m, rows, dels)
        return self._list_json(doc, seg)

    def to_json(self, doc: int, name: str | None = None):
        """A root YArray's JSON content, nested types included
        (reference YArray.toJSON)."""
        name = name or self.root_name
        fb = self.fallback.get(doc)
        if fb is not None:
            return fb.get_array(name).to_json()
        seg = self.mirrors[doc].segments.get((name, None, NULL))
        if seg is None:
            return []
        return self._list_json(doc, seg)

    def encode_state_vector(self, doc: int) -> bytes:
        fb = self.fallback.get(doc)
        if fb is not None:
            from ..updates import encode_state_vector

            return encode_state_vector(fb)
        return self.mirrors[doc].encode_state_vector()

    def encode_state_as_update(
        self, doc: int, encoded_target_sv: bytes | None = None, v2: bool = False
    ) -> bytes:
        """Sync step 2 straight from the columnar mirror (no CPU Doc)."""
        fb = self.fallback.get(doc)
        if fb is not None:
            from ..updates import encode_state_as_update, encode_state_as_update_v2

            f = encode_state_as_update_v2 if v2 else encode_state_as_update
            return f(fb, encoded_target_sv)
        target = None
        if encoded_target_sv is not None:
            from ..updates import decode_state_vector

            target = decode_state_vector(encoded_target_sv)
        return self.mirrors[doc].encode_state_as_update(target, v2=v2)

    # -- batched sync ------------------------------------------------------

    def sync_step2_batch(
        self, requests: list[tuple[int, dict[int, int] | None]], v2: bool = False
    ) -> list[bytes]:
        """Answer many sync-step-1 requests: (doc, remote state vector)
        pairs in, diff updates out (reference encodeStateAsUpdate,
        encoding.js:490-526), positionally.

        The default path is the host's and never leaves the native
        mirrors: every V1 request whose room's mirror is a NativeMirror
        goes to ``encode_diffs_many``, ONE ``ymx_encode_steps_many`` call
        a slice of 256 requests, which encodes each diff straight from the
        C++ columns into one arena sized by the diffs' own bounds; the
        replies are cut out of it.  A request that call refuses (V2-framed
        or spilled payloads) and every ``v2=True`` request take the
        mirror's own ``encode_state_as_update``: for a NativeMirror one
        native call and one buffer of the room's whole bound, and where
        that declines too, as for every request of a Python-mirror
        engine, the mirror's host mask over its columns and its own
        writer.  Fallback docs are served by the CPU core.

        The whole call is the ``ytpu.sync.encode`` span;
        ``last_sync_metrics`` says what it did: ``encode_batched`` requests
        the batched call answered, ``encode_fallback`` requests that took
        any other path, ``encode_buffer_bytes`` the arena bytes the calls
        wrote plus what the fallbacks' own buffers held (no less than the
        answers' bytes)."""
        t0 = time.perf_counter()
        with self._phase_ctx("sync.encode"):
            replies, buffer_bytes, n_batched = self._sync_step2_batch(
                requests, v2
            )
        self.last_sync_metrics = {
            "n_requests": len(requests),
            "encode_batched": n_batched,
            "encode_fallback": len(requests) - n_batched,
            "encode_buffer_bytes": buffer_bytes,
            "t_encode_s": time.perf_counter() - t0,
        }
        return replies

    def _sync_step2_batch(self, requests, v2):
        """The answers, the bytes their encodes wrote or allocated, and
        how many of them the batched native call gave."""
        replies: list[bytes | None] = [None] * len(requests)
        buffer_bytes = n_batched = 0
        mirrors = self.mirrors
        batch, one_by_one = [], []
        for j, (i, sv) in enumerate(requests):
            if i in self.fallback:
                enc_sv = None
                if sv:
                    from ..coding import DSEncoderV1
                    from ..updates import write_state_vector

                    e = DSEncoderV1()
                    write_state_vector(e, sv)
                    enc_sv = e.to_bytes()
                replies[j] = self.encode_state_as_update(i, enc_sv, v2=v2)
            elif not v2 and isinstance(mirrors[i], NativeMirror):
                batch.append((j, i, sv))
            else:
                one_by_one.append((j, i, sv))
        if batch:
            updates, arena_bytes = encode_diffs_many(
                [(mirrors[i], sv) for _j, i, sv in batch]
            )
            buffer_bytes += arena_bytes
            for r, u in zip(batch, updates):
                if u is None:
                    one_by_one.append(r)
                else:
                    replies[r[0]] = u
                    n_batched += 1
        for j, i, sv in one_by_one:
            m = mirrors[i]
            replies[j] = m.encode_state_as_update(sv, v2=v2)
            buffer_bytes += getattr(m, "encode_buffer_bytes", 0)
        return replies, buffer_bytes, n_batched

    def encode_states_batched(
        self, docs: list[int], v2: bool = False
    ) -> list[bytes]:
        """Full-state exports for many docs in ONE batched call (a
        sync-step-2 answer against the empty state vector) — the WAL
        checkpoint's snapshot producer (ISSUE 3): compacting a fleet
        must not cost one encode call per doc."""
        return self.sync_step2_batch([(i, None) for i in docs], v2=v2)

    def has_pending(self, doc: int) -> bool:
        if doc in self.fallback:
            fb = self.fallback[doc]
            return bool(fb.store.pending_clients_struct_refs) or bool(
                fb.store.pending_delete_readers
            )
        return self.mirrors[doc].has_pending()
