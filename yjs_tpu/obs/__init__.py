"""yjs_tpu.obs: observability for the engine/provider stack.

Four pieces (ISSUE 1 tentpole):

- :mod:`.registry` — zero-dependency counters/gauges/log-bucketed
  histograms, cheap enough to stay on in the flush hot path;
- :mod:`.history` — the bounded flush-history ring superseding the
  overwrite-only ``last_flush_metrics`` (which remains as a
  compatibility view of the newest entry);
- :mod:`.trace` — the program's one span API: every span lands on the
  JAX profiler's clock and, tracer enabled, in a host ring exported as
  Chrome-trace JSON;
- :mod:`.expo` — Prometheus text dump + JSON snapshot.

Fleet-wide observability (ISSUE 11):

- :mod:`.dist` — deterministic cross-provider trace contexts
  (``YTPU_TRACE_SAMPLE`` head sampling, envelope carry, hash-derived
  flow ids);
- :mod:`.blackbox` — the always-on black-box flight recorder
  (``YTPU_BLACKBOX{,_CAP,_DIR}``), auto-dumped on quarantine /
  failover / ``ProviderFullError`` / flush exceptions;
- :mod:`.federate` — N-shard metric federation (counters sum, gauges
  keep per-shard series, histograms merge) shared by
  ``FleetRouter.metrics_snapshot``, ``ytpu_top`` and ``ytpu_stats``.

Env knobs: ``YTPU_OBS_DISABLED=1`` (no-op registry, empty trace ring:
spans still reach an active profiler trace; the flush history stays on
so ``last_flush_metrics`` keeps its contract),
``YTPU_OBS_HISTORY`` (ring size, default 128), ``YTPU_TRACE_PATH``
(write a merged Chrome trace at interpreter exit), ``YTPU_TRACE_EVENTS``
(per-tracer event cap, default 200k).
"""

from __future__ import annotations

import os

from .expo import prometheus_text, registry_snapshot  # noqa: F401
from .history import FlushHistory  # noqa: F401
from .registry import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NOOP_METRIC,
)
from .trace import Tracer  # noqa: F401
from .blackbox import (  # noqa: F401
    FlightRecorder,
    flight_recorder,
    reset_flight_recorder,
)
from .dist import (  # noqa: F401
    TraceContext,
    current_context,
    flow_id_for,
    mint_for_update,
    trace_metrics,
    use_context,
)
from .federate import (  # noqa: F401
    FederationMetrics,
    fed_metrics,
    federate_snapshots,
    merge_summaries,
    read_snapshot_dir,
    scrape_endpoints,
)
from .admin import (  # noqa: F401
    AdminConfig,
    AdminServer,
    maybe_start_admin,
)
from .tsdb import (  # noqa: F401
    Tsdb,
    TsdbConfig,
    maybe_attach_tsdb,
    tsdb,
    tsdb_enabled,
    tsdb_metrics,
    tsdb_window,
)
from .cost import CostLedger, cost_enabled  # noqa: F401
from .capacity import (  # noqa: F401
    CapacityConfig,
    ramp_capacity,
    read_knee,
    sessions_per_device,
)

SNAPSHOT_SCHEMA_VERSION = 1

# -- the per-flush metrics schema -------------------------------------------
# ONE constructor for every flush exit (batched / per-doc /
# empty-early-return): the paths previously shared these keys by
# convention only, and a drift was silent until a consumer KeyError'd.
# tests/test_obs.py pins identical key sets across all modes.
FLUSH_METRICS_SCHEMA: dict = {
    "n_docs_flushed": 0,
    "n_demoted": 0,
    # docs transactionally rolled back (and demoted) by failure
    # isolation during this flush — always <= n_demoted
    "n_rolled_back": 0,
    "n_fallback_docs": 0,
    "n_rows_max": 0,
    "n_sched_entries": 0,
    "schedule_occupancy": 0.0,
    "n_pending_docs": 0,
    "pending_depth": 0,
    # planner fan-out this flush actually used: the most threads one
    # native call planned on, the caller included (the core's own rule:
    # a thread for each share of the work the call is reckoned to hold,
    # at most YTPU_PLAN_THREADS and the call's docs).  1 = fully serial
    # per-doc planning, as the Python planner's lane always is.
    "plan_threads": 1,
    # the native planner's pool outlives the call: workers the flush's
    # calls woke, and threads they had to construct (0 in every flush
    # once the pool is as wide as its widest call)
    "plan_pool_woken": 0,
    "plan_pool_started": 0,
    # what a flush looked at: slots the plan phase visited (the engine's
    # dirty set, fed by queue_update: over n_docs, the share of the
    # slots a flush pays for) and slots whose n_rows the compaction
    # look read (the rooms planned since the look before), and the rooms
    # among them that had doubled, were asked whether a rebuild would
    # change them, answered no and were not rebuilt
    "rooms_dirty": 0,
    "rooms_compact_looked": 0,
    "rooms_compact_skipped": 0,
    # the native pool's own clock (ymx_prepare_many, summed over the
    # flush's calls): the longest single room's prepare, and the sum
    # over rooms.  plan_pool_s / (threads x the ytpu.plan.native span)
    # is how evenly the pool was loaded; a plan_room_max_s near the
    # span's length is one long room ending the phase alone
    "plan_room_max_s": 0.0,
    "plan_pool_s": 0.0,
    # plan_pool_s by the phase of a room's prepare (the core's laps:
    # scan; merge + fixpoint; the cuts, delete-set clamp to pre-split;
    # rows + deletes + LWW; finalize), and what the pool itself cost the
    # flushing thread: handing the call to its workers (the job
    # published and they woken; threads constructed only where the pool
    # grows), and the wait from the last thread's finding the queue
    # empty to the flushing thread's running again (both 0 when the
    # call ran serially).  The flush ring and /debug carry them; no
    # registry family does
    "plan_scan_s": 0.0,
    "plan_merge_s": 0.0,
    "plan_cuts_s": 0.0,
    "plan_rows_s": 0.0,
    "plan_finalize_s": 0.0,
    "plan_pool_start_s": 0.0,
    "plan_pool_join_s": 0.0,
    # frontier-keyed plan cache (ISSUE 9): probes served from cache /
    # planned cold this flush, and structs placed by the segment-sorted
    # fast path instead of the sequential YATA walk.  Of the cold plans,
    # plan_cache_admitted left a snapshot (their key had been sighted
    # before); the rest were first sightings (ISSUE 30)
    "plan_cache_hits": 0,
    "plan_cache_misses": 0,
    "plan_cache_admitted": 0,
    "plan_fastpath_structs": 0,
    # the planners' segment pass (ISSUE 15): structs placed as chained
    # runs straight from their ranks (fast set) vs handed to the
    # sequential YATA conflict fallback (residue)
    "plan_segment_fast": 0,
    "plan_segment_residue": 0,
    "t_compact_s": 0.0,
    "t_plan_s": 0.0,
    # t_plan_s split: snapshot-adoption time for cache hits vs cold
    # prepare time (t_plan_cached_s + t_plan_cold_s <= t_plan_s)
    "t_plan_cached_s": 0.0,
    "t_plan_cold_s": 0.0,
    "t_pack_s": 0.0,
    "t_dispatch_s": 0.0,
    "t_emit_s": 0.0,
    "t_total_s": 0.0,
    # pipelined flush (ISSUE 12): host pack time that overlapped an
    # in-flight device dispatch, and host time spent blocked on the
    # device (staging-buffer reuse guards + YTPU_FLUSH_PIPELINE=0's
    # per-dispatch barrier).  Pipeline-off, overlap is 0 and the wait
    # is the full device time; pipeline-on, pack overlap is the payoff
    # and wait shrinks to the true dependency stalls.
    "t_pack_overlap_s": 0.0,
    "t_device_wait_s": 0.0,
    # 1 when every device dispatch of this flush updated donated
    # resident tables in place (no table growth/reallocation since the
    # previous flush); realloc_bytes is the growth cost when it is 0
    "flush_donated": 0,
    "realloc_bytes": 0,
    # rooms this flush wrote to the device as rows of a block and not
    # link by link (a room loaded whole into a slot that held no row: a
    # room bound, reloaded after a release, or recovered), and the bytes
    # staged for them (allocation = transfer); 0 in a flush of rooms
    # that held rows
    "rooms_row_loaded": 0,
    "row_block_bytes": 0,
    # which of the two write paths a flush's links took (their sum is
    # n_sched_entries): the element lanes (rooms that held rows) or the
    # row blocks (rooms loaded whole), and the lanes of the keys the
    # flush dispatched, summed over chunks and shards: lane_links over
    # lanes_dispatched is how full the lane keys were, padding, list
    # heads and deletes counted against them
    "lane_links": 0,
    "row_links": 0,
    "lanes_dispatched": 0,
    # rows the native planner's conflict scan stepped over (list_insert:
    # a sibling in the same gap each); 0 from the Python planner, which
    # does not count its walk
    "conflict_steps": 0,
    # bytes the compactions and hydrations since the previous flush
    # staged for scatter_rows (host allocation = transfer = device
    # writes), and the bytes of rebuilt rows the rooms in those blocks
    # hold: held / staged is how full a staged block is.  Rooms are
    # staged in width classes (a room's bucketed rows, or twice that),
    # one block (one scatter_rows) a class: rows_staged_blocks counts
    # them (0 where nothing was compacted)
    "rows_staged_bytes": 0,
    "rows_held_bytes": 0,
    "rows_staged_blocks": 0,
    # bytes of device rows the releases since the previous flush blanked
    # in place (reset_doc: one whole row of each resident table a slot)
    "release_blanked_bytes": 0,
    # the broadcast updates of this flush (0 when nobody listens for
    # updates): rooms encoded by the one native call over all planned
    # rooms (ymx_encode_steps_many), rooms that took the per-room
    # encode_step_update (the Python planner's, and those whose payloads
    # the native writer refuses), and the bytes handed to the listeners
    "emit_batched": 0,
    "emit_fallback": 0,
    "emit_bytes": 0,
    # max device dispatches in flight at once (0 = no dispatch or
    # synchronous mode; the double-buffered staging pair bounds it)
    "pipeline_depth": 0,
    # what the planner integrated, by kind: rows it added, and of those
    # the rows whose parent is a type item (a nested type's children),
    # ContentFormat rows, rows under a parentSub (a map entry, an XML
    # attribute) and ContentType rows; segments the flush created (a
    # nested type's list, a map key's chain); map entries the flush
    # deleted (overwritten by a later writer, whose own delete set names
    # the old value, or by the last-writer-wins pass; or removed);
    # format rows the flush deleted
    "rows_planned": 0,
    "rows_nested": 0,
    "rows_format": 0,
    "rows_attr": 0,
    "rows_type": 0,
    "segs_created": 0,
    "lww_overwritten": 0,
    "format_deleted": 0,
    # format items the clean-up after a remote transaction deleted
    # (engine._format_cleanup: what a Y.Doc's YText._callObserver
    # deletes), and the texts it walked
    "format_cleanup_deleted": 0,
    "format_cleanup_texts": 0,
    # the widest planned room's segments, beside the width of the list
    # heads' table (starts is [n_docs, seg_cap + 1])
    "n_segs_max": 0,
    "seg_cap": 0,
}

FLUSH_PHASES = ("compact", "plan", "pack", "dispatch", "emit")


def new_flush_metrics(**overrides) -> dict:
    """A fresh flush-metrics dict with every schema key present.

    Unknown keys raise: a new metric must be added to the schema (and
    the README table) first, so the exposed key set cannot drift."""
    unknown = set(overrides) - set(FLUSH_METRICS_SCHEMA)
    if unknown:
        raise KeyError(
            f"not in FLUSH_METRICS_SCHEMA: {sorted(unknown)}"
        )
    m = dict(FLUSH_METRICS_SCHEMA)
    m.update(overrides)
    return m


def obs_enabled() -> bool:
    return os.environ.get("YTPU_OBS_DISABLED") != "1"


# -- process-global registry -------------------------------------------------
# Serves module-level consumers with no engine handle (the y-protocols
# sync framing).  Engine/provider exposition merges it in.

_GLOBAL: MetricsRegistry | None = None


def global_registry() -> MetricsRegistry:
    global _GLOBAL
    if _GLOBAL is None:
        _GLOBAL = MetricsRegistry(enabled=obs_enabled())
        # pre-register the protocol family so exposition (and the schema
        # checker) sees it before the first frame is read/written
        _GLOBAL.counter(
            "ytpu_sync_messages_total",
            "y-protocols sync frames processed by yjs_tpu.sync.protocol",
            labelnames=("dir", "type"),
        )
        _GLOBAL.counter(
            "ytpu_chaos_faults_total",
            "Faults injected by the chaos harness, by fault kind",
            labelnames=("fault",),
        )
    return _GLOBAL


class EngineObs:
    """Per-engine observability bundle: registry + flush ring + tracer.

    Every instrument the flush hot path touches is pre-created here so
    recording is attribute access + arithmetic — no name resolution, no
    label resolution (phase children are pre-resolved)."""

    def __init__(self, history_len: int | None = None):
        self.enabled = obs_enabled()
        self.registry = MetricsRegistry(enabled=self.enabled)
        self.history = FlushHistory(maxlen=history_len)
        self.tracer = Tracer(enabled=self.enabled)
        # the process-global black box records even with metrics
        # disabled (it is forensics, not telemetry); trace metrics are
        # registered here so the schema checker sees the families after
        # one provider construction
        self.blackbox = flight_recorder()
        self.blackbox._obs()
        trace_metrics()
        r = self.registry
        self._flushes = r.counter(
            "ytpu_engine_flushes_total", "Engine flushes run"
        )
        self._docs_flushed = r.counter(
            "ytpu_engine_docs_flushed_total",
            "Docs integrated with visible work, summed over flushes",
        )
        self._updates_emitted = r.counter(
            "ytpu_engine_updates_emitted_total",
            "Incremental updates emitted via doc.on('update')",
        )
        self._egress_bytes = r.counter(
            "ytpu_engine_update_egress_bytes_total",
            "Bytes of emitted incremental updates",
            unit="bytes",
        )
        self._demotions = r.counter(
            "ytpu_engine_demotions_total",
            "Device->CPU demotions by reason",
            labelnames=("reason",),
        )
        self._fallback_docs = r.gauge(
            "ytpu_engine_fallback_docs", "Docs currently on the CPU core"
        )
        self._pending_docs = r.gauge(
            "ytpu_engine_pending_docs",
            "Docs with parked (causally unready) traffic after last flush",
        )
        self._pending_depth = r.gauge(
            "ytpu_engine_pending_depth",
            "Total parked struct depth after last flush",
        )
        self._occupancy = r.gauge(
            "ytpu_engine_schedule_occupancy",
            "Real fraction of dispatched schedule/lane slots, last flush",
            unit="ratio",
        )
        self._plan_threads = r.gauge(
            "ytpu_engine_plan_threads",
            "Most threads one native planner call of the last flush "
            "planned on, the flushing thread included",
        )
        self._row_capacity = r.gauge(
            "ytpu_engine_row_capacity",
            "Device row capacity (per doc) after last flush",
            unit="rows",
        )
        # segment-planner residue (ISSUE 16 satellite): the live number
        # the residue-elimination work drives against — fraction of
        # planned structs the planner's fast set could NOT place and
        # handed to the sequential YATA conflict fallback
        self._segment_residue_fraction = r.gauge(
            "ytpu_plan_segment_residue_fraction",
            "Fraction of planned structs handed to the sequential YATA "
            "conflict fallback, last flush with planner work "
            "(residue / (fast + residue))",
            unit="ratio",
        )
        self._flush_seconds = r.histogram(
            "ytpu_engine_flush_seconds", "End-to-end flush wall time",
            unit="s",
        )
        self._phase_seconds = r.histogram(
            "ytpu_engine_phase_seconds",
            "Per-phase flush wall time",
            unit="s",
            labelnames=("phase",),
        )
        self._phase_children = {
            ph: self._phase_seconds.labels(phase=ph) for ph in FLUSH_PHASES
        }
        self._native_prepare_seconds = r.histogram(
            "ytpu_native_prepare_many_seconds",
            "One ymx_prepare_many batch (stage + plan), per call",
            unit="s",
        )
        self._native_prepare_docs = r.histogram(
            "ytpu_native_prepare_many_docs",
            "Docs planned per ymx_prepare_many call",
            unit="docs",
        )
        self._plan_pool_seconds = r.counter(
            "ytpu_plan_pool_seconds_total",
            "Seconds the native planner's pool spent in prepare, summed "
            "over rooms (the pool's own clock)",
            unit="s",
        )
        self._plan_pool_threads_started = r.counter(
            "ytpu_plan_pool_threads_started_total",
            "Threads the native planner's pool constructed: it keeps "
            "them between calls, so this stops at its widest call",
            unit="threads",
        )
        self._plan_pool_wakeups = r.counter(
            "ytpu_plan_pool_wakeups_total",
            "Workers the native planner's calls woke (a call plans on "
            "the flushing thread too)",
            unit="wakeups",
        )
        self._plan_room_max_seconds = r.gauge(
            "ytpu_plan_room_max_seconds",
            "Longest single room's native prepare, last flush that "
            "planned cold",
            unit="s",
        )
        self._rollbacks = r.counter(
            "ytpu_resilience_rollbacks_total",
            "Per-doc transactional flush rollbacks by reason",
            labelnames=("reason",),
        )
        self._dead_letters = r.counter(
            "ytpu_resilience_dead_letters_total",
            "Updates diverted to the dead-letter queue by reason",
            labelnames=("reason",),
        )
        self._dlq_depth = r.gauge(
            "ytpu_resilience_dead_letter_depth",
            "Dead letters currently held in the bounded queue",
        )
        self._dlq_dropped = r.counter(
            "ytpu_resilience_dead_letters_dropped_total",
            "Dead letters evicted (oldest-first) by the capacity bound",
        )
        self._docs_degraded = r.gauge(
            "ytpu_resilience_docs_degraded",
            "Docs currently in the degraded health state",
        )
        self._docs_quarantined = r.gauge(
            "ytpu_resilience_docs_quarantined",
            "Docs currently quarantined (traffic diverted to dead letters)",
        )
        self._readmissions = r.counter(
            "ytpu_resilience_readmissions_total",
            "Quarantined docs re-admitted after backoff expiry",
        )
        self._replayed = r.counter(
            "ytpu_resilience_replayed_total",
            "Dead letters successfully re-integrated by replay()",
        )
        self._replay_truncated = r.counter(
            "ytpu_resilience_dlq_replay_truncated_total",
            "Matching dead letters left queued by the per-invocation "
            "replay batch cap (YTPU_DLQ_REPLAY_BATCH)",
        )
        # device-memory cost attribution (ISSUE 4): refreshed once per
        # flush from the engine's persistent device buffers
        self._device_table_bytes = r.gauge(
            "ytpu_prof_device_table_bytes",
            "Live device bytes per persistent doc-table column group",
            unit="bytes",
            labelnames=("table",),
        )
        self._device_bytes_total = r.gauge(
            "ytpu_prof_device_bytes_total",
            "Total live persistent device bytes, by backend platform",
            unit="bytes",
            labelnames=("backend",),
        )
        self._slot_occupancy = r.gauge(
            "ytpu_prof_slot_occupancy",
            "Fraction of engine doc slots holding live rows",
            unit="ratio",
        )
        # pipelined flush (ISSUE 12): overlap/donation accounting
        self._flush_pipeline_depth = r.gauge(
            "ytpu_flush_pipeline_depth",
            "Max device dispatches in flight during the last flush "
            "(0 = synchronous / no dispatch)",
        )
        self._flush_pack_overlap = r.histogram(
            "ytpu_flush_pack_overlap_seconds",
            "Host pack time spent while a device dispatch was "
            "outstanding (not yet blocked on), per flush",
            unit="s",
        )
        self._flush_device_wait = r.histogram(
            "ytpu_flush_device_wait_seconds",
            "Host time blocked waiting on device dispatches, per flush",
            unit="s",
        )
        self._flush_donated = r.counter(
            "ytpu_flush_donated_total",
            "Flushes whose dispatches all updated donated device tables "
            "in place (zero table reallocation)",
        )
        self._flush_realloc_bytes = r.counter(
            "ytpu_flush_realloc_bytes_total",
            "Device bytes allocated by resident-table growth (the cost "
            "a donated steady-state flush avoids)",
            unit="bytes",
        )
        self._flush_rows_staged_bytes = r.counter(
            "ytpu_flush_rows_staged_bytes_total",
            "Bytes staged for the row scatter by compactions and "
            "hydrations (host allocation, transfer and device writes)",
            unit="bytes",
        )
        self._flush_rows_held_bytes = r.counter(
            "ytpu_flush_rows_held_bytes_total",
            "Bytes of rebuilt rows the staged rooms hold (over the "
            "staged bytes: how full a staged block is)",
            unit="bytes",
        )
        self._flush_rows_staged_blocks = r.counter(
            "ytpu_flush_rows_staged_blocks_total",
            "Blocks staged for the row scatter: one a width class of "
            "the rooms a flush compacts or hydrates",
            unit="blocks",
        )
        self._flush_rooms_dirty = r.counter(
            "ytpu_flush_rooms_dirty_total",
            "Slots the plan phase visited: the rooms that took an "
            "update since a flush last planned them, or that park structs",
            unit="rooms",
        )
        self._flush_rows_by_kind = {
            kind: r.counter(
                "ytpu_flush_rows_by_kind_total",
                "Rows the planner integrated, by kind: every row "
                "(planned), children of a nested type (nested), format "
                "items (format), entries under a parentSub (attr), type "
                "items (type)",
                labelnames=("kind",),
            ).labels(kind=kind)
            for kind in ("planned", "nested", "format", "attr", "type")
        }
        self._flush_segs_created = r.counter(
            "ytpu_flush_segments_created_total",
            "Segments flushes created: a nested type's list, a map "
            "key's chain",
        )
        self._flush_lww_overwritten = r.counter(
            "ytpu_flush_lww_overwritten_total",
            "Map entries flushes deleted: overwritten by a later writer "
            "(last writer wins) or removed",
        )
        self._flush_format_cleanup_deleted = r.counter(
            "ytpu_flush_format_cleanup_deleted_total",
            "Format items deleted by the clean-up after a remote "
            "transaction (what a Y.Doc's YText._callObserver deletes)",
        )
        self._segment_capacity = r.gauge(
            "ytpu_engine_segment_capacity",
            "Per-doc device list-head capacity after last flush",
        )
        self._flush_rooms_compact_looked = r.counter(
            "ytpu_flush_rooms_compact_looked_total",
            "Slots whose row count the compaction look read: the rooms "
            "planned since the look before",
            unit="rooms",
        )
        self._flush_rooms_compact_skipped = r.counter(
            "ytpu_flush_rooms_compact_skipped_total",
            "Rooms that had doubled since their last compaction and were "
            "not rebuilt, because the rebuild would have changed nothing",
            unit="rooms",
        )
        self._flush_rooms_row_loaded = r.counter(
            "ytpu_flush_rooms_row_loaded_total",
            "Rooms flushes wrote to the device as rows of a block: rooms "
            "loaded whole into slots that held no row",
            unit="rooms",
        )
        self._release_blanked_bytes = r.counter(
            "ytpu_release_blanked_bytes_total",
            "Bytes of device rows blanked in place by room releases "
            "(one whole row of each resident table a released slot)",
            unit="bytes",
        )

    # -- hot-path recording hooks -------------------------------------

    def record_flush(self, metrics: dict, row_capacity: int = 0) -> None:
        """One flush finished: ring append + registry update."""
        self.history.append(metrics)
        if not self.enabled:
            return
        self._flushes.inc()
        self._docs_flushed.inc(metrics["n_docs_flushed"])
        self._fallback_docs.set(metrics["n_fallback_docs"])
        self._pending_docs.set(metrics["n_pending_docs"])
        self._pending_depth.set(metrics["pending_depth"])
        self._occupancy.set(metrics["schedule_occupancy"])
        self._plan_threads.set(metrics["plan_threads"])
        self._row_capacity.set(row_capacity)
        self._flush_seconds.observe(metrics["t_total_s"])
        for ph, child in self._phase_children.items():
            child.observe(metrics[f"t_{ph}_s"])
        planned = (
            metrics["plan_segment_fast"] + metrics["plan_segment_residue"]
        )
        if planned:
            # idle flushes keep the last real verdict on the gauge
            self._segment_residue_fraction.set(
                metrics["plan_segment_residue"] / planned
            )
        self._flush_pipeline_depth.set(metrics["pipeline_depth"])
        self._flush_pack_overlap.observe(metrics["t_pack_overlap_s"])
        self._flush_device_wait.observe(metrics["t_device_wait_s"])
        if metrics["flush_donated"]:
            self._flush_donated.inc()
        if metrics["realloc_bytes"]:
            self._flush_realloc_bytes.inc(metrics["realloc_bytes"])
        if metrics["rows_staged_bytes"]:
            self._flush_rows_staged_bytes.inc(metrics["rows_staged_bytes"])
            self._flush_rows_held_bytes.inc(metrics["rows_held_bytes"])
            self._flush_rows_staged_blocks.inc(metrics["rows_staged_blocks"])
        self._flush_rooms_dirty.inc(metrics["rooms_dirty"])
        self._flush_rooms_compact_looked.inc(metrics["rooms_compact_looked"])
        self._flush_rooms_compact_skipped.inc(
            metrics["rooms_compact_skipped"]
        )
        if metrics["rooms_row_loaded"]:
            self._flush_rooms_row_loaded.inc(metrics["rooms_row_loaded"])
        if metrics["rows_planned"]:
            for kind, child in self._flush_rows_by_kind.items():
                child.inc(metrics[f"rows_{kind}"])
            self._flush_segs_created.inc(metrics["segs_created"])
            self._flush_lww_overwritten.inc(metrics["lww_overwritten"])
        if metrics["format_cleanup_deleted"]:
            self._flush_format_cleanup_deleted.inc(
                metrics["format_cleanup_deleted"]
            )
        self._segment_capacity.set(metrics["seg_cap"])
        if metrics["plan_pool_s"]:
            self._plan_pool_seconds.inc(metrics["plan_pool_s"])
            self._plan_room_max_seconds.set(metrics["plan_room_max_s"])
            self._plan_pool_threads_started.inc(metrics["plan_pool_started"])
            self._plan_pool_wakeups.inc(metrics["plan_pool_woken"])
        if metrics["release_blanked_bytes"]:
            self._release_blanked_bytes.inc(metrics["release_blanked_bytes"])

    def demoted(self, doc: int, reason: str) -> None:
        ctx = current_context()
        self.blackbox.record(
            "engine", "demote", guid=None, doc=doc, reason=reason,
            trace=ctx.trace_hex if ctx else None,
        )
        if not self.enabled:
            return
        self._demotions.labels(reason=reason).inc()
        self.tracer.instant("ytpu.demote", doc=doc, reason=reason)

    def update_emitted(self, n_bytes: int) -> None:
        if not self.enabled:
            return
        self._updates_emitted.inc()
        self._egress_bytes.inc(n_bytes)

    def native_prepare(self, n_docs: int, dt_s: float) -> None:
        if not self.enabled:
            return
        self._native_prepare_seconds.observe(dt_s)
        self._native_prepare_docs.observe(n_docs)

    def device_memory(
        self, tables: dict, backend: str, occupancy: float
    ) -> None:
        """Per-table live device bytes + slot occupancy (post-flush)."""
        if not self.enabled:
            return
        total = 0
        for table, nbytes in tables.items():
            self._device_table_bytes.labels(table=table).set(nbytes)
            total += nbytes
        self._device_bytes_total.labels(backend=backend).set(total)
        self._slot_occupancy.set(occupancy)

    # -- resilience hooks ----------------------------------------------

    def rollback(self, doc: int, reason: str) -> None:
        ctx = current_context()
        if ctx is not None:
            ctx.force("rollback")
        self.blackbox.record(
            "engine", "rollback", severity="warning", doc=doc,
            reason=reason, trace=ctx.trace_hex if ctx else None,
        )
        if not self.enabled:
            return
        self._rollbacks.labels(reason=reason).inc()
        self.tracer.instant(
            "ytpu.rollback", doc=doc, reason=reason,
            **({"trace": ctx.trace_hex} if ctx else {}),
        )

    def dead_lettered(self, reason: str, depth: int, dropped: int) -> None:
        ctx = current_context()
        if ctx is not None:
            ctx.force("dlq")
        self.blackbox.record(
            "resilience", "dead_letter", severity="warning",
            reason=reason, depth=depth,
            trace=ctx.trace_hex if ctx else None,
        )
        if not self.enabled:
            return
        # group by the reason's stable prefix so a poison storm with
        # per-byte exception detail cannot explode label cardinality
        self._dead_letters.labels(reason=reason.split(":", 1)[0]).inc()
        self._dlq_depth.set(depth)
        # `dropped` is the queue's monotonic total; counters only inc,
        # so mirror the delta since the last call
        seen = getattr(self, "_dlq_dropped_seen", 0)
        if dropped > seen:
            self._dlq_dropped.inc(dropped - seen)
            self._dlq_dropped_seen = dropped

    def health_gauges(self, degraded: int, quarantined: int) -> None:
        if not self.enabled:
            return
        self._docs_degraded.set(degraded)
        self._docs_quarantined.set(quarantined)

    def readmitted(self) -> None:
        if not self.enabled:
            return
        self._readmissions.inc()

    def replayed(self, n: int) -> None:
        if not self.enabled or n <= 0:
            return
        self._replayed.inc(n)

    def replay_truncated(self, n: int) -> None:
        if not self.enabled or n <= 0:
            return
        self._replay_truncated.inc(n)

    # -- exposition ----------------------------------------------------

    def metrics_text(self) -> str:
        return prometheus_text(self.registry, global_registry())

    def snapshot(self) -> dict:
        snap = registry_snapshot(self.registry, global_registry())
        snap["schema"] = SNAPSHOT_SCHEMA_VERSION
        latest = self.history.latest
        snap["flush"] = dict(latest) if latest is not None else None
        snap["flush_history"] = self.history.snapshot()
        snap["n_flushes_recorded"] = self.history.total
        return snap


class TierMetrics:
    """The ``ytpu_tier_*`` families (ISSUE 7): doc-lifecycle tiering.

    Registered unconditionally at provider construction (the schema
    checker instantiates ``TpuProvider(1)`` and expects every family
    live) on the provider's engine registry, so per-shard fleets get
    per-shard tier series like every other engine family."""

    TIERS = ("hot", "warm", "cold")

    def __init__(self, registry: MetricsRegistry):
        r = registry
        self._docs = r.gauge(
            "ytpu_tier_docs",
            "Docs resident per lifecycle tier (hot=device slot, "
            "warm=detached host columns, cold=WAL tier record)",
            labelnames=("tier",),
        )
        self._bytes = r.gauge(
            "ytpu_tier_bytes",
            "Approximate bytes held by demoted docs, per tier "
            "(warm: host mirrors; cold: encoded state blobs/records)",
            unit="bytes",
            labelnames=("tier",),
        )
        self._transitions = r.counter(
            "ytpu_tier_transitions_total",
            "Tier transitions, by source and destination tier",
            labelnames=("src", "dst"),
        )
        self._promote_seconds = r.histogram(
            "ytpu_tier_promote_seconds",
            "Wall time to promote one doc back into a device slot, "
            "by source tier",
            unit="s",
            labelnames=("src",),
        )
        self._demote_seconds = r.histogram(
            "ytpu_tier_demote_seconds",
            "Wall time to demote one doc, by destination tier",
            unit="s",
            labelnames=("dst",),
        )
        self._evictions = r.counter(
            "ytpu_tier_evictions_total",
            "Hot docs auto-demoted to admit another doc (the path that "
            "previously raised ProviderFullError)",
        )
        self._gc_passes = r.counter(
            "ytpu_tier_gc_passes_total",
            "Forced tombstone/GC compaction passes over hot docs",
        )
        self._gc_rows = r.counter(
            "ytpu_tier_gc_reclaimed_rows_total",
            "Packed-column rows dropped by tier GC compaction",
        )
        self._gc_bytes = r.counter(
            "ytpu_tier_gc_reclaimed_bytes_total",
            "Approximate host-mirror bytes reclaimed by tier GC "
            "compaction",
            unit="bytes",
        )
        # pre-resolve label children: transitions/demotes run inside the
        # admission path
        self._docs_by_tier = {
            t: self._docs.labels(tier=t) for t in self.TIERS
        }
        self._bytes_by_tier = {
            t: self._bytes.labels(tier=t) for t in self.TIERS
        }

    def occupancy(self, counts: dict, nbytes: dict) -> None:
        for t in self.TIERS:
            self._docs_by_tier[t].set(counts.get(t, 0))
            self._bytes_by_tier[t].set(nbytes.get(t, 0))

    def transition(self, src: str, dst: str) -> None:
        self._transitions.labels(src=src, dst=dst).inc()

    def promoted(self, src: str, dt_s: float) -> None:
        self._promote_seconds.labels(src=src).observe(dt_s)

    def demoted(self, dst: str, dt_s: float) -> None:
        self._demote_seconds.labels(dst=dst).observe(dt_s)

    def evicted(self) -> None:
        self._evictions.inc()

    def gc(self, rows: int, nbytes: int) -> None:
        self._gc_passes.inc()
        if rows > 0:
            self._gc_rows.inc(rows)
        if nbytes > 0:
            self._gc_bytes.inc(nbytes)
