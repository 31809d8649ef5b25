"""yjs_tpu.obs: metrics registry, flush-history ring, span tracing,
exposition (ISSUE 1).

Fast host-only tests: ring semantics, histogram bucket/percentile math,
Chrome-trace JSON validity, flush-metrics schema parity across every
flush mode, Prometheus text, and the provider's defensive metrics copy.
"""

import json
import math
import os

import pytest

import yjs_tpu as Y
from yjs_tpu.obs import FLUSH_METRICS_SCHEMA, global_registry, new_flush_metrics
from yjs_tpu.obs.history import FlushHistory
from yjs_tpu.obs.registry import Histogram, MetricsRegistry
from yjs_tpu.ops import BatchEngine
from yjs_tpu.ops.native_mirror import native_plan_available
from yjs_tpu.provider import TpuProvider
from yjs_tpu.updates import encode_state_as_update


def _update(text="hello"):
    d = Y.Doc(gc=False)
    d.get_text("text").insert(0, text)
    return encode_state_as_update(d)


# -- flush-history ring ------------------------------------------------------


def test_ring_bounded_fifo_and_alias():
    ring = FlushHistory(maxlen=4)
    entries = [{"i": i} for i in range(6)]
    for e in entries:
        ring.append(e)
    assert len(ring) == 4
    # FIFO eviction: the two oldest entries are gone
    assert [m["i"] for m in ring] == [2, 3, 4, 5]
    assert ring[0] is entries[2]
    # latest is the SAME object as the newest append (the
    # last_flush_metrics alias contract), while snapshot() copies
    assert ring.latest is entries[-1]
    assert ring.snapshot() == [{"i": 2}, {"i": 3}, {"i": 4}, {"i": 5}]
    assert ring.snapshot()[0] is not entries[2]
    assert ring.total == 6


def test_engine_ring_one_entry_per_flush(monkeypatch):
    monkeypatch.setenv("YTPU_OBS_HISTORY", "3")
    eng = BatchEngine(2)
    for k in range(5):
        eng.queue_update(0, _update(f"v{k}"))
        eng.flush()
    assert eng.obs.history.total == 5
    assert len(eng.obs.history) == 3  # bounded by YTPU_OBS_HISTORY
    # last_flush_metrics is the newest ring entry ITSELF, not a copy
    assert eng.last_flush_metrics is eng.obs.history.latest
    assert eng.last_flush_metrics["n_docs_flushed"] == 1
    # empty flushes are real flushes: they get a ring entry too
    eng.flush()
    assert eng.obs.history.total == 6
    assert eng.last_flush_metrics["n_docs_flushed"] == 0


# -- histogram math ----------------------------------------------------------


def test_histogram_exact_stats_and_percentiles():
    h = Histogram("t")
    for v in range(1, 1001):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 1000
    assert s["sum"] == pytest.approx(500500.0)
    assert s["min"] == 1.0
    assert s["max"] == 1000.0
    # 8 buckets/octave => quantiles land within ~4.5% of the true value
    assert s["p50"] == pytest.approx(500.0, rel=0.05)
    assert s["p95"] == pytest.approx(950.0, rel=0.05)
    assert s["p99"] == pytest.approx(990.0, rel=0.05)


def test_histogram_quantile_clamped_and_zero_bucket():
    h = Histogram("t")
    h.observe(42.0)
    # single observation: every quantile IS that value (midpoint clamped
    # into [min, max])
    assert h.quantile(0.5) == 42.0
    assert h.quantile(0.99) == 42.0
    z = Histogram("z")
    z.observe(0.0)
    z.observe(0.0)
    z.observe(8.0)
    assert z.quantile(0.5) == 0.0  # underflow bucket reports min
    assert z.summary()["max"] == 8.0
    assert Histogram("e").summary() == {
        "count": 0, "sum": 0.0, "min": 0.0, "max": 0.0,
        "p50": 0.0, "p95": 0.0, "p99": 0.0,
    }


def test_histogram_bucket_relative_error_across_decades():
    # the geometric-midpoint readback stays within the 8-per-octave bound
    # (2**(1/16) - 1 ~ 4.4%) from microseconds to kiloseconds
    for v in (1e-6, 3.7e-4, 0.02, 1.5, 88.0, 4096.0):
        h = Histogram("t")
        for _ in range(100):
            h.observe(v)
        assert h.quantile(0.5) == pytest.approx(v, rel=0.045)


def test_registry_kind_mismatch_and_reuse():
    r = MetricsRegistry()
    c = r.counter("x", "help")
    assert r.counter("x") is c  # re-registration returns the family
    with pytest.raises(ValueError):
        r.gauge("x")
    lab = r.counter("y", labelnames=("k",))
    lab.labels(k="a").inc(2)
    lab.labels(k="a").inc()
    assert lab.labels(k="a").value == 3
    assert lab.labels(k="b").value == 0


# -- flush-metrics schema ----------------------------------------------------


def test_new_flush_metrics_rejects_unknown_keys():
    m = new_flush_metrics(n_demoted=2)
    assert m["n_demoted"] == 2
    assert set(m) == set(FLUSH_METRICS_SCHEMA)
    with pytest.raises(KeyError):
        new_flush_metrics(no_such_metric=1)


def test_flush_metrics_schema_identical_across_modes():
    """Native planner / pure-Python planner / a flush with nothing
    staged: one key set (FLUSH_METRICS_SCHEMA), no mode-specific drift."""
    keysets = {}
    for mode in ("native", "python"):
        if mode == "python":
            os.environ["YTPU_NO_NATIVE_PLAN"] = "1"
        try:
            eng = BatchEngine(2)
            eng.queue_update(0, _update())
            eng.queue_update(1, _update("other"))
            eng.flush()
            keysets[mode] = set(eng.last_flush_metrics)
            eng.flush()
            keysets[mode + ", empty"] = set(eng.last_flush_metrics)
        finally:
            os.environ.pop("YTPU_NO_NATIVE_PLAN", None)
    for mode, keys in keysets.items():
        assert keys == set(FLUSH_METRICS_SCHEMA), mode


# -- span tracing ------------------------------------------------------------


def test_chrome_trace_json_valid_and_phased():
    eng = BatchEngine(2)
    n_flushes = 2
    for k in range(n_flushes):
        eng.queue_update(0, _update(f"flush{k}"))
        eng.flush()
    trace = eng.export_chrome_trace()
    # loadable: a strict JSON round trip of the Perfetto container shape
    loaded = json.loads(json.dumps(trace))
    assert loaded["displayTimeUnit"] == "ms"
    events = loaded["traceEvents"]
    assert events
    ts = [e["ts"] for e in events]
    assert ts == sorted(ts)  # monotonic (metadata events sit at ts 0.0)
    for e in events:
        assert e["ph"] in ("X", "i", "M", "s", "f")
        if e["ph"] == "X":  # complete events carry a duration
            assert e["dur"] >= 0.0
        assert {"name", "pid", "tid", "cat"} <= set(e)
    # pid/tid metadata present so Perfetto names the process lanes
    meta = [e for e in events if e["ph"] == "M"]
    assert {m["name"] for m in meta} >= {"process_name", "thread_name"}
    names = [e["name"] for e in events]
    # one flush span per flush, one span per host phase per flush
    assert names.count("ytpu.flush") == n_flushes
    for phase in ("compact", "emit"):
        assert names.count(f"ytpu.{phase}") == n_flushes
    # work flushed every time, so plan+pack+dispatch ran each flush (the
    # chunked batched path emits one span per chunk on top of the
    # prepare-scan span: >=)
    for phase in ("plan", "pack", "dispatch"):
        assert names.count(f"ytpu.{phase}") >= n_flushes


def test_trace_instant_on_demotion():
    eng = BatchEngine(1)
    d = Y.Doc(gc=False)
    d.get_text("text").insert(0, "x")
    sub = Y.Doc(gc=False)
    d.get_map("m").set("sub", sub)  # subdoc -> device demotion
    eng.queue_update(0, encode_state_as_update(d))
    eng.flush()
    assert len(eng.fallback) == 1
    events = eng.export_chrome_trace()["traceEvents"]
    inst = [e for e in events if e["ph"] == "i" and e["name"] == "ytpu.demote"]
    assert len(inst) == 1
    assert inst[0]["s"] == "t"
    assert inst[0]["args"]["doc"] == 0
    # and the labeled demotion counter matches the ledger
    fams = dict.fromkeys(eng.obs.registry.names())
    assert "ytpu_engine_demotions_total" in fams
    total = sum(
        series.value
        for _labels, series in eng.obs.registry.get(
            "ytpu_engine_demotions_total"
        ).samples()
    )
    assert total == len(eng.demotions) == 1


def test_tracer_save(tmp_path):
    eng = BatchEngine(1)
    eng.queue_update(0, _update())
    eng.flush()
    p = eng.save_trace(str(tmp_path / "trace.json"))
    with open(p) as f:
        assert json.load(f)["traceEvents"]


def test_release_spans_and_blanked_bytes_counter():
    """A release opens ``ytpu.release`` around the engine branch of
    ``release_doc`` and ``ytpu.release.blank`` around the dispatch of
    the blanking program inside it; the bytes blanked are counted by
    the next flush (``release_blanked_bytes``) and by the registry."""
    prov = TpuProvider(4)
    for room in ("a", "b", "c"):
        prov.receive_update(room, _update(room * 40))
    prov.flush()
    eng = prov.engine
    for room in ("a", "c"):
        prov.release_doc(room)
    spans = {
        name: [
            e for e in eng.export_chrome_trace()["traceEvents"]
            if e["ph"] == "X" and e["name"] == name
        ]
        for name in ("ytpu.release", "ytpu.release.blank")
    }
    assert [len(v) for v in spans.values()] == [2, 2]
    for outer, inner in zip(*spans.values()):
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    counter = eng.obs.registry.get("ytpu_release_blanked_bytes_total")
    assert counter.value == 0  # counted by the flush that follows
    prov.receive_update("b", _update("more"))
    prov.flush()
    row = (eng._cap + 1) * 5 + (eng._seg_cap + 1) * 4
    assert eng.last_flush_metrics["release_blanked_bytes"] == 2 * row
    assert counter.value == 2 * row
    assert "ytpu_release_blanked_bytes_total" in prov.metrics_text()
    # a room that never reached the device has no rows to blank
    cold = TpuProvider(2, backend="cpu")
    cold.receive_update("a", _update())
    cold.release_doc("a")
    names = [e["name"] for e in cold.engine.export_chrome_trace()["traceEvents"]]
    assert "ytpu.release" in names and "ytpu.release.blank" not in names


# -- exposition --------------------------------------------------------------


def test_prometheus_text_dump():
    prov = TpuProvider(2)
    prov.receive_update("room", _update())
    prov.flush()
    prov.handle_sync_message("room", prov.sync_step1("room"))
    text = prov.metrics_text()
    assert "# TYPE ytpu_engine_flushes_total counter" in text
    assert "# TYPE ytpu_engine_fallback_docs gauge" in text
    # histograms render as summaries with the three quantile series
    assert "# TYPE ytpu_engine_flush_seconds summary" in text
    assert 'ytpu_engine_flush_seconds{quantile="0.5"}' in text
    assert 'ytpu_engine_flush_seconds{quantile="0.95"}' in text
    assert "ytpu_engine_flush_seconds_count" in text
    assert 'ytpu_engine_phase_seconds{phase="plan",quantile="0.5"}' in text
    assert "ytpu_provider_updates_received_total 1" in text
    assert 'ytpu_provider_sync_messages_total{type="step1"} 1' in text
    # every line is name{labels} value or a comment
    for line in text.strip().splitlines():
        assert line.startswith("#") or len(line.rsplit(" ", 1)) == 2


def test_json_snapshot_round_trips():
    eng = BatchEngine(1)
    eng.queue_update(0, _update())
    eng.flush()
    snap = json.loads(json.dumps(eng.metrics_snapshot()))
    assert snap["schema"] == 1
    assert snap["counters"]["ytpu_engine_flushes_total"][""] == 1
    assert snap["flush"] == eng.last_flush_metrics
    assert snap["flush_history"] == [eng.last_flush_metrics]
    assert snap["n_flushes_recorded"] == 1
    assert snap["histograms"]["ytpu_engine_flush_seconds"][""]["count"] == 1


def test_provider_metrics_is_defensive_copy():
    prov = TpuProvider(1)
    prov.receive_update("r", _update())
    prov.flush()
    m = prov.metrics
    assert set(m) == set(FLUSH_METRICS_SCHEMA)
    m["n_docs_flushed"] = 999
    m.clear()
    assert prov.metrics["n_docs_flushed"] == 1
    assert prov.engine.last_flush_metrics["n_docs_flushed"] == 1
    # history snapshot is copies too
    prov.metrics_history[0]["n_docs_flushed"] = 999
    assert prov.metrics["n_docs_flushed"] == 1


def test_sync_protocol_frame_counters():
    fam = global_registry().get("ytpu_sync_messages_total")
    if fam is None:  # process-global obs disabled by the environment
        pytest.skip("YTPU_OBS_DISABLED in this process")

    def val(direction, typ):
        return fam.labels(dir=direction, type=typ).value

    before = {
        (d, t): val(d, t)
        for d in ("read", "write")
        for t in ("step1", "step2", "update")
    }
    from yjs_tpu.lib0.decoding import Decoder
    from yjs_tpu.lib0.encoding import Encoder
    from yjs_tpu.sync import protocol

    a, b = Y.Doc(gc=False), Y.Doc(gc=False)
    a.get_text("text").insert(0, "sync me")
    enc = Encoder()
    protocol.write_sync_step1(enc, b)
    reply = Encoder()
    protocol.read_sync_message(Decoder(enc.to_bytes()), reply, a)
    protocol.read_sync_message(Decoder(reply.to_bytes()), Encoder(), b)
    upd = Encoder()
    protocol.write_update(upd, encode_state_as_update(a))
    protocol.read_sync_message(Decoder(upd.to_bytes()), Encoder(), b)
    assert b.get_text("text").to_string() == "sync me"
    assert val("write", "step1") - before[("write", "step1")] == 1
    assert val("read", "step1") - before[("read", "step1")] == 1
    assert val("write", "step2") - before[("write", "step2")] == 1
    assert val("read", "step2") - before[("read", "step2")] == 1
    assert val("write", "update") - before[("write", "update")] == 1
    assert val("read", "update") - before[("read", "update")] == 1


def test_obs_disabled_keeps_flush_metrics(monkeypatch):
    monkeypatch.setenv("YTPU_OBS_DISABLED", "1")
    eng = BatchEngine(1)
    assert not eng.obs.enabled
    eng.queue_update(0, _update())
    eng.flush()
    # the compatibility surface survives: ring + last_flush_metrics work
    assert set(eng.last_flush_metrics) == set(FLUSH_METRICS_SCHEMA)
    assert eng.last_flush_metrics["n_docs_flushed"] == 1
    assert len(eng.obs.history) == 1
    # but nothing is registered, recorded, or traced for this engine
    assert eng.obs.registry.names() == []
    assert "ytpu_engine_" not in eng.metrics_text()
    assert eng.export_chrome_trace()["traceEvents"] == []


def test_metrics_schema_matches_readme():
    """Every registered family is in README's Observability table and
    vice versa (the scripts/check_metrics_schema.py contract, enforced
    in tier-1 so docs can't drift)."""
    import importlib.util
    from pathlib import Path

    root = Path(__file__).resolve().parent.parent
    spec = importlib.util.spec_from_file_location(
        "check_metrics_schema", root / "scripts" / "check_metrics_schema.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    live = mod.registered_names()
    if not live:
        pytest.skip("YTPU_OBS_DISABLED in this process")
    doc = mod.documented_names((root / "README.md").read_text())
    assert live - doc == set(), "registered but undocumented"
    assert doc - live == set(), "documented but not registered"


def test_native_prepare_histograms_on_batched_path():
    eng = BatchEngine(2)
    eng.queue_update(0, _update())
    eng.queue_update(1, _update("two"))
    eng.flush()
    from yjs_tpu.ops.native_mirror import native_plan_available

    fam = eng.obs.registry.get("ytpu_native_prepare_many_docs")
    if not native_plan_available():
        assert fam.count == 0  # python planner: batched path never runs
        return
    assert fam.count == 1
    assert fam.summary()["max"] == 2.0  # both docs planned in one call


def test_the_pools_own_clock_rides_the_flush_metrics():
    """``plan_room_max_s`` / ``plan_pool_s`` are measured inside
    ``ymx_prepare_many``: the longest room's prepare and the sum over
    rooms, 0 in a flush that planned nothing cold."""
    from yjs_tpu.ops.native_mirror import native_plan_available

    if not native_plan_available():
        pytest.skip("native plan core unavailable")
    eng = BatchEngine(3)
    for i, word in enumerate(("one", "two", "three")):
        eng.queue_update(i, _update(word))
    eng.flush()
    m = eng.last_flush_metrics
    assert 0.0 < m["plan_room_max_s"] <= m["plan_pool_s"] <= 3 * m["plan_room_max_s"]
    reg = eng.obs.registry
    assert reg.get("ytpu_plan_pool_seconds_total").value == m["plan_pool_s"]
    assert reg.get("ytpu_plan_room_max_seconds").value == m["plan_room_max_s"]
    eng.flush()
    m = eng.last_flush_metrics
    assert m["plan_room_max_s"] == m["plan_pool_s"] == 0.0
    assert reg.get("ytpu_plan_pool_seconds_total").value > 0.0


@pytest.mark.parametrize("threads", ["1", "3"])
def test_the_cores_laps_ride_the_flush_metrics(monkeypatch, threads):
    """The native core's laps are a clock of the flush's: ``plan_pool_s``
    by the phase of a room's prepare, summed over the flush's chunks,
    and what the pool cost the flushing thread in handing it the call
    and in waiting for its last worker (0 on the serial branch), with
    the workers its calls woke and the threads they had to construct;
    every key 0 in a flush that planned nothing cold, and no registry
    family for any but the pool's two counters."""
    from yjs_tpu.ops.native_mirror import (
        PLAN_POOL_COUNTS, PLAN_TIMES, native_plan_available,
    )

    if not native_plan_available():
        pytest.skip("native plan core unavailable")
    monkeypatch.setenv("YTPU_PLAN_THREADS", threads)
    monkeypatch.setenv("YTPU_FLUSH_CHUNK", "2")  # two calls a flush
    phases, pool = PLAN_TIMES[2:7], PLAN_TIMES[7:]
    assert set(PLAN_TIMES + PLAN_POOL_COUNTS) <= set(FLUSH_METRICS_SCHEMA)
    eng = BatchEngine(4)
    families = set(eng.obs.registry.names())
    assert {
        "ytpu_plan_pool_threads_started_total", "ytpu_plan_pool_wakeups_total",
    } <= families
    # a pasted page a room: the core reckons a call's work from its
    # rooms and staged bytes, and wakes nobody for under a millisecond
    for i, word in enumerate(("one", "two", "three", "four")):
        eng.queue_update(i, _update(word * 10000))
    eng.flush()
    m = eng.last_flush_metrics
    assert all(m[k] > 0.0 for k in phases)
    assert sum(m[k] for k in phases) <= m["plan_pool_s"]
    woken = eng.obs.registry.get("ytpu_plan_pool_wakeups_total")
    started = eng.obs.registry.get("ytpu_plan_pool_threads_started_total")
    if threads == "1":
        assert m["plan_threads"] == 1 and all(m[k] == 0.0 for k in pool)
        assert m["plan_pool_woken"] == m["plan_pool_started"] == 0
    else:
        # two rooms a call: the flushing thread and one worker, twice
        assert m["plan_threads"] == 2 and all(m[k] > 0.0 for k in pool)
        assert m["plan_pool_woken"] == 2 and m["plan_pool_started"] <= 1
    assert woken.value == m["plan_pool_woken"]
    assert started.value == m["plan_pool_started"]
    assert eng.obs.snapshot()["flush_history"][-1]["plan_scan_s"] == m["plan_scan_s"]
    assert set(eng.obs.registry.names()) == families
    eng.flush()
    m = eng.last_flush_metrics
    assert all(m[k] == 0.0 for k in PLAN_TIMES)
    assert m["plan_threads"] == 1
    assert m["plan_pool_woken"] == m["plan_pool_started"] == 0


@pytest.mark.parametrize("planner", ["native", "python"])
def test_what_a_flush_looked_at_rides_the_flush_metrics(monkeypatch, planner):
    """``rooms_dirty`` / ``rooms_compact_looked`` are in the schema, in
    the metrics of every exit of a flush (the empty one too) and in the
    registry, and ``ytpu.compact.scan`` opens once a flush whatever the
    sets hold."""
    if planner == "python":
        monkeypatch.setenv("YTPU_NO_NATIVE_PLAN", "1")
    assert {
        "rooms_dirty", "rooms_compact_looked", "rooms_compact_skipped",
    } <= set(FLUSH_METRICS_SCHEMA)
    eng = BatchEngine(8)
    reg = eng.obs.registry
    looked_at = []  # (rooms_dirty, rooms_compact_looked) a flush
    for rooms in ((1, 4, 6), (4,), (), (), (0, 1, 2, 3, 4, 5, 6, 7)):
        for i in rooms:
            eng.queue_update(i, _update(f"room {i} of {len(rooms)}"))
        eng.flush()
        m = eng.last_flush_metrics
        assert set(m) == set(FLUSH_METRICS_SCHEMA)
        looked_at.append((m["rooms_dirty"], m["rooms_compact_looked"]))
    # a look reads the rooms the flush before it planned
    assert looked_at == [(3, 0), (1, 3), (0, 1), (0, 0), (8, 0)]
    assert reg.get("ytpu_flush_rooms_dirty_total").value == 12
    assert reg.get("ytpu_flush_rooms_compact_looked_total").value == 4
    names = [e["name"] for e in eng.obs.tracer.trace_events()]
    assert names.count("ytpu.compact.scan") == names.count("ytpu.flush") == 5


@pytest.mark.parametrize("planner", ["native", "python"])
def test_rooms_the_look_did_not_rebuild_are_counted(monkeypatch, planner):
    """``rooms_compact_skipped`` and ``ytpu_flush_rooms_compact_skipped_
    total``: a room that has doubled and has nothing to merge is counted
    and not rebuilt; one that has runs to merge is rebuilt and not
    counted.  A Python mirror is not asked: it is rebuilt, and the
    counter stays 0."""
    if planner == "python":
        monkeypatch.setenv("YTPU_NO_NATIVE_PLAN", "1")
    elif not native_plan_available():
        pytest.skip("native plan core unavailable")
    eng = BatchEngine(4, compact_min_rows=16)
    reg = eng.obs.registry
    skipped = reg.get("ytpu_flush_rooms_compact_skipped_total")
    docs = {}
    for i in (0, 2):  # 20 prepended characters, one update: 20 rows
        d = docs[i] = Y.Doc(gc=False)
        d.client_id = 7 + i
        t = d.get_text("text")
        d.transact(lambda _txn: [t.insert(0, "ab"[k % 2]) for k in range(20)])
        eng.queue_update(i, encode_state_as_update(d))
    eng.flush()
    assert eng.last_flush_metrics["rooms_compact_skipped"] == 0

    def type_at_end(i, n):
        d = docs[i]
        for _ in range(n):
            sv = Y.encode_state_vector(d)
            d.get_text("text").insert(len(d.get_text("text")), "x")
            eng.queue_update(i, encode_state_as_update(d, sv))

    type_at_end(0, 1)
    eng.flush()  # looks at both loaded rooms
    m = eng.last_flush_metrics
    native = planner == "native"
    assert m["rooms_compact_looked"] == 2
    assert m["rooms_compact_skipped"] == (2 if native else 0)
    assert (eng.last_compaction is None) == native
    assert skipped.value == (2 if native else 0)
    type_at_end(2, 30)  # 30 keystrokes, a row each until they are merged
    eng.flush()
    eng.flush()  # looks at room 2: 50 rows, its typing merges into one
    m = eng.last_flush_metrics
    assert (m["rooms_compact_looked"], m["rooms_compact_skipped"]) == (1, 0)
    assert eng.last_compaction == [
        {"doc": 2, "rows_before": 50, "rows_after": 21}
    ]
    assert skipped.value == (2 if native else 0)
    assert set(m) == set(FLUSH_METRICS_SCHEMA)
    for i, d in docs.items():
        assert eng.text(i) == d.get_text("text").to_string()
