"""yjs_tpu.obs.slo: convergence-latency SLOs (ISSUE 4 tentpole).

Covers: the zero-wire-change update key (first-struct id + digest
fallback), the origin clock, the receive→integrate→visible pipeline
under a fake clock, multiwindow burn-rate transitions (ok / warning /
page, incl. the required two-provider breach→page test), window
aging, duplicate/rejected handling, bounded pending state, env knobs,
and the CPU-doc protocol seam.  The incremental burn windows (ISSUE 28)
are held, value for value, to the plain walk over the ring that they
replaced, kept here as the reference.
"""

import collections
import json
import random
import sys
import threading

import pytest

import yjs_tpu as Y
from yjs_tpu.lib0.decoding import Decoder
from yjs_tpu.lib0.encoding import Encoder
from yjs_tpu.obs.registry import MetricsRegistry
from yjs_tpu.obs import slo as slo_module
from yjs_tpu.obs.slo import (
    PAGE_BURN,
    WARN_BURN,
    ConvergenceTracker,
    OriginClock,
    update_key,
)
from yjs_tpu.provider import TpuProvider
from yjs_tpu.sync import protocol
from yjs_tpu.updates import encode_state_as_update, encode_state_vector


def _update(text="hello", client=None):
    d = Y.Doc(gc=False)
    if client is not None:
        d.client_id = client
    d.get_text("text").insert(0, text)
    return encode_state_as_update(d)


class _Clock:
    """Injectable deterministic clock for the tracker's ``now``."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _tracker(clock, **kw):
    kw.setdefault("origins", OriginClock())
    return ConvergenceTracker(MetricsRegistry(), now=clock, **kw)


def _key_bytes(i):
    """Unique unparseable payloads (numClients=0 -> digest fallback)."""
    return b"\x00" + str(i).encode()


# -- update keys -------------------------------------------------------------


def test_update_key_is_first_struct_client_clock():
    u = _update("hi", client=12345)
    assert update_key(u) == (12345, 0)
    # the key is computed from the BYTES both sides transport: identical
    # bytes, identical key, no wire change needed
    assert update_key(bytes(u)) == update_key(u)


def test_update_key_delete_only_digest_fallback():
    d = Y.Doc(gc=False)
    t = d.get_text("text")
    t.insert(0, "abc")
    sv = encode_state_vector(d)
    t.delete(0, 3)
    delete_only = encode_state_as_update(d, sv)
    key = update_key(delete_only)
    assert key[0] == -1  # no struct blocks: digest fallback
    assert key == update_key(delete_only)  # deterministic
    assert key != update_key(b"\x00other")


def test_update_key_garbage_never_raises():
    for junk in (b"", b"\xff\xff\xff\xff", b"\x00"):
        client, _ = update_key(junk)
        assert client == -1


# -- origin clock ------------------------------------------------------------


def test_origin_clock_first_sighting_wins_and_bounded():
    oc = OriginClock(maxlen=4)
    oc.record_once("k", 1.0)
    oc.record_once("k", 99.0)  # later sighting must not overwrite
    assert oc.lookup("k") == 1.0
    for i in range(6):
        oc.record_once(f"x{i}", float(i))
    assert len(oc) <= 4
    assert oc.lookup("k") is None  # oldest evicted


# -- the pipeline under a fake clock -----------------------------------------


def test_pipeline_stages_and_latency_histogram():
    clock = _Clock()
    tr = _tracker(clock, target_ms=250.0)
    u = _update("stage test", client=7)
    clock.t = 1.0
    key = tr.receive(u)
    clock.t = 1.01
    tr.integrated(key)
    clock.t = 1.05
    assert tr.visible() == 1
    snap = tr.snapshot()
    assert snap["completed"] == 1 and snap["pending"] == 0
    assert snap["state"] == "ok"  # 50ms < 250ms target
    lat = tr._latency.summary()
    assert lat["count"] == 1
    assert lat["max"] == pytest.approx(0.05, abs=1e-6)
    # stage decomposition: receive 0 (origin floored at receive),
    # integrate 10ms, visible 40ms
    assert tr._stage["integrate"].summary()["max"] == pytest.approx(
        0.01, abs=1e-6
    )
    assert tr._stage["visible"].summary()["max"] == pytest.approx(
        0.04, abs=1e-6
    )


def test_origin_stamp_measures_true_end_to_end():
    clock = _Clock()
    tr = _tracker(clock, target_ms=250.0)
    u = _update("origin test", client=9)
    clock.t = 0.0
    tr.origin(u)  # emitted now (the broadcasting provider stamps)
    clock.t = 0.4  # transport delay
    key = tr.receive(u)
    tr.integrated(key)
    clock.t = 0.5
    tr.visible()
    # latency is origin->visible (500ms), not receive->visible (100ms)
    assert tr._latency.summary()["max"] == pytest.approx(0.5, abs=1e-6)
    assert tr.snapshot()["state"] == "page"  # 500ms > 250ms, 100% breach


def test_duplicate_delivery_completes_once():
    clock = _Clock()
    tr = _tracker(clock)
    u = _update("dup", client=3)
    k1 = tr.receive(u)
    k2 = tr.receive(u)  # duplicate: first delivery wins
    assert k1 == k2
    tr.integrated(k1)
    assert tr.visible() == 1
    assert tr.visible() == 0  # nothing left
    assert tr.snapshot()["completed"] == 1


def test_rejected_updates_stop_tracking():
    clock = _Clock()
    tr = _tracker(clock)
    key = tr.receive(_update("bad", client=4))
    tr.rejected(key)
    assert tr.visible() == 0
    assert tr.snapshot()["pending"] == 0


def test_unintegrated_pending_survives_flush():
    clock = _Clock()
    tr = _tracker(clock)
    tr.receive(_update("parked", client=5))  # never integrated (parked)
    assert tr.visible() == 0  # a flush does NOT complete it
    assert tr.snapshot()["pending"] == 1


def test_pending_bounded():
    clock = _Clock()
    tr = _tracker(clock, max_pending=8)
    for i in range(50):
        tr.receive(_key_bytes(i))
    assert tr.snapshot()["pending"] <= 8


# -- burn-rate state machine -------------------------------------------------


def _drive(tr, clock, n, breach_every=None, dt=0.001, breach_s=1.0):
    """Complete ``n`` convergences; every ``breach_every``-th one is slow."""
    for i in range(n):
        clock.t += dt
        key = tr.receive(_key_bytes(i))
        tr.integrated(key)
        if breach_every and i % breach_every == 0:
            clock.t += breach_s
        tr.visible()


def test_all_fast_stays_ok():
    clock = _Clock()
    tr = _tracker(clock, target_ms=250.0, window_s=1200.0, objective=0.99)
    _drive(tr, clock, 50)
    snap = tr.snapshot()
    assert snap["state"] == "ok"
    assert snap["burn_rates"]["long"] == 0.0


def test_warning_state_at_moderate_burn():
    clock = _Clock()
    tr = _tracker(clock, target_ms=250.0, window_s=1200.0, objective=0.99)
    # 10% breaches against a 1% budget -> burn 10: warning (>=6, <14.4)
    _drive(tr, clock, 100, breach_every=10)
    snap = tr.snapshot()
    assert snap["state"] == "warning"
    assert snap["burn_rates"]["long"] == pytest.approx(10.0)
    assert snap["windows"]["long"]["breached"] == 10


def test_page_state_at_high_burn():
    clock = _Clock()
    tr = _tracker(clock, target_ms=250.0, window_s=1200.0, objective=0.99)
    # 20% breaches -> burn 20 on BOTH windows: page
    _drive(tr, clock, 50, breach_every=5)
    assert tr.snapshot()["state"] == "page"


def test_breaches_age_out_of_the_windows():
    clock = _Clock()
    tr = _tracker(clock, target_ms=250.0, window_s=10.0, objective=0.99)
    _drive(tr, clock, 10, breach_every=2)  # heavy breaching -> page
    assert tr.snapshot()["state"] == "page"
    clock.t += 100.0  # both windows age out completely
    snap = tr.snapshot()
    assert snap["state"] == "ok"
    assert snap["windows"]["long"]["total"] == 0


def test_env_knobs_configure_tracker(monkeypatch):
    monkeypatch.setenv("YTPU_SLO_CONVERGENCE_MS", "42")
    monkeypatch.setenv("YTPU_SLO_WINDOW", "60")
    monkeypatch.setenv("YTPU_SLO_OBJECTIVE", "0.999")
    tr = ConvergenceTracker(MetricsRegistry(), origins=OriginClock())
    assert tr.target_ms == 42.0
    assert tr.window_s == 60.0
    assert tr.short_window_s == 5.0  # window/12
    assert tr.objective == 0.999


def test_snapshot_is_json_able():
    clock = _Clock()
    tr = _tracker(clock)
    _drive(tr, clock, 3)
    snap = json.loads(json.dumps(tr.snapshot()))
    assert set(snap) >= {
        "target_ms", "window_s", "objective", "state", "burn_rates",
        "windows", "completed", "pending",
    }


# -- the incremental burn windows against the plain walk (ISSUE 28) ----------


def _plain_walk(events, now, short, long, objective):
    """The burn pass as ``_update_state`` made it up to PR 27: copy the
    ring of the last ``max_events`` completions and walk it from the
    newest end, once a window.  The reference the running counts are
    held to."""
    budget = max(1e-9, 1.0 - objective)
    burns = {}
    windows = {}
    events = tuple(events)
    for wname, wlen in (("short", short), ("long", long)):
        total = breached = 0
        for t, b in reversed(events):
            if now - t > wlen:
                break
            total += 1
            if b:
                breached += 1
        frac = breached / total if total else 0.0
        burns[wname] = frac / budget
        windows[wname] = {
            "total": total,
            "breached": breached,
            "breach_fraction": frac,
        }
    worst_common = min(burns.values())
    if worst_common >= PAGE_BURN:
        state = "page"
    elif worst_common >= WARN_BURN:
        state = "warning"
    else:
        state = "ok"
    return {"windows": windows, "burn_rates": burns, "state": state}


def _complete(tr, first, flags):
    """One flush that completes ``len(flags)`` updates, numbered from
    ``first``.  A true flag is a breach (its origin stamped a second
    before the clock's zero); a false one converges in no time."""
    keys = []
    for i, slow in enumerate(flags, first):
        payload = _key_bytes(i)
        if slow:
            tr._origins.record_once(update_key(payload), -1.0)
        keys.append(tr.receive(payload))
    for key in keys:
        tr.integrated(key)
    assert tr.visible() == len(flags)


class _Twin:
    """A tracker under a fake clock beside the plain walk over a ring of
    its own, evaluated at the calls at which the tracker evaluates."""

    def __init__(self, **kw):
        self.clock = _Clock()
        self.tr = _tracker(self.clock, **kw)
        self.max_events = kw.get("max_events", 65536)
        self.ring = collections.deque(maxlen=self.max_events)
        self.ref = _plain_walk((), 0.0, 1.0, 1.0, 0.99)
        self.n = 0

    def _evaluate(self):
        tr = self.tr
        self.ref = _plain_walk(
            self.ring, self.clock.t, tr.short_window_s, tr.window_s,
            tr.objective,
        )

    def advance(self, dt):
        self.clock.t += dt

    def complete(self, flags):
        _complete(self.tr, self.n, flags)
        self.n += len(flags)
        self.ring.extend((self.clock.t, slow) for slow in flags)
        if flags:
            self._evaluate()

    def poll(self, how):
        if self.ring:
            self._evaluate()
        if how == "state":
            assert self.tr.state() == self.ref["state"]
        else:
            snap = self.tr.snapshot()
            assert {k: snap[k] for k in self.ref} == self.ref
            assert snap["completed"] == self.n

    def check(self):
        """What the last evaluation left behind, in every place a reader
        finds it: exactly the plain walk's."""
        tr, ref = self.tr, self.ref
        assert tr._windows == ref["windows"]
        assert tr._burns == ref["burn_rates"]
        assert tr._state == ref["state"]
        for w in ("short", "long"):
            assert tr._burn[w].value == ref["burn_rates"][w]
        assert tr._m_state.value == {"ok": 0, "warning": 1, "page": 2}[
            ref["state"]
        ]
        assert ref["windows"]["long"]["total"] <= self.max_events

    def run(self, schedule):
        for op, arg in schedule:
            getattr(self, op)(arg)
            self.check()


def _random_schedule(rng, steps, dts, kmax):
    """``steps`` of advance / complete / poll, drawn from ``rng``."""
    out = []
    for _ in range(steps):
        roll = rng.random()
        if roll < 0.4:
            out.append(("advance", rng.choice(dts)))
        elif roll < 0.8:
            p = rng.choice((0.0, 0.3, 1.0))
            out.append(
                ("complete",
                 [rng.random() < p for _ in range(rng.randint(0, kmax))])
            )
        else:
            out.append(("poll", rng.choice(("state", "snapshot"))))
    return out


def _edge_schedule(rng):
    """Completions exactly on a window's left edge: every time is a
    multiple of 1/4, so ``now - t == wlen`` (1.0 and 12.0) is met to the
    bit, stays inside, and leaves one ulp-sized step later."""
    head = [
        ("advance", 3.0),
        ("complete", [True, False, True]),
        ("advance", 1.0),  # now - t == short_window_s: still inside
        ("poll", "snapshot"),
        ("advance", 2.0 ** -40),  # and out
        ("poll", "state"),
        ("advance", 11.0 - 2.0 ** -40),  # now - t == window_s
        ("complete", [False]),
        ("poll", "snapshot"),
        ("advance", 2.0 ** -30),
        ("poll", "snapshot"),
    ]
    return head + _random_schedule(
        rng, 300, (0.25, 0.5, 0.75, 1.0, 11.0, 12.0), 4
    )


def _shared_t_schedule(rng):
    """Many flushes at one reading of the clock, then past both edges."""
    out = [("advance", 0.5)]
    for _ in range(6):
        for _ in range(rng.randint(3, 9)):
            out.append(
                ("complete",
                 [rng.random() < 0.4 for _ in range(rng.randint(1, 30))])
            )
        out.append(("poll", "snapshot"))
        out.append(("advance", rng.choice((0.0, 1.0, 1.5, 12.0, 40.0))))
        out.append(("poll", "state"))
    return out


def _long_gap_schedule(rng):
    """Bursts, then nothing but ``state()`` polls while both windows run
    out: the verdict decays with no completion to trigger a pass."""
    out = []
    for _ in range(4):
        for _ in range(5):
            out.append(("advance", rng.random() * 0.2))
            out.append(
                ("complete", [True] * rng.randint(1, 6) + [False])
            )
        for _ in range(40):
            out.append(("advance", rng.choice((0.1, 0.4, 1.0))))
            out.append(("poll", "state"))
    return out


_DT_FINE = (0.0, 0.001, 0.01, 0.05, 0.3)
_DT_WIDE = (0.0, 0.05, 0.3, 0.9, 5.0, 30.0)

_EQUIVALENCE_CASES = {
    # the ring overflows: the cap pushes completions out of both windows
    "ring_of_8": (
        {"max_events": 8, "window_s": 1200.0},
        lambda rng: _random_schedule(rng, 400, _DT_FINE, 5),
    ),
    "ring_of_64": (
        {"max_events": 64, "window_s": 1200.0},
        lambda rng: _random_schedule(rng, 400, _DT_FINE, 40),
    ),
    # cap and age both at work on one ring
    "ring_of_64_ageing": (
        {"max_events": 64, "window_s": 12.0},
        lambda rng: _random_schedule(rng, 600, _DT_WIDE, 40),
    ),
    "both_windows_age_out_and_refill": (
        {"window_s": 12.0},
        lambda rng: _random_schedule(rng, 600, _DT_WIDE, 12),
    ),
    "completion_on_a_windows_edge": ({"window_s": 12.0}, _edge_schedule),
    "many_completions_share_one_t": (
        {"window_s": 12.0}, _shared_t_schedule,
    ),
    "long_gap_of_state_polls": ({"window_s": 12.0}, _long_gap_schedule),
    # window_s / 12 < 1: the short window is clamped to 1.0, LONGER
    # than the long one
    "short_window_clamped_to_1s": (
        {"window_s": 0.5},
        lambda rng: _random_schedule(
            rng, 600, (0.0, 0.01, 0.1, 0.25, 0.5, 0.6, 1.0, 1.1), 8
        ),
    ),
}


@pytest.mark.parametrize("seed", (28, 2147483659))
@pytest.mark.parametrize("case", sorted(_EQUIVALENCE_CASES))
def test_running_counts_equal_the_plain_walk(case, seed):
    kw, schedule = _EQUIVALENCE_CASES[case]
    twin = _Twin(target_ms=250.0, objective=0.99, **kw)
    if case == "short_window_clamped_to_1s":
        assert twin.tr.short_window_s == 1.0 > twin.tr.window_s
    twin.run(schedule(random.Random(seed)))
    assert twin.n > 0
    twin.poll("snapshot")
    twin.check()


def test_scrapes_age_the_windows_while_a_flush_appends():
    """One thread completes batches through the pipeline, another
    scrapes ``snapshot()`` (which ages the windows) as fast as it can:
    nothing raises, no count is lost, and what is left equals the plain
    walk over the writer's own record."""
    clock = _Clock()
    tr = _tracker(
        clock, target_ms=250.0, window_s=12.0, objective=0.99,
        max_events=256,
    )
    ring = collections.deque(maxlen=256)
    rng = random.Random(28)
    errors = []
    done = threading.Event()
    scrapes = [0]

    def writer():
        try:
            n = 0
            for _ in range(1500):
                clock.t += rng.choice((0.0, 0.01, 0.2, 1.0, 3.0))
                flags = [rng.random() < 0.3 for _ in range(rng.randint(1, 40))]
                _complete(tr, n, flags)
                n += len(flags)
                ring.extend((clock.t, slow) for slow in flags)
        except BaseException as e:  # reported by the test's own thread
            errors.append(e)
        finally:
            done.set()

    def scraper():
        try:
            while not done.is_set():
                snap = tr.snapshot()
                for w in snap["windows"].values():
                    assert 0 <= w["breached"] <= w["total"] <= 256
                scrapes[0] += 1
        except BaseException as e:
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=f) for f in (writer, scraper)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(interval)
    assert errors == []
    assert scrapes[0] > 0
    snap = tr.snapshot()
    ref = _plain_walk(ring, clock.t, 1.0, 12.0, 0.99)
    assert {k: snap[k] for k in ref} == ref
    assert snap["completed"] == tr._m_completed.value > 256


class _CountingDeque(collections.deque):
    """A deque that counts the entries its user looks at or moves."""

    touched = 0

    def append(self, x):
        type(self).touched += 1
        super().append(x)

    def popleft(self):
        type(self).touched += 1
        return super().popleft()

    def pop(self):
        type(self).touched += 1
        return super().pop()

    def __getitem__(self, i):
        type(self).touched += 1
        return super().__getitem__(i)

    def __iter__(self):
        for x in super().__iter__():
            type(self).touched += 1
            yield x

    def __reversed__(self):
        for x in super().__reversed__():
            type(self).touched += 1
            yield x


def test_a_pass_costs_arrivals_plus_departures_not_the_ring(monkeypatch):
    """The typing flood's shape: the ring full at the default 65,536,
    then 1,000 flushes of 80 completions at 1800 a second.  A pass may
    touch the entries that arrived and those that left (by the cap in
    the long window, by age in the short), never the ring: a count of
    deque entries, not a timing."""
    monkeypatch.setattr(_CountingDeque, "touched", 0)
    monkeypatch.setattr(slo_module, "deque", _CountingDeque)
    clock = _Clock()
    tr = _tracker(clock, target_ms=250.0, objective=0.99)
    n = 0

    def flush(k):
        nonlocal n
        clock.t += k / 1800.0
        _complete(tr, n, [False] * k)
        n += k

    while n < 65536:
        flush(4096)
    windows = tr.snapshot()["windows"]
    assert windows["long"]["total"] == 65536  # 300 s would hold 540,000
    assert windows["short"]["total"] == 45056  # 25 s of them (11 x 4096)
    before = _CountingDeque.touched
    for _ in range(1000):
        flush(80)
        assert tr.state() == "ok"
    touched = _CountingDeque.touched - before
    windows = tr.snapshot()["windows"]
    assert windows["long"]["total"] == 65536
    assert 44900 <= windows["short"]["total"] <= 45100
    # per flush: 80 appended to and ~80 taken from each of two windows,
    # a look at each window's oldest entry per departure and per pass
    # (~500); the walk touched the ring's 65,536 and the windows' 110,000
    assert 1000 * 2 * 80 <= touched <= 1000 * 10 * 80


# -- two-provider end-to-end (the ISSUE acceptance test) ---------------------


def test_two_provider_breach_transitions_to_page(monkeypatch):
    """Provider A broadcasts, provider B converges; with a 0 ms target
    every real convergence breaches, and B's multiwindow burn rate must
    transition its verdict to ``page``."""
    monkeypatch.setenv("YTPU_SLO_CONVERGENCE_MS", "0")
    a = TpuProvider(4)
    b = TpuProvider(4)
    a.on_update(lambda guid, u: b.receive_update(guid, u))
    for k in range(3):
        d = Y.Doc(gc=False)
        d.get_text("text").insert(0, f"edit {k} ")
        a.receive_update("room", encode_state_as_update(d))
        a.flush()  # emits the broadcast -> B receives
        b.flush()  # B integrates: convergence completes
    assert "edit 0" in b.text("room")
    snap = b.slo_snapshot()
    assert snap["completed"] >= 3
    assert snap["windows"]["long"]["breached"] == snap["windows"]["long"]["total"]
    assert snap["state"] == "page"
    # the verdict also rides the exposition surfaces
    assert b.metrics_snapshot()["slo"]["state"] == "page"
    text = b.metrics_text()
    assert "ytpu_slo_state 2" in text


def test_two_provider_convergence_within_target():
    """With a generous target the same exchange stays ``ok`` and the
    latency histogram records one completion per converged update."""
    a = TpuProvider(4)
    b = TpuProvider(4)
    a.on_update(lambda guid, u: b.receive_update(guid, u))
    d = Y.Doc(gc=False)
    d.get_text("text").insert(0, "hello peer")
    a.receive_update(
        "room", encode_state_as_update(d)
    )
    a.flush()
    b.flush()
    assert b.text("room") == "hello peer"
    fam = b.engine.obs.registry.get("ytpu_convergence_latency_seconds")
    assert fam.count == 1


# -- the CPU-doc protocol seam -----------------------------------------------


def test_protocol_slo_seam_zero_wire_change():
    d1 = Y.Doc(gc=False)
    d1.get_text("text").insert(0, "wire test")
    enc_plain = Encoder()
    protocol.write_update(enc_plain, encode_state_as_update(d1))
    frame = enc_plain.to_bytes()

    clock = _Clock()
    tr = _tracker(clock)
    d2 = Y.Doc(gc=False)
    reply = Encoder()
    mt = protocol.read_sync_message(Decoder(frame), reply, d2, slo=tr)
    assert mt == protocol.MESSAGE_YJS_UPDATE
    assert str(d2.get_text("text")) == "wire test"
    # a CPU Doc integrates synchronously: the pipeline completed inline
    snap = tr.snapshot()
    assert snap["completed"] == 1 and snap["pending"] == 0
    # zero wire change: the tracked frame IS the plain frame
    d3 = Y.Doc(gc=False)
    protocol.read_sync_message(Decoder(frame), Encoder(), d3)
    assert str(d3.get_text("text")) == "wire test"
