"""chip_smoke.py: drive the main path once on the chip and check it.

    python chip_smoke.py

runs, one after another and each in a process of its own (a chip belongs
to one process at a time; this parent never initialises a JAX backend):

- ``provider``  a 4096-room ``TpuProvider(backend="device", wal_dir=...)``
  on one chip: a cold load of the committed traces through
  ``receive_update`` + ``flush()``, back-to-back steady-state flushes fed
  by CPU ``Y.Doc`` clients with ``on_update`` fan-out to their peers, late
  joiners through ``handle_sync_message``, then every check against a
  CPU ``Y.Doc`` oracle, the text once more read back from the device;
- ``provider``  again, unchanged: the persistent compile cache must have
  served every program the first run compiled;
- ``mesh``      the same over ``doc_mesh(4)``, when four devices are
  visible;
- ``served``    ``Supervisor`` + ``Gateway`` in a process that stays off
  JAX, the shard child owning the chip, socket clients in 8 rooms, every
  cluster, gateway and session timeout at its default.

It exits non-zero when any phase fails, when JAX finds no TPU, or when
the native plan core did not build (with the compiler's message).  The
last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import random
import shutil
import signal
import struct
import subprocess
import sys
import tempfile
import time
import zlib
from pathlib import Path

ROOT = Path(__file__).resolve().parent
FIXTURES = ROOT / "tests" / "fixtures"
RESULT_PREFIX = "CHIP_SMOKE_PHASE "
# the whole run must end within 1200 s, compilation included
BUDGET_S = 1150.0


@dataclasses.dataclass(frozen=True)
class Sizes:
    """How big one run is.  ``FULL`` is what ``python chip_smoke.py``
    runs; tests/test_chip_smoke.py runs the same bodies at a tiny size."""

    n_docs: int          # provider slots, every one of them loaded
    storm: int           # rooms of the 4-client conflict-storm traces
    b4: int              # rooms of the B4 editing trace
    prepend: int         # rooms of prepend_frag_100000 (cap 131072)
    flushes: int         # back-to-back steady-state flushes
    active: int          # rooms edited per steady-state flush
    slide: int           # rooms the active window moves per flush
    joiners: int         # late joiners (step 1 -> step 2)
    others: int          # untouched rooms checked besides
    served_docs: int     # slots of the served shard
    served_rooms: int    # rooms with two socket clients each


FULL = Sizes(
    n_docs=4096, storm=256, b4=8, prepend=2, flushes=32, active=64,
    slide=8, joiners=32, others=128, served_docs=4096, served_rooms=8,
)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


# ---------------------------------------------------------------------------
# fixtures: a missing one is an error, never a cue to synthesise another
# ---------------------------------------------------------------------------


def load_traces(stem: str) -> list[bytes]:
    raw = zlib.decompress((FIXTURES / f"{stem}_1500.bin.z").read_bytes())
    n, _ops = struct.unpack_from("<II", raw, 0)
    out, o = [], 8
    for _ in range(n):
        (ln,) = struct.unpack_from("<I", raw, o)
        out.append(raw[o + 4 : o + 4 + ln])
        o += 4 + ln
    return out


def room_plan(sz: Sizes) -> list[tuple[str, str, bytes]]:
    """``(guid, kind, update)`` for every slot: distinct traces cycled
    over the rooms, then storm, B4 and prepend-fragmented rooms.  The
    load order is the list order, so the biggest rooms grow the tables
    last."""
    distinct = load_traces("distinct_traces")
    storm = load_traces("storm_traces")
    b4 = (FIXTURES / "b4_trace.bin").read_bytes()
    prepend = zlib.decompress(
        (FIXTURES / "prepend_frag_100000.bin.z").read_bytes()
    )
    n_distinct = sz.n_docs - sz.storm - sz.b4 - sz.prepend
    check(n_distinct > 0, "sizes leave no room for distinct traces")
    plan = [
        (f"smoke/distinct-{i:04d}", "distinct", distinct[i % len(distinct)])
        for i in range(n_distinct)
    ]
    plan += [
        (f"smoke/storm-{i:04d}", "storm", storm[i % len(storm)])
        for i in range(sz.storm)
    ]
    plan += [(f"smoke/b4-{i}", "b4", b4) for i in range(sz.b4)]
    plan += [(f"smoke/prepend-{i}", "prepend", prepend) for i in range(sz.prepend)]
    return plan


# ---------------------------------------------------------------------------
# what every phase reports about the process it ran in
# ---------------------------------------------------------------------------


class CompileCounter:
    """Counts XLA compilations through ``jax.monitoring``: how many, the
    seconds spent in them, and how many the persistent cache served."""

    def __init__(self):
        import jax

        self.requests = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += secs

    def report(self) -> dict:
        return {
            "compile_requests": self.requests,
            "compile_seconds": round(self.seconds, 3),
            "persistent_cache_hits": self.cache_hits,
            "persistent_cache_misses": self.cache_misses,
        }


def device_report(platform: str) -> dict:
    """The device as JAX reports it; fails unless it is ``platform``."""
    import jax

    devices = jax.devices()
    d = devices[0]
    check(
        d.platform == platform,
        f"no {platform} device: jax.devices()[0].platform == {d.platform!r} "
        f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})",
    )
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def host_report() -> dict:
    """Host cores and the planner that runs on them; fails with the
    compiler's message when the native core is unavailable."""
    from yjs_tpu import native
    from yjs_tpu.ops.native_mirror import native_plan_available

    available = native_plan_available()
    check(
        available,
        "native plan core unavailable (the smoke does not time the "
        f"pure-Python DocMirror): {native.load_error()}",
    )
    return {
        "cpu_count": os.cpu_count(),
        "native_plan_available": available,
        "plan_threads": int(native.load().ymx_plan_threads()),
    }


def peak_bytes() -> list[int | None]:
    import jax

    return [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.devices()
    ]


# ---------------------------------------------------------------------------
# phase: provider (one chip) / mesh (four)
# ---------------------------------------------------------------------------


def canonical(update: bytes) -> bytes:
    import yjs_tpu as Y

    return Y.merge_updates([update])


class Room:
    """One steady-state room's CPU side: client A and client B, both
    started from the room's trace.  Either edits; both apply whatever
    the provider fans out, so each is an oracle of the room."""

    def __init__(self, index: int, base: bytes):
        import yjs_tpu as Y

        self.docs = []
        for k in range(2):
            d = Y.Doc(gc=False)
            d.client_id = 1_000_000 + 2 * index + k
            Y.apply_update(d, base)
            self.docs.append(d)

    def edit(self, who: int, rng: random.Random) -> bytes:
        """A few seeded ops on client ``who``; the incremental update."""
        import yjs_tpu as Y

        d = self.docs[who]
        sv = Y.encode_state_vector(d)
        t = d.get_text("text")
        for _ in range(rng.randint(1, 6)):
            n = len(t)
            if n and rng.random() < 0.3:
                pos = rng.randrange(n)
                t.delete(pos, min(rng.randint(1, 5), n - pos))
            else:
                t.insert(rng.randint(0, n), rng.choice(_WORDS))
        return Y.encode_state_as_update(d, sv)


_WORDS = ("the ", "quick ", "brown ", "fox ", "jumps ", "over ", "lazy ", "dog. ")


def cold_load(prov, plan) -> list[dict]:
    """Every room through ``receive_update`` + ``flush()``, the biggest
    last: the prepend rooms grow every table to cap 131072."""
    import jax

    eng = prov.engine
    out = []
    for kinds in (("distinct", "storm"), ("b4",), ("prepend",)):
        t0 = time.perf_counter()
        n = 0
        for guid, kind, update in plan:
            if kind in kinds:
                check(prov.receive_update(guid, update), f"{guid} refused")
                n += 1
        prov.flush()
        jax.block_until_ready(eng._right)
        m = eng.last_flush_metrics
        out.append({
            "kinds": "+".join(kinds), "rooms": n, "cap": eng._cap,
            "seconds": round(time.perf_counter() - t0, 3),
            **{k: round(m[k], 3) for k in _FLUSH_TIMERS},
            "plan_threads": m["plan_threads"],
            "realloc_bytes": m["realloc_bytes"],
        })
        log(f"cold load {out[-1]}")
    return out


_FLUSH_TIMERS = (
    "t_compact_s", "t_plan_s", "t_pack_s", "t_dispatch_s", "t_emit_s",
    "t_device_wait_s", "t_total_s",
)


def steady_state(prov, plan, rooms: dict, sz: Sizes) -> dict:
    """Back-to-back flushes, no barrier between them (the pipeline, the
    donation and the staging reuse all run): seeded CPU clients edit a
    window of rooms that slides from the distinct rooms into the storm
    rooms, and ``on_update`` fan-out reaches both clients of a room.
    Fills ``rooms`` with every room touched."""
    import jax

    import yjs_tpu as Y

    eng = prov.engine
    rng = random.Random(21)
    first = max(0, sz.n_docs - sz.storm - sz.b4 - sz.prepend - 2 * sz.active)

    def fan_out(guid: str, update: bytes) -> None:
        for d in rooms[guid].docs:
            Y.apply_update(d, update)

    prov.on_update(fan_out)
    flushes = []
    t0 = time.perf_counter()
    for f in range(sz.flushes):
        lo = first + f * sz.slide
        for i in range(lo, lo + sz.active):
            guid, _kind, base = plan[i]
            room = rooms.get(guid)
            if room is None:
                room = rooms[guid] = Room(i, base)
            check(prov.receive_update(guid, room.edit(0, rng)), guid)
            if i % 4 == 0:  # a concurrent edit by the room's other client
                check(prov.receive_update(guid, room.edit(1, rng)), guid)
        prov.flush()
        flushes.append(dict(eng.last_flush_metrics))
    jax.block_until_ready(eng._right)

    def median(key: str) -> float:
        return sorted(m[key] for m in flushes)[len(flushes) // 2]

    out = {
        "flushes": len(flushes),
        "rooms_touched": len(rooms),
        "seconds": round(time.perf_counter() - t0, 3),
        # host clock; t_emit_s holds this script's own fan-out into the
        # CPU clients, which runs inside the emit callbacks
        "flush_ms_median": {
            k: round(median(k) * 1e3, 3) for k in _FLUSH_TIMERS
        },
        "realloc_bytes": sum(m["realloc_bytes"] for m in flushes),
        "flushes_donated": sum(m["flush_donated"] for m in flushes),
        "pipeline_depth_max": max(m["pipeline_depth"] for m in flushes),
    }
    log(f"steady state {out}")
    check(
        out["realloc_bytes"] == 0 and out["flushes_donated"] == len(flushes),
        "a steady-state flush reallocated the resident tables instead of "
        f"donating them: {out}",
    )
    return out


def late_joiners(prov, plan, rooms: dict, sz: Sizes) -> tuple[dict, dict]:
    """Fresh clients do step 1 -> step 2 through ``handle_sync_message``:
    one B4 room, one prepend room, the rest rooms just edited."""
    import yjs_tpu as Y
    from yjs_tpu.lib0.decoding import Decoder
    from yjs_tpu.lib0.encoding import Encoder
    from yjs_tpu.sync import protocol

    guids = [
        next(g for g, k, _u in plan if k == kind)
        for kind in ("b4", "prepend")
        if any(k == kind for _g, k, _u in plan)
    ]
    guids += sorted(rooms)[: sz.joiners - len(guids)]
    t0 = time.perf_counter()
    step2_bytes = 0
    joined = {}
    for guid in guids:
        c = Y.Doc(gc=False)
        enc = Encoder()
        protocol.write_sync_step1(enc, c)
        reply = prov.handle_sync_message(guid, enc.to_bytes())
        check(reply is not None, f"{guid}: no step 2")
        step2_bytes += len(reply)
        protocol.read_sync_message(Decoder(reply), Encoder(), c, "smoke")
        joined[guid] = c
    out = {
        "n": len(joined), "step2_bytes": step2_bytes,
        "seconds": round(time.perf_counter() - t0, 3),
    }
    log(f"late joiners {out}")
    return joined, out


def verify(prov, plan, rooms: dict, joined: dict, sz: Sizes) -> dict:
    """Every room touched, every big room and ``sz.others`` more against
    a CPU ``Y.Doc`` oracle: canonical state bytes, text from the host
    mirror, then text once more with ``export_from_device``, which is the
    only comparison that reads the chip."""
    import yjs_tpu as Y

    eng = prov.engine
    t0 = time.perf_counter()
    base_of = {g: u for g, _k, u in plan}
    big = [g for g, k, _u in plan if k in ("b4", "prepend")]
    untouched = [
        g for g, k, _u in plan if k in ("distinct", "storm") and g not in rooms
    ]
    others = random.Random(22).sample(untouched, min(sz.others, len(untouched)))
    oracle_of_base: dict[bytes, tuple[bytes, str]] = {}

    def oracle(guid: str) -> tuple[bytes, str]:
        """(canonical state, text) the room must hold."""
        room = rooms.get(guid)
        if room is not None:
            a, b = (canonical(Y.encode_state_as_update(d)) for d in room.docs)
            check(a == b, f"{guid}: fan-out left clients A and B apart")
            return a, room.docs[0].get_text("text").to_string()
        base = base_of[guid]
        hit = oracle_of_base.get(base)
        if hit is None:
            d = Y.Doc(gc=False)
            Y.apply_update(d, base)
            hit = oracle_of_base[base] = (
                canonical(Y.encode_state_as_update(d)),
                d.get_text("text").to_string(),
            )
        return hit

    checked = sorted(set(rooms) | set(big) | set(others))
    eng.export_from_device = False
    text = {}
    for guid in checked:
        want_state, want_text = oracle(guid)
        check(
            canonical(prov.encode_state_as_update(guid)) == want_state,
            f"{guid}: state differs from the oracle",
        )
        text[guid] = prov.text(guid)
        check(text[guid] == want_text, f"{guid}: host text differs")
    for guid, c in joined.items():
        check(
            canonical(Y.encode_state_as_update(c)) == oracle(guid)[0],
            f"{guid}: late joiner differs from the oracle",
        )
    b4_meta = json.loads((FIXTURES / "b4_trace.json").read_text())
    b4_sv = {int(c): v for c, v in b4_meta["state_vector"].items()}
    for guid, kind, _u in plan:
        if kind == "b4":
            check(
                len(text[guid]) == b4_meta["text_len"]
                and hashlib.sha256(text[guid].encode()).hexdigest()
                == b4_meta["text_sha256"]
                and prov.state_vector(guid) == b4_sv,
                f"{guid}: text or state vector differs from b4_trace.json",
            )
    eng.export_from_device = True
    t_dev = time.perf_counter()
    for guid in checked:
        check(
            prov.text(guid) == text[guid],
            f"{guid}: text read from the device differs from the oracle",
        )
    out = {
        "rooms": len(checked), "touched": len(rooms), "big": len(big),
        "others": len(others), "joiners": len(joined),
        "device_text_seconds": round(time.perf_counter() - t_dev, 3),
        "seconds": round(time.perf_counter() - t0, 3),
    }
    check(eng.fallback == {}, f"docs on the CPU fallback: {eng.fallback}")
    check(not eng.demotions, f"demotions: {eng.demotions}")
    check(not eng.rollbacks, f"rollbacks: {eng.rollbacks}")
    check(
        len(eng.dead_letters) == 0,
        f"dead letters: {eng.dead_letters.snapshot()}",
    )
    log(f"checks {out}")
    return out


def residency(eng, mesh_devices: int) -> dict:
    """What is resident where; on a mesh every table must be split
    evenly over the devices and no device may have held the whole."""
    tables = {"right": eng._right, "deleted": eng._deleted, "starts": eng._starts}
    out = {
        "cap": eng._cap, "seg_cap": eng._seg_cap,
        "bytes": {k: int(v.nbytes) for k, v in tables.items()},
        "devices_per_table": {
            k: len(v.sharding.device_set) for k, v in tables.items()
        },
        "peak_bytes_in_use": peak_bytes(),
    }
    log(f"resident {out}")
    if mesh_devices:
        whole = sum(out["bytes"].values())
        for name, table in tables.items():
            shards = table.addressable_shards
            check(
                len({s.device for s in shards}) == mesh_devices
                and all(
                    s.data.nbytes * mesh_devices == table.nbytes for s in shards
                ),
                f"{name} is not split evenly over {mesh_devices} devices",
            )
        # where the backend keeps memory statistics (the CPU does not)
        for peak in out["peak_bytes_in_use"]:
            check(
                peak is None or peak < whole,
                f"a device's peak {peak} holds the whole tables ({whole})",
            )
    return out


def run_provider(sz: Sizes, platform: str, mesh_devices: int = 0) -> dict:
    """The provider phase's body (``mesh_devices`` > 0: the mesh phase).
    Returns the phase report; raises on the first failed check."""
    compiles = CompileCounter()
    from yjs_tpu.provider import TpuProvider

    report = {"phase": "mesh" if mesh_devices else "provider"}
    report["device"] = device_report(platform)
    report["host"] = host_report()
    log(f"device {report['device']} host {report['host']}")
    mesh = None
    if mesh_devices:
        from yjs_tpu.parallel import doc_mesh

        mesh = doc_mesh(mesh_devices)
    plan = room_plan(sz)
    wal_dir = tempfile.mkdtemp(prefix="chip-smoke-wal-")
    prov = TpuProvider(
        n_docs=sz.n_docs, backend="device", mesh=mesh, wal_dir=wal_dir
    )
    try:
        report["cold_load"] = cold_load(prov, plan)
        report["compile_after_cold_load"] = compiles.report()
        rooms: dict[str, Room] = {}
        report["steady_state"] = steady_state(prov, plan, rooms, sz)
        joined, report["late_joiners"] = late_joiners(prov, plan, rooms, sz)
        report["checks"] = verify(prov, plan, rooms, joined, sz)
        report["resident"] = residency(prov.engine, mesh_devices)
    finally:
        prov.close(checkpoint=False)
        shutil.rmtree(wal_dir, ignore_errors=True)
    report["compile"] = compiles.report()
    log(f"compile {report['compile']}")
    return report


# ---------------------------------------------------------------------------
# phase: served (the shard child owns the chip; this process stays off JAX)
# ---------------------------------------------------------------------------


def _metric(text: str, name: str, labels: str = "") -> float:
    """Sum of the samples of ``name`` whose label set contains
    ``labels`` in a Prometheus exposition."""
    total, seen = 0.0, False
    for line in text.splitlines():
        if line.startswith("#") or not line.startswith(name):
            continue
        head, _, value = line.rpartition(" ")
        if head.split("{")[0] != name or labels not in head:
            continue
        total += float(value)
        seen = True
    check(seen, f"/metrics has no sample {name}{{{labels}}}")
    return total


def run_served(sz: Sizes, platform: str) -> dict:
    import socket
    import urllib.request

    sys.path.insert(0, str(ROOT / "examples"))
    from socket_connector import SocketConnector

    import yjs_tpu as Y
    from yjs_tpu.cluster import Gateway, GatewayConfig, Supervisor

    report = {"phase": "served"}
    distinct = load_traces("distinct_traces")
    wal_root = tempfile.mkdtemp(prefix="chip-smoke-served-")
    # Every timeout is the product's default (ClusterConfig, GatewayConfig,
    # SessionConfig), cold compiles included: what this phase passes with
    # is what `scripts/ytpu_cluster.py` and an operator's own start run.
    sup = Supervisor(
        1, wal_root, docs_per_shard=sz.served_docs, backend="device"
    )
    gw = None
    conns = []
    try:
        t0 = time.perf_counter()
        sup.start()
        gw = Gateway(sup, config=GatewayConfig(port=0)).start()
        report["start_seconds"] = round(time.perf_counter() - t0, 3)
        log(f"supervisor + gateway up in {report['start_seconds']} s")

        def connect(room: str, client_id: int, base: bytes | None):
            doc = Y.Doc(gc=False)
            doc.client_id = client_id
            if base is not None:
                Y.apply_update(doc, base)
            sock = socket.create_connection(("127.0.0.1", gw.port), timeout=60)
            conn = SocketConnector(
                doc, sock, room=room, peer=f"peer-{client_id}"
            )
            conns.append(conn)
            conn.connect()
            return doc, conn

        def texts(pair) -> list[str]:
            out = []
            for doc, conn in pair:
                with conn.lock:
                    out.append(doc.get_text("text").to_string())
            return out

        def wait_equal(pair, require=(), deadline_s=240.0) -> str:
            end = time.monotonic() + deadline_s
            while time.monotonic() < end:
                got = texts(pair)
                if (
                    got[0] == got[1] and got[0]
                    and all(tok in got[0] for tok in require)
                ):
                    return got[0]
                time.sleep(0.05)
            got = texts(pair)
            sessions = []
            for _doc, conn in pair:
                with conn.lock:
                    sessions.append(conn.session.snapshot())
            raise AssertionError(
                f"clients never converged on {require}: lengths "
                f"{[len(t) for t in got]}, tokens present "
                f"{[[tok in t for tok in require] for t in got]}, "
                f"sessions {sessions}"
            )

        # A arrives with a real document, B empty: the handshake carries
        # the room through the shard to B
        pairs = {}
        for r in range(sz.served_rooms):
            room = f"smoke/served-{r}"
            pairs[room] = (
                connect(room, 9000 + 2 * r, distinct[r]),
                connect(room, 9001 + 2 * r, None),
            )
        t0 = time.perf_counter()
        for room, pair in pairs.items():
            wait_equal(pair)
        report["initial_sync_seconds"] = round(time.perf_counter() - t0, 3)
        log(f"{len(pairs)} rooms synced in {report['initial_sync_seconds']} s")
        rng = random.Random(23)
        t0 = time.perf_counter()
        for r, (room, pair) in enumerate(pairs.items()):
            for k, (doc, conn) in enumerate(pair):
                with conn.lock:
                    t = doc.get_text("text")
                    t.insert(rng.randint(0, len(t)), f"[{'AB'[k]}{r}]")
        for r, (room, pair) in enumerate(pairs.items()):
            text = wait_equal(pair, require=(f"[A{r}]", f"[B{r}]"))
            check(sup.text(room) == text, f"{room}: shard text differs")
            with pair[0][1].lock:
                want = canonical(Y.encode_state_as_update(pair[0][0]))
            check(
                canonical(sup.diff_update(room, None)) == want,
                f"{room}: shard state differs from client A",
            )
        report["edit_visible_seconds"] = round(time.perf_counter() - t0, 3)
        log(f"edits visible at every peer in {report['edit_visible_seconds']} s")

        # what the cold shard cost the sessions: they may have waited and
        # retransmitted, but none may have given the connection up
        snaps = []
        for conn in conns:
            with conn.lock:
                snaps.append(conn.session.snapshot())
        report["sessions"] = {
            "n": len(snaps),
            **{
                k: sum(s[k] for s in snaps)
                for k in (
                    "liveness_timeouts", "retransmits", "dead_lettered",
                    "busy_backoffs", "full_resyncs", "resumes",
                )
            },
        }
        log(f"sessions {report['sessions']}")
        check(
            report["sessions"]["liveness_timeouts"] == 0
            and all(s["state"] == "live" for s in snaps)
            and all(s["full_resyncs"] == 1 for s in snaps),
            f"a session lost its connection to the gateway: {snaps}",
        )

        # the shard's own admin plane says which device holds its tables
        base = dict(sup.admin_urls())["shard-000"]
        with urllib.request.urlopen(base + "/metrics", timeout=60) as r:
            metrics = r.read().decode()
        with urllib.request.urlopen(base + "/statusz", timeout=60) as r:
            status = json.loads(r.read())
        device_bytes = _metric(
            metrics, "ytpu_prof_device_bytes_total", f'backend="{platform}"'
        )
        check(
            device_bytes > 0,
            f"the shard holds no bytes on a {platform} device",
        )
        check(status["fallback_docs"] == 0, f"fallback docs: {status}")
        check(
            status["docs"] == sz.served_rooms,
            f"shard rooms {status['docs']} != {sz.served_rooms}",
        )
        report["shard"] = {
            "platform": platform,
            "device_bytes": int(device_bytes),
            "docs": status["docs"],
            "capacity": status["capacity"],
            "fallback_docs": status["fallback_docs"],
            "compiles": int(_metric(metrics, "ytpu_prof_compiles_total")),
            "compile_seconds": round(
                _metric(metrics, "ytpu_prof_compile_seconds_sum"), 3
            ),
            "native_prepare_many_calls": int(
                _metric(metrics, "ytpu_native_prepare_many_seconds_count")
            ),
            "cpu_count": os.cpu_count(),
        }
        check(
            report["shard"]["native_prepare_many_calls"] > 0,
            "the shard never planned through the native core",
        )
        events = sup.recovery_report()["events"]
        check(not events, f"the shard was restarted: {events}")
        import jax._src.xla_bridge as xla_bridge

        check(
            not xla_bridge.backends_are_initialized(),
            "the supervisor's process initialised a JAX backend",
        )
        log(f"shard {report['shard']}")
    except Exception as e:
        # what the shard's own admin plane says about it, before it goes
        notes = []
        for name, base in sorted(sup.admin_urls().items()):
            for ep in ("/statusz", "/debug/blackbox"):
                try:
                    with urllib.request.urlopen(base + ep, timeout=10) as r:
                        notes.append(f"{name}{ep}: {r.read(4000).decode()}")
                except OSError as err:
                    notes.append(f"{name}{ep}: {err}")
        raise AssertionError(f"{e}\n" + "\n".join(notes)) from e
    finally:
        for conn in conns:
            conn.close()
        if gw is not None:
            gw.close()
        sup.close()
        shutil.rmtree(wal_root, ignore_errors=True)
    return report


# ---------------------------------------------------------------------------
# parent: one child per phase, one after another
# ---------------------------------------------------------------------------


def run_phase_child(name: str) -> int:
    if name == "served":
        report = run_served(FULL, "tpu")
    else:
        report = run_provider(FULL, "tpu", mesh_devices=4 if name == "mesh" else 0)
    print(RESULT_PREFIX + json.dumps(report), flush=True)
    return 0


def spawn_phase(name: str, deadline: float) -> dict:
    """Run one phase in a process group of its own, stream its output
    through, and return its report.  The group is killed on the way out,
    so a failed phase leaves no shard child behind."""
    log(f"=== phase {name} ===")
    t0 = time.monotonic()
    proc = subprocess.Popen(
        [sys.executable, str(ROOT / "chip_smoke.py"), "--phase", name],
        stdout=subprocess.PIPE, text=True, cwd=str(ROOT),
        start_new_session=True,
    )
    report = None

    def on_alarm(_sig, _frame):
        raise TimeoutError(f"phase {name} ran out of the {BUDGET_S:.0f} s budget")

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(max(1, int(deadline - time.monotonic())))
    try:
        for line in proc.stdout:
            if line.startswith(RESULT_PREFIX):
                report = json.loads(line[len(RESULT_PREFIX):])
            else:
                sys.stdout.write(line)
                sys.stdout.flush()
        rc = proc.wait()
    finally:
        signal.alarm(0)
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    if rc != 0 or report is None:
        raise RuntimeError(f"phase {name} failed (exit code {rc})")
    report["wall_seconds"] = round(time.monotonic() - t0, 1)
    log(f"phase {name} ok in {report['wall_seconds']} s")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--phase", choices=("provider", "mesh", "served"),
        help="run one phase in this process (what the parent spawns)",
    )
    args = ap.parse_args(argv)
    if args.phase:
        return run_phase_child(args.phase)
    try:
        run_parent()
    except Exception as e:  # the boundary: say why, exit non-zero
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    return 0


def run_parent() -> None:
    for needed in (ROOT / "yjs_tpu", FIXTURES / "b4_trace.bin"):
        check(needed.exists(), f"{needed} is missing: run from a checkout")
    deadline = time.monotonic() + BUDGET_S
    first = spawn_phase("provider", deadline)
    second = spawn_phase("provider", deadline)
    # the second run asks for the same programs: the persistent cache
    # (JAX_COMPILATION_CACHE_DIR, else .jax_compile_cache/) serves them
    # all.  The first run is a cold one only where the cache was empty:
    # its own hits and misses say which, and go into the summary.
    log(f"compile first run {first['compile']}")
    log(f"compile second run {second['compile']}")
    check(
        second["compile"]["persistent_cache_misses"] == 0
        and second["compile"]["persistent_cache_hits"] > 0,
        "the second provider run compiled programs the first had compiled: "
        f"{second['compile']}",
    )
    phases = [first, second]
    if first["device"]["count"] >= 4:
        phases.append(spawn_phase("mesh", deadline))
    else:
        log(f"phase mesh skipped: {first['device']['count']} device(s) visible")
    phases.append(spawn_phase("served", deadline))
    summary = {
        "phases": [
            {k: p[k] for k in ("phase", "wall_seconds")} for p in phases
        ],
        "host": first["host"],
        "compile_first_run": first["compile"],
        "compile_second_run": second["compile"],
        "resident_bytes": first["resident"]["bytes"],
        "peak_bytes_in_use": first["resident"]["peak_bytes_in_use"],
        "claim": None,
    }
    print(json.dumps(summary), flush=True)
    print(json.dumps({"ok": True, "device": first["device"]}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
