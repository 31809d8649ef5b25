"""A configuration file made into a running provider.

``benchmarks/configs/<name>.json`` states one deployment: slots, chips,
the room mix and the guarantees.  This module reads the committed
fixtures, deals the rooms from ``--seed`` (every seed holds the same
traces, in another order), builds the ``TpuProvider`` with nothing but
its defaults, and cold-loads it.  ``room_plan`` and ``cold_load`` began
as copies of ``chip_smoke.py``'s (see PERF.md, Open questions).

Nothing here touches JAX before :func:`require_device` has said that
the platform is the one the cell asks for.
"""

from __future__ import annotations

import dataclasses
import random
import struct
import time
import zlib
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
FIXTURES = ROOT / "tests" / "fixtures"

# load order: the biggest rooms grow the tables last
LOAD_ORDER = (("distinct", "storm"), ("b4",), ("prepend",))
FLUSH_TIMERS = (
    "t_compact_s", "t_plan_s", "t_pack_s", "t_dispatch_s", "t_emit_s",
    "t_total_s",
)


class BenchError(RuntimeError):
    """The benchmark cannot run as asked (no chip, no native core, a
    missing file): say why, exit non-zero, print no result."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


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


@dataclasses.dataclass(frozen=True)
class RoomSpec:
    guid: str
    kind: str     # distinct | storm | b4 | prepend
    trace: int    # index into the kind's traces (0 for b4 and prepend)
    base: bytes   # the room's committed trace, one update


def room_plan(cfg: dict, seed: int) -> list[RoomSpec]:
    """Every room of the deployment, in slot order.  The mix is dealt
    ``max(1, mesh_devices)`` times over, one share to a chip's block of
    slots; the seed shuffles which distinct and storm trace a room
    holds, never how many rooms hold each."""
    rng = random.Random(f"rooms:{seed}")
    traces = {
        "distinct": load_traces("distinct_traces"),
        "storm": load_traces("storm_traces"),
        "b4": [(FIXTURES / "b4_trace.bin").read_bytes()],
        "prepend": [
            zlib.decompress(
                (FIXTURES / "prepend_frag_100000.bin.z").read_bytes()
            )
        ],
    }
    order = {k: list(range(len(v))) for k, v in traces.items()}
    for k in ("distinct", "storm"):
        rng.shuffle(order[k])
    blocks = max(1, int(cfg["mesh_devices"]))
    rooms = cfg["rooms"]
    if sum(rooms.values()) != cfg["slots"] or any(
        n % blocks for n in rooms.values()
    ):
        raise BenchError(
            f"{cfg['name']}: the rooms {rooms} do not fill {cfg['slots']} "
            f"slots in {blocks} equal shares"
        )
    plan = []
    dealt = dict.fromkeys(rooms, 0)
    for block in range(blocks):
        for kind in ("distinct", "storm", "b4", "prepend"):
            for _ in range(rooms[kind] // blocks):
                i = dealt[kind]
                dealt[kind] += 1
                t = order[kind][i % len(order[kind])]
                plan.append(
                    RoomSpec(f"bench/{kind}-{i:05d}", kind, t, traces[kind][t])
                )
    return plan


def pick_rooms(
    plan, cfg: dict, kind: str, n: int, rng, n_traces: int | None = None
) -> list[RoomSpec]:
    """``n`` rooms of ``kind`` that hold the same documents in every
    seed: the k-th holds the kind's (k mod T)-th lowest-numbered trace of
    the T the deployment holds (of its first ``n_traces``, if said), and
    is taken from the chips' blocks of slots in turn.  The seed decides
    which of the rooms that hold a trace is taken, never which traces."""
    blocks = max(1, int(cfg["mesh_devices"]))
    per_block = len(plan) // blocks
    by_trace: dict[int, list] = {}
    for slot, room in enumerate(plan):
        if room.kind == kind:
            by_trace.setdefault(room.trace, []).append((slot // per_block, room))
    if sum(len(v) for v in by_trace.values()) < n:
        raise BenchError(
            f"{cfg['name']} holds fewer than {n} {kind} rooms"
        )
    traces = sorted(by_trace)[:n_traces]
    for rooms in by_trace.values():
        rng.shuffle(rooms)
    out = []
    for k in range(n):
        rooms = by_trace[traces[k % len(traces)]]
        if not rooms:
            raise BenchError(
                f"{cfg['name']}: too few rooms hold {kind} trace "
                f"{traces[k % len(traces)]} for {n} rooms"
            )
        here = [i for i, (b, _r) in enumerate(rooms) if b == k % blocks]
        out.append(rooms.pop(here[0] if here else 0)[1])
    return out


# ---------------------------------------------------------------------------
# the device, the host, the provider
# ---------------------------------------------------------------------------


def require_device(platform: str, chips: int) -> dict:
    """The device as JAX reports it; fails unless it is ``platform``
    with at least ``chips`` devices (no fallback to another backend)."""
    import jax

    devices = jax.devices()
    d = devices[0]
    if d.platform != platform or len(devices) < chips:
        raise BenchError(
            f"needs {chips} {platform} device(s): JAX found {len(devices)} x "
            f"{d.platform!r} ({d.device_kind})"
        )
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def require_native() -> dict:
    """The host planner is the native core or the run fails with the
    compiler's message: the pure-Python mirror is never timed."""
    import os

    from yjs_tpu import native
    from yjs_tpu.ops.native_mirror import native_plan_available

    if not native_plan_available():
        raise BenchError(
            f"native plan core unavailable: {native.load_error()}"
        )
    return {
        "cpu_count": os.cpu_count(),
        "plan_threads": int(native.load().ymx_plan_threads()),
    }


class CompileCounter:
    """XLA compilations through ``jax.monitoring``: how many, their
    seconds, and how many the persistent cache served."""

    def __init__(self):
        import jax

        self.programs = 0
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
            self.programs += 1
            self.seconds += secs

    def report(self) -> dict:
        return {
            "programs": self.programs, "seconds": round(self.seconds, 3),
            "persistent_cache_hits": self.cache_hits,
            "persistent_cache_misses": self.cache_misses,
        }


def make_provider(cfg: dict, wal_dir: Path):
    """``TpuProvider`` as the configuration states it: device backend,
    WAL on, every other argument the program's default."""
    from yjs_tpu.provider import TpuProvider

    mesh = None
    if cfg["mesh_devices"]:
        from yjs_tpu.parallel import doc_mesh

        mesh = doc_mesh(cfg["mesh_devices"])
    if cfg["provider"] != {
        "backend": "device", "wal": True,
        "wal_fsync": "interval", "wal_fsync_interval": 64,
    }:
        raise BenchError(
            f"{cfg['name']}: this harness builds only the default provider "
            f"with the WAL on, not {cfg['provider']}"
        )
    prov = TpuProvider(
        n_docs=cfg["slots"], backend="device", mesh=mesh, wal_dir=str(wal_dir)
    )
    policy = prov.wal.config
    if (policy.fsync, policy.fsync_interval) != ("interval", 64):
        raise BenchError(
            f"the WAL runs {policy.as_dict()}, the configuration states "
            "fsync every 64 appends: unset YTPU_WAL_FSYNC*"
        )
    return prov


def fence(prov) -> None:
    """Wait until the device holds everything dispatched so far."""
    import jax

    eng = prov.engine
    jax.block_until_ready((eng._right, eng._deleted, eng._starts))


def cold_load(prov, plan: list[RoomSpec], refused: list) -> list[dict]:
    """Every room through ``receive_update`` + ``flush()``.  Slots are
    dealt in plan order first, so that a chip's block holds one share
    of the mix; the biggest rooms load last and grow the tables."""
    eng = prov.engine
    for room in plan:
        prov.doc_id(room.guid)
    out = []
    for kinds in LOAD_ORDER:
        rooms = [r for r in plan if r.kind in kinds]
        if not rooms:
            continue
        t0 = time.perf_counter()
        for room in rooms:
            if not prov.receive_update(room.guid, room.base):
                refused.append(room.guid)
        prov.flush()
        fence(prov)
        m = eng.last_flush_metrics
        out.append({
            "kinds": "+".join(kinds), "rooms": len(rooms), "cap": eng._cap,
            "seconds": round(time.perf_counter() - t0, 3),
            **{k: round(m[k], 3) for k in FLUSH_TIMERS},
            "realloc_bytes": m["realloc_bytes"],
        })
        log(f"load {out[-1]}")
    return out


def peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest chip (0 where the backend keeps
    no memory statistics, as the CPU's does not)."""
    import jax

    return max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.devices()[:chips]
    )
