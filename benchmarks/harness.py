"""One run of one cell: set-up, rehearsal, window, checks, result line.

The harness knows no cell, configuration, traffic mix or per-layer
metric by name.  ``BENCHMARK.json`` names them, and each is a file of
its own that is found by that name:

    benchmarks/configs/<config>.json          the deployment
    benchmarks/traffic/<traffic>.json         the mix's parameters
    benchmarks/generators/<generator>.py      the generator a mix names
    benchmarks/layer_metrics/<metric>.py      one reader per per-layer
                                              metric (the part of the
                                              metric's name before the
                                              first dot names the file)

See benchmarks/README.md for what each holds.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import shutil
import statistics
import tempfile
import time
from pathlib import Path

from . import deployment, faults, oracle, trace_reduce
from .deployment import BenchError, log

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_manifest(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_module(kind: str, name: str, roots):
    """``<root>/<kind>/<name>.py`` from the first root that has it."""
    for root in roots:
        path = Path(root) / kind / f"{name}.py"
        if path.is_file():
            spec = importlib.util.spec_from_file_location(
                f"bench_{kind}_{name.replace('.', '_')}", path
            )
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            return module
    raise BenchError(f"{kind} {name!r}: no {kind}/{name}.py under {list(roots)}")


def load_data(kind: str, name: str, roots) -> dict:
    for root in roots:
        path = Path(root) / kind / f"{name}.json"
        if path.is_file():
            return json.loads(path.read_text())
    raise BenchError(f"{kind} {name!r}: no {kind}/{name}.json under {list(roots)}")


class Cell:
    """What a generator and the oracle see of a run: the provider, the
    rooms, and a record of everything sent, acknowledged and broadcast."""

    def __init__(self, cfg, seed, seconds, prov, plan, wal_dir, compiles):
        import jax

        self.cfg, self.seed, self.seconds = cfg, seed, seconds
        self.prov, self.plan, self.wal_dir = prov, plan, wal_dir
        self.compiles = compiles
        self.oracle = oracle.Oracle()
        self.clock = time.perf_counter
        self.log = log
        self._span = jax.profiler.TraceAnnotation
        # per room, since its last release: updates acknowledged (the
        # trace first) and updates broadcast
        self.history: dict[str, list[bytes]] = {r.guid: [r.base] for r in plan}
        self.listener_base: dict[str, list[bytes]] = {
            r.guid: [r.base] for r in plan
        }
        self.past: dict[str, list[list[bytes]]] = {}
        self.left: dict[str, list[dict]] = {}
        self.broadcasts: dict[str, list[bytes]] = {}
        # rooms sent to since the last flush, and those heard from in it
        self._owed: set[str] = set()
        self._heard: set[str] = set()
        self.unheard = 0
        self.missing_at_release = 0
        self.touched: set[str] = set()
        self.refused: list[str] = []
        self.notes: dict[str, list[float]] = {}
        self.in_window = False
        self.unit_times: list[float] = []
        self.ingest_s = 0.0
        self.flushes = 0
        self.acknowledged = 0
        self.phase_s = dict.fromkeys(deployment.FLUSH_TIMERS, 0.0)
        self.counts = dict.fromkeys((
            "realloc_bytes", "plan_cache_hits", "plan_cache_misses",
            "link_writes", "rows_compacted",
        ), 0)
        self._last_compaction = prov.engine.last_compaction

    # -- what a generator does ---------------------------------------------

    def send_all(self, updates, news: bool = True) -> None:
        """``(guid, update)`` pairs into ``receive_update``, under the
        ``bench.ingest`` span.  An update that was accepted is owed to
        the WAL and to the room's state, and, unless it carries no
        ``news`` (a duplicate of one sent before), to the room's peers."""
        receive = self.prov.receive_update
        t = self.clock()
        with self._span("bench.ingest"):
            for guid, update in updates:
                if receive(guid, update):
                    self.history[guid].append(update)
                    self.touched.add(guid)
                    if news:
                        self._owed.add(guid)
                    self.acknowledged += 1
                else:
                    self.refused.append(guid)
        self.ingest_s += self.clock() - t

    def flush(self) -> None:
        self._heard.clear()
        self.prov.flush()
        # every room that was sent an update is owed a broadcast by the
        # flush that integrates it
        self.unheard += len(self._owed - self._heard)
        self._owed.clear()
        if self.in_window:
            eng = self.prov.engine
            m = eng.last_flush_metrics
            self.flushes += 1
            for k in self.phase_s:
                self.phase_s[k] += m.get(k, 0.0)
            self.counts["realloc_bytes"] += m["realloc_bytes"]
            self.counts["plan_cache_hits"] += m.get("plan_cache_hits", 0)
            self.counts["plan_cache_misses"] += m.get("plan_cache_misses", 0)
            self.counts["link_writes"] += m.get("n_sched_entries", 0)
            if eng.last_compaction is not self._last_compaction:
                self._last_compaction = eng.last_compaction
                self.counts["rows_compacted"] += len(eng.last_compaction)

    def fence(self) -> None:
        with self._span("bench.fence"):
            deployment.fence(self.prov)

    def release(self, guid: str) -> None:
        """Drop a room (journaled as a release): nothing is owed to it
        any more, and its next listener starts from nothing.  The state
        vector it held is kept for the comparison: what a life that ends
        in the window integrated can be seen at no other time."""
        if self.prov.has_doc(guid):
            self.left.setdefault(guid, []).append(self.prov.state_vector(guid))
            self.prov.release_doc(guid)
        else:  # an acknowledged load never made the room
            self.left.setdefault(guid, []).append({})
            self.missing_at_release += 1
        self.past.setdefault(guid, []).append(self.history[guid])
        self.history[guid] = []
        self.listener_base[guid] = []
        self.broadcasts[guid] = []

    @contextlib.contextmanager
    def unit(self):
        t = self.clock()
        with self._span("bench.unit"):
            yield
        if self.in_window:
            self.unit_times.append(self.clock() - t)

    def note(self, name: str, value: float) -> None:
        self.notes.setdefault(name, []).append(value)

    def heard(self, guid: str, update: bytes) -> None:
        self._heard.add(guid)
        self.broadcasts.setdefault(guid, []).append(update)


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    # the Python tracer records every call of every function (millions
    # of events a second of flood) and slows the host it measures
    opts.python_tracer_level = 0
    return opts


def percentile(values: list[float], q: float) -> float:
    s = sorted(values)
    return s[min(len(s) - 1, int(q * len(s)))]


def run_cell(
    workload: str, seed: int, seconds: float, trace: bool,
    platform: str = "tpu", roots=(HERE,), manifest: dict | None = None,
    fault: str | None = None, t_process: float | None = None,
) -> dict:
    """Run one cell and return the result object of the contract.
    ``platform`` other than ``tpu``, further ``roots`` and ``fault`` are
    for the tests and the fault controls: the command passes none."""
    t_process = time.perf_counter() if t_process is None else t_process
    manifest = manifest or load_manifest()
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json: {sorted(cells)}")
    entry = cells[workload]
    cfg = load_data("configs", entry["config"], roots)
    traffic = load_data("traffic", entry["traffic"], roots)
    if int(cfg["chips"]) != int(entry["chips"]):
        raise BenchError(f"{workload}: chips differ between cell and configuration")

    compiles = deployment.CompileCounter()
    device = deployment.require_device(platform, int(entry["chips"]))
    host = deployment.require_native()
    log(f"device {device} host {host}")
    t_backend = time.perf_counter()

    plan = deployment.room_plan(cfg, seed)
    work_dir = ROOT / ".bench_work"
    work_dir.mkdir(exist_ok=True)
    run_dir = Path(tempfile.mkdtemp(prefix="run-", dir=work_dir))
    prov = None
    try:
        prov = deployment.make_provider(cfg, run_dir / "wal")
        cell = Cell(cfg, seed, seconds, prov, plan, run_dir / "wal", compiles)
        armed = faults.install(fault, prov, seed) if fault else None
        deployment.cold_load(prov, plan, cell.refused)
        prov.on_update(cell.heard)
        t_load = time.perf_counter()

        gen = load_module("generators", traffic["generator"], roots).Generator(
            traffic, cell
        )
        gen.prepare()
        gen.rehearse()
        cell.fence()
        t_setup = time.perf_counter()
        setup_s = t_setup - t_process
        log(
            f"set-up {setup_s:.3f} s: backend and native core "
            f"{t_backend - t_process:.3f}, rooms and load {t_load - t_backend:.3f}, "
            f"clients and rehearsal {t_setup - t_load:.3f}; compile "
            f"{json.dumps(compiles.report())}"
        )

        # -- the window ----------------------------------------------------
        import jax

        compiled_before = compiles.programs
        trace_dir = run_dir / "trace"
        if armed is not None:
            armed()
        cell.in_window = True
        if trace:
            jax.profiler.start_trace(
                str(trace_dir), profiler_options=_profile_options()
            )
        timed_s: list[float] = []
        by_interval: list[dict] = []
        t_window = time.perf_counter()
        i = 0
        try:
            while True:
                gen.untimed(i)
                before = {**cell.phase_s, "ingest": cell.ingest_s}
                t = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.timed"):
                    gen.timed(i)
                timed_s.append(time.perf_counter() - t)
                after = {**cell.phase_s, "ingest": cell.ingest_s}
                by_interval.append({
                    k.removeprefix("t_").removesuffix("_s"): after[k] - before[k]
                    for k in after
                })
                i += 1
                if trace and len(cell.unit_times) >= int(traffic["trace_units"]):
                    break
                if time.perf_counter() - t_window >= seconds:
                    break
        finally:
            if trace:
                jax.profiler.stop_trace()
        window_s = time.perf_counter() - t_window
        cell.in_window = False
        compiles_in_window = compiles.programs - compiled_before
        peak = deployment.peak_bytes(int(entry["chips"]))

        # -- after the window: the reference, the checks -------------------
        t = time.perf_counter()
        gen.finish()
        views = gen.views() if hasattr(gen, "views") else None
        compared = oracle.check(cell, views)
        compared["realloc_bytes_in_window"] = cell.counts["realloc_bytes"]
        compared["compiles_in_window"] = compiles_in_window
        work = gen.work()
        log(f"checks took {time.perf_counter() - t:.3f} s")
        failed = 0
        for name, value in compared.items():
            ok = value == 0
            failed += 0 if ok else max(1, int(value))
            log(f"check {name}: {value} (limit 0) {'ok' if ok else 'FAILED'}")

        timed_total = sum(timed_s)
        units = cell.unit_times
        log(
            f"window {window_s:.3f} s: {len(timed_s)} timed intervals in "
            f"{timed_total:.3f} s, {len(units)} units, work {work} "
            f"{traffic['work_unit']}, {cell.flushes} flushes, "
            f"{cell.acknowledged} acknowledged"
        )
        log(
            "unit times s: first "
            f"{[round(u, 4) for u in units[:8]]} median "
            f"{statistics.median(units):.4f} max {max(units):.4f}"
        )
        log(
            "the window's time by phase s (host clock): "
            f"{ {k: round(v, 3) for k, v in cell.phase_s.items()} } "
            f"ingest {cell.ingest_s:.3f}"
        )
        for k, (seconds, phases) in enumerate(zip(timed_s, by_interval)):
            log(
                f"timed interval {k}: {seconds:.4f} s = "
                + " ".join(f"{name} {v:.4f}" for name, v in phases.items())
            )
        for name, values in cell.notes.items():
            log(f"{name}: median {statistics.median(values):.3f} over {len(values)}")

        stats = {
            "work_per_timed_second": work / timed_total,
            "unit_p50_ms": statistics.median(units) * 1e3,
            "unit_p95_ms": percentile(units, 0.95) * 1e3,
            "setup_s": setup_s,
        }
        counters = {
            **cell.counts, **stats,
            "timed_s": timed_total, "window_s": window_s, "units": len(units),
            "timed_intervals_s": list(timed_s),
            "flushes": cell.flushes, "ingest_s": cell.ingest_s,
            "phase_s": dict(cell.phase_s), "work": work,
            "compiles_in_window": compiles_in_window,
            "memory_peak_bytes": peak, "chips": int(entry["chips"]),
            "device_kind": device["kind"],
            "cap": prov.engine._cap, "seg_cap": prov.engine._seg_cap,
        }
        result = {
            "correct": failed == 0,
            "attempted": cell.acknowledged + len(cell.refused),
            "failed": failed,
            "metrics": {},
            "device": {**device, "memory_peak_bytes": peak},
        }
        if trace:
            reduced = trace_reduce.reduce_dir(trace_dir, int(entry["chips"]))
            result["device"]["busy_s"] = reduced["busy_s"]
            result["device"]["window_s"] = reduced["window_s"]
            result["breakdown"] = {
                "device_ops": reduced["device_ops"],
                "idle_gaps": reduced["idle_gaps"],
            }
            for m in manifest["per_layer"]:
                if workload not in m.get("workloads", [workload]):
                    continue
                reader = load_module(
                    "layer_metrics", m["name"].split(".", 1)[0], roots
                )
                value = reader.read(reduced, counters)
                if value is not None:
                    result["metrics"][m["name"]] = {
                        "value": value, "unit": m["unit"],
                    }
        else:
            wanted = traffic["end_to_end"]
            for m in manifest["end_to_end"]:
                if workload not in m.get("workloads", [workload]):
                    continue
                if m["name"] != "setup_s" and m["name"] not in wanted:
                    raise BenchError(
                        f"traffic {entry['traffic']!r} does not say how to "
                        f"measure {m['name']!r}"
                    )
                stat = "setup_s" if m["name"] == "setup_s" else wanted[m["name"]]
                result["metrics"][m["name"]] = {
                    "value": stats[stat], "unit": m["unit"],
                }
        return result
    finally:
        if prov is not None:
            prov.close(checkpoint=False)
        shutil.rmtree(run_dir, ignore_errors=True)
