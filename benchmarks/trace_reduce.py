"""From the JAX profiler's trace to the numbers the per-layer metrics read.

A traced run records one ``.xplane.pb``.  :func:`read_xplane` turns it
into plain events ``[plane, line, name, start_ns, duration_ns]``, keeping
what the reduction needs (device ops and programs, and the host spans
named ``ytpu.*`` or ``bench.*``); :func:`reduce_events` makes the
numbers.  Everything is taken inside the timed intervals, the
``bench.timed`` spans the harness puts around each one:

- ``window_s``        the timed intervals' total length
- ``busy_s``          seconds in which an operation ran on the device:
                      the union of the ``XLA Ops`` intervals, averaged
                      over the devices used
- ``spans``           self seconds of each host span (its own time less
                      the spans inside it, on its own thread)
- ``programs``        seconds and launches of each device program
                      (``XLA Modules``), by jitted name, averaged over
                      the devices: ``apply_plan2``, ``scatter_rows`` ...
- ``device_ops``      the ten operations that took most device time,
                      as ``<program>/<op>``
- ``idle_gaps``       the device's idle time by the innermost host span
                      that covered it (``_no_span_`` for none)

The same function reduces the small recorded trace kept with the tests
(``tests/bench/data/``), so every PR computes these numbers one way.
"""

from __future__ import annotations

import bisect
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
SPAN_PREFIXES = ("ytpu.", "bench.")
WINDOW_SPAN = "bench.timed"
# spans that mark the benchmark's own bookkeeping, not a layer's work
NOT_A_LAYER = (WINDOW_SPAN, "bench.unit")


def find_xplane(trace_dir) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path) -> list[list]:
    """Every event of the trace, slimmed as it is read."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, PROGRAMS_LINE):
                continue
            for ev in line.events:
                name = ev.name
                if device or name.startswith(SPAN_PREFIXES):
                    out.append([
                        plane.name, line.name, name,
                        float(ev.start_ns), float(ev.duration_ns),
                    ])
    return out


def program_name(name: str) -> str:
    """``jit_apply_plan2(2310058380723456173)`` -> ``apply_plan2``."""
    name = name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def op_name(name: str) -> str:
    """``%fusion.42 = s32[64]{0} fusion(...)`` -> ``fusion.42``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(intervals, windows) -> list[tuple[float, float]]:
    """The parts of sorted disjoint ``intervals`` inside sorted disjoint
    ``windows``."""
    out = []
    j = 0
    for a, b in intervals:
        while j < len(windows) and windows[j][1] <= a:
            j += 1
        k = j
        while k < len(windows) and windows[k][0] < b:
            lo, hi = max(a, windows[k][0]), min(b, windows[k][1])
            if hi > lo:
                out.append((lo, hi))
            k += 1
    return out


def _length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def _complement(intervals, windows) -> list[tuple[float, float]]:
    """``windows`` less the sorted disjoint ``intervals``."""
    out = []
    for lo, hi in windows:
        at = lo
        for a, b in _clip(intervals, [(lo, hi)]):
            if a > at:
                out.append((at, a))
            at = b
        if hi > at:
            out.append((at, hi))
    return out


def _leaf_segments(spans: list[tuple[float, float, str]]):
    """One thread's spans as disjoint segments, each labelled with the
    innermost span that covers it."""
    out = []
    stack: list[tuple[float, str]] = []  # (end, name)
    at = None

    def emit(until: float) -> None:
        nonlocal at
        if stack and until > at:
            out.append((at, until, stack[-1][1]))
        at = until

    for start, end, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][0] <= start:
            emit(stack[-1][0])
            stack.pop()
        if at is None or not stack:
            at = start
        else:
            emit(start)
        stack.append((end, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return out


def reduce_events(events: list[list], n_devices: int | None = None) -> dict:
    """See the module's docstring.  Times in seconds."""
    windows = _union([
        (e[3], e[3] + e[4]) for e in events if e[2] == WINDOW_SPAN
    ])
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    window_ns = _length(windows)
    planes = sorted({e[0] for e in events if DEVICE_PLANE.match(e[0])})
    if n_devices is not None:
        planes = planes[:n_devices]
    n = max(1, len(planes))

    busy_ns = 0.0
    idle: list[tuple[float, float]] = []
    programs: dict[str, list[float]] = {}
    ops: dict[str, float] = {}
    for plane in planes:
        mods = sorted(
            (e[3], e[3] + e[4], program_name(e[2]))
            for e in events if e[0] == plane and e[1] == PROGRAMS_LINE
        )
        starts = [m[0] for m in mods]
        for a, b, name in mods:
            inside = _length(_clip([(a, b)], windows))
            if inside > 0:
                rec = programs.setdefault(name, [0.0, 0.0])
                rec[0] += inside / n
                rec[1] += 1 / n
        op_iv = []
        for e in events:
            if e[0] != plane or e[1] != OPS_LINE:
                continue
            a, b = e[3], e[3] + e[4]
            op_iv.append((a, b))
            inside = _length(_clip([(a, b)], windows))
            if inside <= 0:
                continue
            k = bisect.bisect_right(starts, a) - 1
            owner = mods[k][2] if k >= 0 and a < mods[k][1] else "_"
            key = f"{owner}/{op_name(e[2])}"
            ops[key] = ops.get(key, 0.0) + inside / n
        busy = _clip(_union(op_iv), windows)
        busy_ns += _length(busy) / n
        if plane == planes[0]:
            idle = _complement(busy, windows)

    # host spans: self time per name, and the idle gaps by innermost span
    by_thread: dict[tuple[str, str], list] = {}
    for e in events:
        if not DEVICE_PLANE.match(e[0]) and e[2].startswith(SPAN_PREFIXES):
            by_thread.setdefault((e[0], e[1]), []).append(
                (e[3], e[3] + e[4], e[2])
            )
    spans: dict[str, float] = {}
    gaps: dict[str, float] = {}
    covered = 0.0
    for thread_spans in by_thread.values():
        for a, b, name in _leaf_segments(thread_spans):
            inside = _clip([(a, b)], windows)
            if not inside:
                continue
            spans[name] = spans.get(name, 0.0) + _length(inside)
            if name in NOT_A_LAYER:
                continue
            g = _length(_clip(inside, idle))
            if g > 0:
                gaps[name] = gaps.get(name, 0.0) + g
                covered += g
    gaps["_no_span_"] = max(0.0, _length(idle) - covered)

    def top(d: dict, k: int = 10) -> list[list]:
        return [
            [name, v / 1e9]
            for name, v in sorted(d.items(), key=lambda kv: -kv[1])[:k]
            if v > 0
        ]

    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / 1e9,
        "devices": len(planes),
        "spans": {k: v / 1e9 for k, v in spans.items()},
        "programs": {
            k: {"seconds": v[0] / 1e9, "launches": v[1]}
            for k, v in programs.items()
        },
        "device_ops": top(ops),
        "idle_gaps": top(gaps),
    }


def reduce_dir(trace_dir, n_devices: int | None = None) -> dict:
    return reduce_events(read_xplane(find_xplane(trace_dir)), n_devices)


def span_share(trace: dict, span: str) -> float | None:
    """A host span's self time as a share (%) of the timed intervals;
    nothing where the trace holds no such span."""
    if span not in trace["spans"]:
        return None
    return 100.0 * trace["spans"][span] / trace["window_s"]
