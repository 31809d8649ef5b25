"""The readers this configuration brought: ``scatter_ms_a_load`` and
``apply_ms_a_load`` (device time of the row scatter and of the bulk
apply a timed load): on synthetic programs, on the traces recorded on
the chip and kept beside this file, and on a traced run taken here."""

import json
from pathlib import Path

import pytest

from benchmarks import harness
from benchmarks import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
ROOTS = (harness.HERE,)
DEV, OPS, MODS = "/device:TPU:{}", tr.OPS_LINE, tr.PROGRAMS_LINE
HOST = ("/host:CPU", "python3")


def reader(name):
    return harness.load_module("layer_metrics", name, ROOTS)


def recorded(cell):
    rec = json.loads((DATA / f"trace_{cell}.json").read_text())
    return rec, tr.reduce_events(rec["events"], rec["n_devices"])


@pytest.mark.parametrize("name, programs, want", [
    ("scatter_ms_a_load", {"scatter_rows": 0.026, "apply_plan2": 0.3}, 13.0),
    ("scatter_ms_a_load", {"apply_plan2": 0.3, "_done_token": 1e-6}, None),
    ("apply_ms_a_load", {"apply_plan2": 0.3, "scatter_rows": 0.026}, 150.0),
    ("apply_ms_a_load", {"local_apply": 0.1, "scatter_rows": 0.026}, 50.0),
    ("apply_ms_a_load", {"scatter_rows": 0.026}, None),
    ("apply_ms_a_load", {}, None),
])
def test_kernel_time_a_load_on_synthetic_programs(name, programs, want):
    trace = {"programs": {
        k: {"seconds": s, "launches": 2.0} for k, s in programs.items()
    }}
    got = reader(name).read(trace, {"units": 2})
    assert got == want or got == pytest.approx(want)
    # a window that timed no load has no load to divide by
    assert reader(name).read(trace, {"units": 0}) is None


def test_a_mesh_reads_a_chips_part():
    """Four chips, each running its part of the sharded programs: the
    reduction averages over the chips, the readers divide by the loads."""
    events = [[*HOST, "bench.timed", 0.0, 1e6]]
    for d, (apply_us, scatter_us) in enumerate(
        [(100, 10), (200, 20), (300, 30), (400, 40)]
    ):
        plane = DEV.format(d)
        events += [
            [plane, MODS, "jit_local_apply(5)", 0.0, apply_us * 1e3],
            [plane, OPS, "%scatter.1 = s32[] scatter()", 0.0, apply_us * 1e3],
            [plane, MODS, "jit_scatter_rows(6)", 5e5, scatter_us * 1e3],
            [plane, OPS, "%while.3 = s32[] while()", 5e5, scatter_us * 1e3],
        ]
    trace = tr.reduce_events(events, n_devices=4)
    counters = {"units": 2}
    assert reader("apply_ms_a_load").read(trace, counters) == pytest.approx(
        0.250 / 2
    )
    assert reader("scatter_ms_a_load").read(trace, counters) == pytest.approx(
        0.025 / 2
    )


def test_on_the_traces_recorded_on_the_chip():
    rec, trace = recorded("yws-coldstart")
    counters = {"units": rec["units"]}
    assert rec["units"] >= 1
    scatter = reader("scatter_ms_a_load").read(trace, counters)
    apply = reader("apply_ms_a_load").read(trace, counters)
    # PR 24's trace, before a staged block followed its rooms: the
    # full-width scatter of one load took 0.36 s of device time
    assert scatter == pytest.approx(
        1e3 * trace["programs"]["scatter_rows"]["seconds"] / rec["units"]
    )
    assert apply == pytest.approx(
        1e3 * trace["programs"]["apply_plan2"]["seconds"] / rec["units"]
    )
    assert scatter > apply > 0
    rec, trace = recorded("yws-flood")
    counters = {"units": rec["units"]}
    assert "scatter_rows" not in trace["programs"]
    assert reader("scatter_ms_a_load").read(trace, counters) is None
    assert reader("apply_ms_a_load").read(trace, counters) > 0


def test_a_traced_run_taken_here_has_no_device_plane_to_read(run_tiny):
    """On the CPU the trace holds no device plane: the kernel readers
    report nothing and the line leaves their metrics out, as it does on
    a parent that lacks a program."""
    r = run_tiny("tiny-mesh", trace=True)
    assert r["correct"] is True
    assert "compiles_in_window.bulk" in r["metrics"]
    assert "scatter_ms_a_load.bulk" not in r["metrics"]
    assert "apply_ms_a_load.bulk" not in r["metrics"]
