"""The reduction from a profiler trace to the per-layer numbers: on a
hand-made trace whose answers are known, on the small trace recorded on
the chip and kept beside this file, and on a trace taken here."""

import json
from pathlib import Path

import pytest

from benchmarks import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
DEV, OPS, MODS = "/device:TPU:0", tr.OPS_LINE, tr.PROGRAMS_LINE
HOST = ("/host:CPU", "python3")


def ev(plane, line, name, start_us, dur_us):
    return [plane, line, name, start_us * 1e3, dur_us * 1e3]


def hand_made():
    """Two timed intervals of 100 us, [0,100) and [200,300); the device
    runs apply_plan2 over [10,30) with two ops inside it, scatter_rows
    over [210,250); a program outside the intervals is not counted."""
    return [
        ev(*HOST, "bench.timed", 0, 100), ev(*HOST, "bench.timed", 200, 100),
        ev(*HOST, "bench.unit", 0, 100), ev(*HOST, "bench.unit", 200, 100),
        ev(*HOST, "bench.ingest", 0, 10),
        ev(*HOST, "ytpu.plan", 10, 40), ev(*HOST, "ytpu.emit", 60, 20),
        ev(*HOST, "ytpu.compact", 200, 60), ev(*HOST, "ytpu.plan", 260, 20),
        ev(DEV, MODS, "jit_apply_plan2(123)", 10, 20),
        ev(DEV, OPS, "%fusion.1 = s32[8]{0} fusion(%a)", 10, 5),
        ev(DEV, OPS, "%while.2 = s32[8]{0} while(%b)", 20, 10),
        ev(DEV, MODS, "jit_scatter_rows(9)", 210, 40),
        ev(DEV, OPS, "%scatter.3 = s32[8]{0} scatter(%c)", 210, 40),
        ev(DEV, MODS, "jit_scatter(77)", 120, 50),
        ev(DEV, OPS, "%scatter.9 = s32[8]{0} scatter(%d)", 120, 50),
        ev(DEV, "Async XLA Ops", "%copy-start = ...", 0, 300),
    ]


def test_hand_made_trace_reduces_to_known_numbers():
    r = tr.reduce_events(hand_made())
    assert r["window_s"] == pytest.approx(200e-6)
    assert r["busy_s"] == pytest.approx(55e-6)  # 5 + 10 + 40
    assert r["devices"] == 1
    assert r["programs"]["apply_plan2"] == {
        "seconds": pytest.approx(20e-6), "launches": 1,
    }
    assert r["programs"]["scatter_rows"]["seconds"] == pytest.approx(40e-6)
    assert "scatter" not in r["programs"]  # ran between the intervals
    assert r["spans"]["ytpu.plan"] == pytest.approx(60e-6)
    assert r["spans"]["bench.ingest"] == pytest.approx(10e-6)
    # a unit's own time is what its inner spans leave: 100-70 and 100-80
    assert r["spans"]["bench.unit"] == pytest.approx(50e-6)
    assert tr.span_share(r, "ytpu.plan") == pytest.approx(30.0)
    assert tr.span_share(r, "ytpu.pack") is None
    assert r["device_ops"][0] == ["scatter_rows/scatter.3", pytest.approx(40e-6)]
    assert ["apply_plan2/while.2", pytest.approx(10e-6)] in r["device_ops"]
    gaps = dict(r["idle_gaps"])
    # idle 145 us: plan covers [15,20)+[30,50) and [260,280), compact
    # [200,210)+[250,260), emit 20, ingest 10, no span the rest
    assert gaps["ytpu.plan"] == pytest.approx(45e-6)
    assert gaps["ytpu.compact"] == pytest.approx(20e-6)
    assert gaps["ytpu.emit"] == pytest.approx(20e-6)
    assert gaps["bench.ingest"] == pytest.approx(10e-6)
    assert gaps["_no_span_"] == pytest.approx(50e-6)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_nested_spans_give_self_time():
    events = [
        ev(*HOST, "bench.timed", 0, 100),
        ev(*HOST, "ytpu.emit", 10, 60), ev(*HOST, "bench.heard", 20, 10),
        ev(*HOST, "bench.heard", 40, 10),
    ]
    r = tr.reduce_events(events)
    assert r["spans"]["ytpu.emit"] == pytest.approx(40e-6)
    assert r["spans"]["bench.heard"] == pytest.approx(20e-6)
    assert r["busy_s"] == 0.0 and r["device_ops"] == []


def test_devices_are_averaged():
    events = [ev(*HOST, "bench.timed", 0, 100)]
    for d, dur in enumerate((10, 20, 30, 40)):
        plane = f"/device:TPU:{d}"
        events.append(ev(plane, MODS, "jit_local_apply(1)", 0, dur))
        events.append(ev(plane, OPS, "%scatter.1 = s32[] scatter()", 0, dur))
    r = tr.reduce_events(events, n_devices=4)
    assert r["devices"] == 4
    assert r["busy_s"] == pytest.approx(25e-6)
    assert r["programs"]["local_apply"] == {
        "seconds": pytest.approx(25e-6), "launches": pytest.approx(1.0),
    }


def test_a_trace_with_no_timed_interval_is_refused():
    with pytest.raises(ValueError, match="bench.timed"):
        tr.reduce_events([ev(*HOST, "ytpu.plan", 0, 1)])


def test_names():
    assert tr.program_name("jit_apply_plan2(2310058380723456173)") == "apply_plan2"
    assert tr.program_name("jit__done_token(31)") == "_done_token"
    assert tr.op_name("%fusion.42 = s32[64]{0:T(128)} fusion(%x)") == "fusion.42"


@pytest.mark.parametrize("cell", ["yws-coldstart", "yws-flood"])
def test_recorded_chip_trace(cell):
    """A slim trace recorded on the TPU v5e by a traced run of the cell,
    cut to its first timed interval(s), with the numbers that run
    printed: the reduction has to give them again."""
    path = DATA / f"trace_{cell}.json"
    rec = json.loads(path.read_text())
    r = tr.reduce_events(rec["events"], rec["n_devices"])
    want = rec["reduced"]
    assert r["window_s"] == pytest.approx(want["window_s"])
    assert r["busy_s"] == pytest.approx(want["busy_s"])
    assert 0 < r["busy_s"] < r["window_s"]
    for name, seconds in want["spans"].items():
        assert r["spans"][name] == pytest.approx(seconds)
    assert {"ytpu.plan", "ytpu.emit", "bench.ingest"} <= set(r["spans"])
    assert "apply_plan2" in r["programs"]
    assert [n for n, _s in r["device_ops"]] == [n for n, _s in want["device_ops"]]


def test_a_trace_taken_here_is_read(run_tiny):
    """On the CPU there is no device plane: the host spans are read, the
    device reads idle, and the per-layer readers that need a device
    program report nothing."""
    r = run_tiny("tiny-flood", trace=True)
    assert r["correct"] is True
    assert {"plan_share.flood", "emit_share.flood", "ingest_share.flood",
            "compiles_in_window.flood"} <= set(r["metrics"])
    assert "apply_roofline.flood" not in r["metrics"]
    assert "peak_hbm_gb.flood" not in r["metrics"]
    assert r["device"]["busy_s"] == 0.0 and r["device"]["window_s"] > 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
