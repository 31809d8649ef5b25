"""The fault controls, kept as tests at a size a test run can hold: each
breaks one guarantee of the configuration underneath the timed path and
must turn ``correct`` false in every kind of cell.  On the chip, at the
cells' own size, ``benchmarks/run.py --fault`` runs the same faults."""

import pytest

from benchmarks import faults


@pytest.mark.parametrize("workload", ["tiny-coldstart", "tiny-flood"])
@pytest.mark.parametrize("fault", faults.FAULTS)
def test_fault_turns_correct_false(run_tiny, workload, fault, capsys):
    r = run_tiny(workload, fault=fault)
    assert r["correct"] is False and r["failed"] >= 1
    assert "FAILED" in capsys.readouterr().out


@pytest.mark.parametrize("workload", ["tiny-coldstart", "tiny-flood"])
def test_device_step_that_returns_its_state_unchanged(
    run_tiny, workload, monkeypatch, capsys
):
    """The rest of a run with the timed path broken underneath: the
    device kernels hand back the tables they were given, so the host
    mirror is right and the chip is stale.  Only the text read back from
    the device can see it.  (Both kernels: a compaction's `scatter_rows`
    rewrites a room's rows from the host mirror, and would heal what a
    broken `apply_plan2` left.)"""
    from yjs_tpu.ops import kernels

    real_apply, real_rows = kernels.apply_plan2, kernels.scatter_rows
    state = {"armed": False}

    def apply_plan2(dyn, lanes, *key):
        return dyn if state["armed"] else real_apply(dyn, lanes, *key)

    def scatter_rows(right, deleted, starts, *new):
        if state["armed"]:
            return right, deleted, starts
        return real_rows(right, deleted, starts, *new)

    monkeypatch.setattr(kernels, "apply_plan2", apply_plan2)
    monkeypatch.setattr(kernels, "scatter_rows", scatter_rows)
    # sound through the load and the rehearsal, broken from the window on
    from benchmarks import harness

    begin = harness.Cell.unit

    def unit(self):
        state["armed"] = self.in_window
        return begin(self)

    monkeypatch.setattr(harness.Cell, "unit", unit)
    r = run_tiny(workload)
    assert r["correct"] is False
    out = capsys.readouterr().out
    assert "check rooms_device_text_differs" in out
    assert "check rooms_device_rows_differ: 0" not in out
    assert "check rooms_host_text_differs: 0 (limit 0) ok" in out


@pytest.mark.parametrize("seed", [5, 2**31 + 6, 3_000_000_007])
@pytest.mark.parametrize("workload", ["tiny-coldstart", "tiny-flood"])
def test_a_fault_in_a_room_that_is_not_replayed_is_seen(
    run_tiny, workload, seed, monkeypatch, capsys
):
    """Every room that took traffic is compared, not a sample of them:
    with one room replayed on the ``Y.Doc`` oracle, an update that was
    journaled and then lost in the engine still shows, in the statement
    the plain clients or the table of base states make of its room."""
    from benchmarks import oracle

    monkeypatch.setitem(oracle.SAMPLE, "touched", 1)
    r = run_tiny(workload, seed=seed, fault="drop_in_engine")
    assert r["correct"] is False
    out = capsys.readouterr().out
    assert "check acknowledged_not_in_wal: 0 (limit 0) ok" in out
    # in the room as it stands (a lost backspace shows in the text
    # alone), or in a life of it that a release ended
    assert any(
        f"check {name}: 0" not in out
        for name in (
            "rooms_state_vector_differs", "rooms_host_text_differs",
            "lives_state_vector_differs",
        )
    )


def test_what_the_plain_clients_hold_is_compared(run_tiny, monkeypatch, capsys):
    """A typist that holds another text than it sent: the rooms it
    speaks for differ, and so does its statement from the replays."""
    from benchmarks import plain_client

    text = plain_client.PlainText.text
    monkeypatch.setattr(
        plain_client.PlainText, "text", lambda self: text(self) + "!"
    )
    r = run_tiny("tiny-flood")
    assert r["correct"] is False
    out = capsys.readouterr().out
    assert "check rooms_statement_differs: 10 (limit 0) FAILED" in out
    assert "check rooms_host_text_differs: 10 (limit 0) FAILED" in out


def test_unknown_fault_is_refused():
    with pytest.raises(ValueError, match="unknown fault"):
        faults.install("bit_flip", object(), 1)
