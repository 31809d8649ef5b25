"""The harness end to end on the CPU at a tiny size: every phase of a
run (deal the rooms, load, rehearse, window, oracle, result line) with
the same code the chip runs.  Rates read here are the CPU's and are
asserted only to exist."""

import json

import jax
import pytest

from benchmarks import deployment, harness

SEEDS = [7, 2**31, 2**31 + 12345, 3_000_000_001]


def assert_result(r, end_to_end):
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] > 0
    assert set(r["metrics"]) == set(end_to_end) | {"setup_s"}
    for m in r["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    json.dumps(r)


@pytest.mark.parametrize("seed", SEEDS)
def test_coldstart_runs_and_is_correct(run_tiny, seed):
    assert_result(run_tiny("tiny-coldstart", seed=seed), ["bulk_rate"])


@pytest.mark.parametrize("seed", SEEDS[1:3])
def test_flood_runs_and_is_correct(run_tiny, seed, capsys):
    assert_result(run_tiny("tiny-flood", seed=seed), ["edit_rate"])
    out = capsys.readouterr().out
    assert "circuit rates in the window" in out
    assert "check compiles_in_window: 0 (limit 0) ok" in out


def test_big_rooms_are_loaded_and_compared(run_tiny, capsys):
    """A B4 and a prepend room grow the tables to cap 131072 and are
    among the rooms held against the oracle."""
    assert_result(run_tiny("tiny-big"), ["bulk_rate"])
    out = capsys.readouterr().out
    assert "'cap': 131072" in out


def test_mesh_cell_shards_the_tables(run_tiny, capsys):
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    r = run_tiny("tiny-mesh")
    assert_result(r, ["bulk_rate"])
    assert r["device"]["count"] == 4
    assert "check tables_unevenly_sharded: 0 (limit 0) ok" in capsys.readouterr().out


@pytest.mark.parametrize("part", range(4))
def test_the_table_of_base_states_is_what_a_ydoc_replays(part):
    """``benchmarks/base_states.json`` (state vector and text digest of
    every committed trace, from which a reload's elements are counted
    and an untouched room is judged), recounted a quarter at a time."""
    from benchmarks import oracle

    for kind, entries in oracle.BASE_STATES.items():
        traces = deployment.load_traces(f"{kind}_traces")
        assert len(traces) == len(entries)
        for i in range(part, len(traces), 4):
            w = oracle.Want(oracle.Oracle.replay([traces[i]]))
            assert entries[i] == (w.sv, oracle.text_digest(w.text))
            assert oracle.ELEMENTS[kind][i] == sum(w.sv.values())


def test_a_cell_needs_its_chips(run_tiny):
    with pytest.raises(harness.BenchError, match="needs 1 tpu device"):
        harness.run_cell("yws-coldstart", 1, 1.0, False, platform="tpu")


def test_same_seed_same_rooms_other_seed_other_order(roots):
    cfg = harness.load_data("configs", "tiny-1chip", roots)
    a = deployment.room_plan(cfg, 2**31 + 5)
    b = deployment.room_plan(cfg, 2**31 + 5)
    c = deployment.room_plan(cfg, 2**31 + 6)
    assert a == b
    assert [r.trace for r in a] != [r.trace for r in c]
    # every seed deals the same number of rooms of each kind
    assert sorted(r.kind for r in a) == sorted(r.kind for r in c)


def test_the_full_configurations_deal_every_slot(manifest):
    """The committed configurations at their real size: rooms fill the
    slots, a chip's block holds one share of the mix, and the traffic's
    groups find the traces they want (no device is touched)."""
    import random

    for entry in manifest["workloads"]:
        cfg = harness.load_data("configs", entry["config"], (harness.HERE,))
        plan = deployment.room_plan(cfg, 2**31 + 9)
        assert len(plan) == cfg["slots"] == len({r.guid for r in plan})
        blocks = max(1, cfg["mesh_devices"])
        per = len(plan) // blocks
        mixes = [
            sorted(r.kind for r in plan[b * per : (b + 1) * per])
            for b in range(blocks)
        ]
        assert all(m == mixes[0] for m in mixes)
        picked = deployment.pick_rooms(
            plan, cfg, "distinct", 1200, random.Random(1)
        )
        # the same documents in every seed: the k-th room holds trace
        # k mod 1024, and no room is picked twice
        assert [r.trace for r in picked] == [k % 1024 for k in range(1200)]
        assert len({r.guid for r in picked}) == 1200
        if blocks > 1:  # taken from the chips' blocks in turn
            slot = {r.guid: i for i, r in enumerate(plan)}
            assert [slot[r.guid] // per for r in picked[:8]] == [
                k % blocks for k in range(8)
            ]
