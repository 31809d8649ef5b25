"""Configuration ``yws-mesh4`` and its cell ``mesh4-coldstart``: the
deployment is ``yws-1chip`` four times over, one share to a chip's
block of slots, and the cell reloads the group ``yws-coldstart``
reloads, dealt evenly over the blocks."""

import collections
import json
import random

import pytest

from benchmarks import deployment, harness, oracle

ROOTS = (harness.HERE,)
MANIFEST = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
SEEDS = (7, 2**31 + 12345)


def config(name):
    return harness.load_data("configs", name, ROOTS)


def group(cfg, seed):
    """The rooms the ``reload`` generator picks, as it picks them."""
    traffic = harness.load_data("traffic", "coldstart", ROOTS)
    plan = deployment.room_plan(cfg, seed)
    rng = random.Random(f"reload:{seed}")
    n_storm = traffic["group_storm_rooms"]
    rooms = deployment.pick_rooms(
        plan, cfg, "distinct", traffic["group_rooms"] - n_storm, rng
    ) + deployment.pick_rooms(plan, cfg, "storm", n_storm, rng)
    return plan, rooms


def test_the_cell_is_the_coldstart_mix_on_the_mesh_configuration():
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    cold, mesh = cells["yws-coldstart"], cells["mesh4-coldstart"]
    assert mesh["traffic"] == cold["traffic"] == "coldstart"
    assert (mesh["config"], mesh["chips"]) == ("yws-mesh4", 4)
    # what it reports, the one-chip cold start reports: the two differ
    # by the deployment alone.  (Not the other way round: the lists of
    # PR 25's span readers are pinned by test_layer_spans.py.)
    reports = [
        m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]
        if "mesh4-coldstart" in m.get("workloads", ())
    ]
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        if m["name"] in reports:
            assert "yws-coldstart" in m["workloads"], m["name"]
    assert {
        "bulk_rate", "device_idle.bulk", "peak_hbm_gb.bulk",
        "compiles_in_window.bulk", "scatter_ms_a_load.bulk",
        "apply_ms_a_load.bulk",
    } <= set(reports)


def test_the_configuration_is_yws_1chip_four_times_over():
    one, four = config("yws-1chip"), config("yws-mesh4")
    assert (four["chips"], four["mesh_devices"], four["slots"]) == (4, 4, 16384)
    assert four["rooms"] == {k: 4 * n for k, n in one["rooms"].items()}
    for key in ("provider", "guarantees", "reduced"):
        assert four[key] == one[key]
    assert four["reduced"] == []
    assert any("ytpu_cluster.py" in a for a in four["assumed"])


@pytest.mark.parametrize("seed", SEEDS)
def test_every_block_of_4096_slots_holds_the_one_chip_mix(seed):
    one, four = config("yws-1chip"), config("yws-mesh4")
    plan = deployment.room_plan(four, seed)
    assert len(plan) == 16384 and len({r.guid for r in plan}) == 16384
    want = collections.Counter(r.kind for r in deployment.room_plan(one, seed))
    assert want == one["rooms"]
    for block in range(4):
        rooms = plan[block * 4096 : (block + 1) * 4096]
        assert collections.Counter(r.kind for r in rooms) == want
        # the committed traces cycled: each is held three or four times
        held = collections.Counter(
            r.trace for r in rooms if r.kind == "distinct"
        )
        assert len(held) == 1024 and set(held.values()) == {3, 4}


@pytest.mark.parametrize("seed", SEEDS)
def test_the_group_is_the_one_chip_group_dealt_128_rooms_a_block(seed):
    _plan1, rooms1 = group(config("yws-1chip"), seed)
    plan4, rooms4 = group(config("yws-mesh4"), seed)

    def traces(rooms):
        return collections.Counter((r.kind, r.trace) for r in rooms)

    assert len(rooms4) == len({r.guid for r in rooms4}) == 512
    assert traces(rooms4) == traces(rooms1)
    assert set(traces(rooms4).values()) == {1}  # one room a trace
    assert sum(
        oracle.ELEMENTS[r.kind][r.trace] for r in rooms4
    ) == 3_234_373
    slot = {r.guid: i for i, r in enumerate(plan4)}
    blocks = collections.Counter(slot[r.guid] // 4096 for r in rooms4)
    assert blocks == {0: 128, 1: 128, 2: 128, 3: 128}
    storm = collections.Counter(
        slot[r.guid] // 4096 for r in rooms4 if r.kind == "storm"
    )
    assert storm == {0: 8, 1: 8, 2: 8, 3: 8}
