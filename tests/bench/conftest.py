"""Shared by the benchmark's tests: the tiny cells under
``tests/bench/cells`` (configurations and traffic mixes that exist only
as files there) and a manifest that names them."""

import copy
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

CELLS = Path(__file__).resolve().parent / "cells"
BIG_SEED = 2**31 + 12345  # the driver's seeds pass 32 signed bits


def tiny_manifest_of(manifest: dict) -> dict:
    """BENCHMARK.json with its cells swapped for the tiny ones: every
    metric of a ``coldstart`` cell is reported by ``tiny-coldstart``,
    ``tiny-big`` and ``tiny-mesh``, those of a ``flood`` cell by
    ``tiny-flood``."""
    m = copy.deepcopy(manifest)
    m["workloads"] = [
        {"name": "tiny-coldstart", "config": "tiny-1chip",
         "traffic": "tiny-coldstart", "chips": 1, "why": "tests"},
        {"name": "tiny-flood", "config": "tiny-1chip",
         "traffic": "tiny-flood", "chips": 1, "why": "tests"},
        {"name": "tiny-big", "config": "tiny-big",
         "traffic": "tiny-coldstart", "chips": 1, "why": "tests"},
        {"name": "tiny-mesh", "config": "tiny-mesh4",
         "traffic": "tiny-coldstart", "chips": 4, "why": "tests"},
    ]
    # a tiny cell stands in for every cell of the same traffic mix
    tiny = {
        "coldstart": ["tiny-coldstart", "tiny-big", "tiny-mesh"],
        "flood": ["tiny-flood"],
    }
    swap = {w["name"]: tiny.get(w["traffic"], []) for w in manifest["workloads"]}
    for metric in m["end_to_end"] + m["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = sorted(
                {t for w in metric["workloads"] for t in swap[w]}
            )
    return m


@pytest.fixture(scope="session")
def manifest() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.fixture(scope="session")
def tiny_manifest(manifest) -> dict:
    return tiny_manifest_of(manifest)


@pytest.fixture(scope="session")
def roots():
    from benchmarks import harness

    return (CELLS, harness.HERE)


@pytest.fixture
def run_tiny(tiny_manifest, roots):
    """Run one tiny cell on this CPU through the test-only entry (the
    command itself refuses a CPU: see test_command.py)."""
    from benchmarks import harness

    def run(workload, seed=BIG_SEED, seconds=0.4, trace=False, **kw):
        return harness.run_cell(
            workload, seed, seconds, trace, platform="cpu", roots=roots,
            manifest=kw.pop("manifest", tiny_manifest), **kw,
        )

    return run
