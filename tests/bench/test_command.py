"""The command itself: it refuses a CPU, refuses a checkout that holds
only the benchmark, and names what is missing; the spread helper's
three estimators."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks import harness, spread

ROOT = harness.ROOT


def run_command(cwd, *args):
    return subprocess.run(
        [sys.executable, "benchmarks/run.py", *args], cwd=cwd,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300,
    )


CELLS = [w["name"] for w in harness.load_manifest()["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_command_refuses_a_cpu(workload):
    r = run_command(
        ROOT, "--workload", workload, "--seed", str(2**31 + 7),
        "--seconds", "1", "--trace", "0",
    )
    assert r.returncode != 0
    assert "tpu device" in r.stderr and "'cpu'" in r.stderr
    assert '"correct"' not in r.stdout


def test_a_fault_control_refuses_a_cpu_too():
    r = run_command(
        ROOT, "--workload", CELLS[0], "--seed", "3", "--seconds", "1",
        "--fault", "skip_wal",
    )
    assert r.returncode == 1 and '"correct"' not in r.stdout


def test_command_refuses_an_unknown_workload():
    r = run_command(
        ROOT, "--workload", "no-such-cell", "--seed", "1", "--seconds", "1",
    )
    assert r.returncode != 0 and "no workload 'no-such-cell'" in r.stderr


def test_command_fails_where_only_the_benchmark_is(tmp_path):
    """In a directory that holds BENCHMARK.json and the files under
    ``paths`` and nothing else there is no program to measure."""
    manifest = harness.load_manifest()
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in manifest["paths"]:
        shutil.copytree(
            ROOT / p, tmp_path / p,
            ignore=shutil.ignore_patterns("__pycache__"),
        )
    r = run_command(
        tmp_path, "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
        "--trace", "0",
    )
    assert r.returncode != 0 and '"correct"' not in r.stdout


def test_spread_estimators():
    e = spread.estimators([100.0, 101.0, 102.0, 103.0, 104.0, 120.0])
    assert e["median"] == 102.5
    assert e["range"] == pytest.approx(20 / 102.5)
    assert e["range_less_farthest"] == pytest.approx(4 / 102.5)
    # statistics.quantiles, exclusive: q1 = 100.75, q3 = 108
    assert e["quartiles"] == pytest.approx(7.25 / 102.5)
    assert e["widest"] == e["range"]


def test_spread_reads_the_last_result_line(tmp_path, capsys):
    logs = []
    for i, v in enumerate([10.0, 10.1, 10.2, 9.9, 10.05, 10.15]):
        p = tmp_path / f"run{i}.log"
        p.write_text(
            "[bench] noise\n{not json\n" + json.dumps({
                "correct": True, "metrics": {"bulk_rate": {"value": v, "unit": "x"}},
            }) + "\n"
        )
        logs.append(str(p))
    assert spread.main(["bulk_rate", *logs[:3], "--", *logs[3:]]) == 0
    out = capsys.readouterr().out
    assert "set 1: n 3 median 10.1" in out and "set 2: n 3" in out
    assert "second median against the first" in out
