"""chip_smoke.py on the CPU (ISSUE 21): the script refuses to pass
without a chip, each phase's body holds at a tiny size with the same
checks, the staging fence fences, and the native loader only ever loads
a binary built from the sources beside it."""

import dataclasses
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

import yjs_tpu as Y
from yjs_tpu import native
from yjs_tpu.ops import BatchEngine
from yjs_tpu.ops import engine as engine_mod

ROOT = Path(__file__).resolve().parent.parent

spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(spec)
sys.modules["chip_smoke"] = chip_smoke  # dataclasses resolves the module
spec.loader.exec_module(chip_smoke)

TINY = chip_smoke.Sizes(
    n_docs=32, storm=8, b4=1, prepend=1, flushes=4, active=6, slide=2,
    joiners=4, others=4, served_docs=16, served_rooms=2,
)


def test_refuses_to_pass_without_a_chip():
    """Under JAX_PLATFORMS=cpu the script exits non-zero, names the
    missing chip and prints no result line."""
    r = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300,
    )
    assert r.returncode != 0
    assert "no tpu device" in r.stderr and "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_provider_phase_body_tiny():
    report = chip_smoke.run_provider(TINY, "cpu")
    assert report["device"]["platform"] == "cpu"
    assert report["host"]["native_plan_available"] is True
    assert [c["cap"] for c in report["cold_load"]] == [2048, 16384, 131072]
    assert report["steady_state"]["flushes"] == TINY.flushes
    assert report["steady_state"]["realloc_bytes"] == 0
    assert report["late_joiners"]["n"] == TINY.joiners
    c = report["checks"]
    assert c["rooms"] == c["touched"] + c["big"] + c["others"]
    assert c["others"] == TINY.others and c["big"] == 2


def test_provider_phase_fails_on_another_platform():
    with pytest.raises(AssertionError, match="no tpu device"):
        chip_smoke.run_provider(TINY, "tpu")


def test_mesh_phase_body_tiny():
    """The same body over a four-device mesh (the virtual CPU mesh):
    every table sharded four ways, no device holding the whole."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    sz = dataclasses.replace(TINY, b4=0, prepend=0)  # the big rooms ran above
    report = chip_smoke.run_provider(sz, "cpu", mesh_devices=4)
    assert set(report["resident"]["devices_per_table"].values()) == {4}


@pytest.mark.cluster
def test_served_phase_body_tiny():
    """Supervisor + gateway here, the device path in the shard child
    (on its CPU backend), and this process's own backend untouched by
    the phase: the check inside would fail otherwise — but pytest has
    long initialised one, so the phase runs in a child."""
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import chip_smoke\n"
        "sz = chip_smoke.Sizes(**%r)\n"
        "r = chip_smoke.run_served(sz, 'cpu')\n"
        "assert r['shard']['device_bytes'] > 0, r\n"
        "assert r['shard']['docs'] == sz.served_rooms, r\n"
        "print('SERVED_OK')\n"
    ) % (str(ROOT), TINY.__dict__)
    r = subprocess.run(
        [sys.executable, "-c", code],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "SERVED_OK" in r.stdout


# -- the staging fence ---------------------------------------------------------


def _edits(n: int, seed: int) -> list[bytes]:
    import random

    gen = random.Random(seed)
    d = Y.Doc(gc=False)
    d.client_id = 500 + seed
    out = []
    for _ in range(n):
        sv = Y.encode_state_vector(d)
        t = d.get_text("text")
        t.insert(gen.randint(0, len(t)), gen.choice("abcdef") * 3)
        out.append(Y.encode_state_as_update(d, sv))
    return out


def test_staging_fence_waits_on_real_completion(monkeypatch):
    """Every marker the pipeline books is live when it is waited on or
    polled: the dispatch that follows donates the resident tables, and a
    marker taken from them would be a deleted array by then (is_ready
    and block_until_ready raise on one).  acquire() must find its slot's
    previous dispatch complete before the host buffer is rewritten."""
    monkeypatch.setenv("YTPU_FLUSH_PIPELINE", "1")
    monkeypatch.setenv("YTPU_FLUSH_CHUNK", "1")  # one dispatch per doc
    seen = []
    real_acquire = engine_mod._FlushPipeline.acquire

    def acquire(pl, shape, dtype):
        marker = pl._slots[pl._turn ^ 1].marker  # the slot up next
        slot = real_acquire(pl, shape, dtype)
        if marker is not None:
            # the fence has passed: the marker is alive and complete
            assert not marker.is_deleted()
            assert marker.is_ready()
            seen.append(marker)
        return slot

    monkeypatch.setattr(engine_mod._FlushPipeline, "acquire", acquire)
    eng = BatchEngine(4)
    pl = eng._pl
    # the first round loads empty slots: row blocks, staged anew each
    # time, which acquire no slot; the six after it ride the lanes
    docs = [_edits(7, seed) for seed in range(4)]
    for r in range(7):
        for i in range(4):
            eng.queue_update(i, docs[i][r])
        eng.flush()  # 4 dispatches, each donating the previous tables
    assert len(seen) >= 20
    # the tables of an earlier dispatch were donated; its marker was not
    assert all(not m.is_deleted() for m in seen)
    assert all(not m.is_deleted() for m in pl._inflight)
    eng.export_from_device = True
    for i in range(4):
        d = Y.Doc(gc=False)
        for u in docs[i]:
            Y.apply_update(d, u)
        assert eng.text(i) == d.get_text("text").to_string()


def test_fence_lets_device_errors_out():
    """A failed wait is an error, not "ready"."""
    pl = engine_mod._FlushPipeline()
    x = jax.numpy.zeros(4)
    donated = jax.jit(lambda a: a + 1, donate_argnums=(0,))
    donated(x)
    slot = pl.acquire((1, 8), np.int32)
    pl.dispatched(x, slot)  # a deleted array booked as a marker: a bug
    pl.acquire((1, 8), np.int32)
    with pytest.raises(Exception, match="deleted"):
        pl.acquire((1, 8), np.int32)


# -- the native loader ---------------------------------------------------------


def _fake_sources(d: Path, body: str) -> None:
    (d / "transcode.cpp").write_text(
        '#include "wire.h"\nextern "C" int probe() { return %s; }\n' % body
    )
    (d / "plancore.cpp").write_text("// nothing\n")
    (d / "wire.h").write_text("// nothing\n")


@pytest.mark.skipif(shutil.which("g++") is None, reason="needs g++")
def test_native_loader_is_keyed_by_source_content(tmp_path):
    import ctypes

    _fake_sources(tmp_path, "1")
    so1 = native.build(str(tmp_path))
    assert ctypes.CDLL(so1).probe() == 1
    assert native.build(str(tmp_path)) == so1  # same content: no rebuild
    mtime = os.path.getmtime(so1)
    # a binary that matches no source here (copied in, or left from
    # another commit) is never the one returned, and does not survive
    stale = tmp_path / (native._SO_PREFIX + "0123456789abcdef.so")
    shutil.copy(so1, stale)
    legacy = tmp_path / "_transcode.so"
    shutil.copy(so1, legacy)
    assert native.build(str(tmp_path)) == so1
    assert os.path.getmtime(so1) == mtime
    assert not stale.exists()
    # a source's content changes: another key, built from what is there
    _fake_sources(tmp_path, "2")
    so2 = native.build(str(tmp_path))
    assert so2 != so1 and not os.path.exists(so1)
    assert ctypes.CDLL(so2).probe() == 2
    # file times play no part: an older mtime on the source changes nothing
    os.utime(tmp_path / "transcode.cpp", (1, 1))
    assert native.build(str(tmp_path)) == so2


@pytest.mark.skipif(shutil.which("g++") is None, reason="needs g++")
def test_native_loader_reports_the_compiler_message(tmp_path):
    _fake_sources(tmp_path, "not_declared_anywhere")
    with pytest.raises(subprocess.CalledProcessError) as e:
        native.build(str(tmp_path))
    assert b"not_declared_anywhere" in e.value.stderr
    assert not list(tmp_path.glob("*.so")) and not list(tmp_path.glob("*.tmp"))


def test_loaded_core_is_built_from_the_committed_sources():
    if native.load() is None:
        pytest.skip("native core unavailable: " + str(native.load_error()))
    assert native.load()._name == native.build()
