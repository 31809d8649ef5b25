"""The host allocator's policy (``yjs_tpu/ops/host_heap.py``).

A child process (the policy is a process's, and the test runner's own
heap has a history) does what a cold load does to the allocator: a new
thread and the main thread each fill five 4 MiB blocks, the main thread
frees all ten, six rounds.  With glibc's defaults every round faults the
pages in again (the main heap is trimmed, the thread's heap unmapped);
with the policy the later rounds fault nothing.
"""

import ctypes
import os
import subprocess
import sys
from pathlib import Path

import pytest

from yjs_tpu.ops import host_heap
from yjs_tpu.ops.engine import BatchEngine

MODULE = Path(host_heap.__file__)

CHILD = r"""
import ctypes, importlib.util, resource, sys, threading
spec = importlib.util.spec_from_file_location("host_heap", sys.argv[1])
host_heap = importlib.util.module_from_spec(spec)
spec.loader.exec_module(host_heap)
applied = host_heap.ensure_heap_kept() if sys.argv[2] == "keep" else None
libc = ctypes.CDLL(None)
libc.malloc.restype = ctypes.c_void_p
libc.malloc.argtypes = [ctypes.c_size_t]
libc.free.argtypes = [ctypes.c_void_p]
libc.memset.argtypes = [ctypes.c_void_p, ctypes.c_int, ctypes.c_size_t]
def fill(n, out):
    blocks = [libc.malloc(n) for _ in range(5)]
    for p in blocks:
        libc.memset(p, 1, n)
    out.extend(blocks)
faults = []
for _ in range(6):
    held = []
    before = resource.getrusage(resource.RUSAGE_SELF).ru_minflt
    t = threading.Thread(target=fill, args=(4 << 20, held))
    t.start()
    t.join()
    fill(4 << 20, held)
    for p in held:
        libc.free(p)
    faults.append(resource.getrusage(resource.RUSAGE_SELF).ru_minflt - before)
print(applied, *faults)
"""

needs_glibc = pytest.mark.skipif(
    not hasattr(ctypes.CDLL(None), "mallopt"), reason="no glibc mallopt here"
)


def run_child(mode: str, env: dict | None = None):
    clean = {
        k: v for k, v in os.environ.items()
        if k not in host_heap._OPERATOR_ENV
    }
    out = subprocess.run(
        [sys.executable, "-c", CHILD, str(MODULE), mode],
        env={**clean, **(env or {})}, capture_output=True, text=True,
        check=True, timeout=120,
    ).stdout.split()
    return out[0], [int(n) for n in out[1:]]


@needs_glibc
def test_freed_memory_is_used_again_without_a_fault():
    applied, kept = run_child("keep")
    _, default = run_child("default")
    assert applied == "True"
    # 40 MiB a round is 10,240 pages
    assert kept[0] > 5000, kept
    assert max(kept[-3:]) < 200, kept
    assert min(default[-3:]) > 5000, default


@needs_glibc
@pytest.mark.parametrize("name", host_heap._OPERATOR_ENV)
def test_an_operators_own_setting_stands(name):
    value = "glibc.malloc.top_pad=1" if name == "GLIBC_TUNABLES" else "131072"
    applied, _faults = run_child("keep", {name: value})
    assert applied == "False"


def test_every_engine_sets_it_once_a_process():
    BatchEngine(1, policy="cpu")
    BatchEngine(1, policy="cpu")
    info = host_heap.ensure_heap_kept.cache_info()
    assert info.currsize == 1 and info.hits >= 1
