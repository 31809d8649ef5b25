"""ctypes loader for the native core (transcode.cpp + plancore.cpp).

One build, from the committed sources: the shared object is named after
a hash of their content (and of the compile command), so a binary that
was built from other sources -- copied in with a checkout, or left over
from an earlier commit -- is never loaded.  The first ``load()`` of a
checkout compiles with ``g++``; later processes find the keyed file.

``YTPU_NO_NATIVE`` opts out (callers use the pure-Python codec).  When
the build or the load fails, ``load()`` logs the compiler's message
once and returns None, and ``load_error()`` keeps the message for
callers that must not run without the core (``chip_smoke.py``).
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import subprocess

import numpy as np

logger = logging.getLogger("yjs_tpu.native")

_DIR = os.path.dirname(__file__)
_SOURCES = ("transcode.cpp", "plancore.cpp", "wire.h")
_CXX = ("g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread")
_SO_PREFIX = "_ytpu_native."

_lib = None
_tried = False
_error: str | None = None


def build(src_dir: str = _DIR) -> str:
    """Path of the shared object for the sources in ``src_dir`` as they
    are on disk, compiled now unless a file under that content key is
    already there.  Binaries under any other key are removed: they match
    no source in the directory."""
    h = hashlib.blake2b(" ".join(_CXX).encode(), digest_size=8)
    for name in _SOURCES:
        with open(os.path.join(src_dir, name), "rb") as f:
            h.update(name.encode() + b"\0" + f.read() + b"\0")
    so = os.path.join(src_dir, f"{_SO_PREFIX}{h.hexdigest()}.so")
    if not os.path.exists(so):
        # shard children of one supervisor may race to build the same
        # key: compile to a private name, then rename atomically
        tmp = f"{so}.{os.getpid()}.tmp"
        srcs = [
            os.path.join(src_dir, n) for n in _SOURCES if n.endswith(".cpp")
        ]
        try:
            subprocess.run(
                [*_CXX, "-o", tmp, *srcs],
                check=True, capture_output=True, timeout=600,
            )
            os.replace(tmp, so)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
    for other in glob.glob(os.path.join(src_dir, _SO_PREFIX + "*.so")):
        if other != so:
            try:
                os.unlink(other)
            except OSError:
                pass
    return so


def load_error() -> str | None:
    """Why the last ``load()`` returned None (the compiler's stderr, the
    loader's message, or the opt-out), else None."""
    return _error


def load():
    """The loaded library, or None if unavailable (see ``load_error``)."""
    global _lib, _tried, _error
    if _lib is not None or _tried:
        return _lib
    _tried = True
    if os.environ.get("YTPU_NO_NATIVE"):
        _error = "YTPU_NO_NATIVE is set"
        return None
    try:
        lib = ctypes.CDLL(build())
    except subprocess.CalledProcessError as e:
        _error = "g++ failed:\n" + (e.stderr or b"").decode(errors="replace")
    except (OSError, subprocess.TimeoutExpired) as e:
        _error = f"{type(e).__name__}: {e}"
    if _error is not None:
        logger.warning(
            "native core unavailable; the pure-Python codec and planner "
            "will serve the host path, 10-50x slower: %s", _error[-2000:],
        )
        return None
    u8p = ctypes.POINTER(ctypes.c_uint8)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    i64p = ctypes.POINTER(ctypes.c_int64)
    lib.ytpu_count_v1.restype = ctypes.c_int
    lib.ytpu_count_v1.argtypes = [u8p, ctypes.c_uint64, u64p, u64p]
    lib.ytpu_decode_v1.restype = ctypes.c_int
    lib.ytpu_decode_v1.argtypes = [u8p, ctypes.c_uint64] + [i64p] * 19
    lib.ytpu_count_v2.restype = ctypes.c_int
    lib.ytpu_count_v2.argtypes = [u8p, ctypes.c_uint64, u64p, u64p]
    lib.ytpu_decode_v2.restype = ctypes.c_int
    lib.ytpu_decode_v2.argtypes = [u8p, ctypes.c_uint64] + [i64p] * 22
    lib.ytpu_validate_many.restype = None
    lib.ytpu_validate_many.argtypes = [
        ctypes.c_char_p, u64p, u8p, ctypes.c_uint64, i64p,
    ]
    lib.ytpu_encode_v1.restype = ctypes.c_int64
    lib.ytpu_encode_v1.argtypes = (
        [ctypes.POINTER(u8p), u64p, ctypes.c_uint64]      # bufs
        + [i64p] * 3 + [ctypes.c_uint64]                  # row groups
        + [i64p] * 18                                     # row columns
        + [u8p, ctypes.c_uint64]                          # strings blob
        + [i64p] * 3 + [ctypes.c_uint64] + [i64p] * 2     # ds groups
        + [u8p, ctypes.c_uint64]                          # out
    )
    # plan-core (plancore.cpp) entry points
    i64 = ctypes.c_int64
    u64 = ctypes.c_uint64
    vp = ctypes.c_void_p
    i32p = ctypes.POINTER(ctypes.c_int32)
    lib.ymx_new.restype = vp
    lib.ymx_free.argtypes = [vp]
    lib.ymx_add_buf.restype = i64
    lib.ymx_add_buf.argtypes = [vp, u8p, u64]
    lib.ymx_n_bufs.restype = i64
    lib.ymx_n_bufs.argtypes = [vp]
    lib.ymx_buf_len.restype = i64
    lib.ymx_buf_len.argtypes = [vp, i64]
    lib.ymx_prepare.restype = ctypes.c_int
    lib.ymx_prepare.argtypes = [vp, i64p, i64p, i64, i64p]
    vpp = ctypes.POINTER(vp)
    lib.ymx_prepare_many.restype = None
    lib.ymx_prepare_many.argtypes = [vpp, i64, i64p, i64p, i64p,
                                     ctypes.c_int, i64p, i64p,
                                     ctypes.POINTER(ctypes.c_double), i64p]
    for pack_name in ("ymx_pack_apply", "ymx_pack_apply16"):
        fn = getattr(lib, pack_name)
        fn.restype = None
        fn.argtypes = [vpp, i64p, i64, i64, i64, i64, i64, i64, i64,
                       ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
                       vp, i64p]
    for pack_name in ("ymx_pack_rows", "ymx_pack_rows16"):
        fn = getattr(lib, pack_name)
        fn.restype = i64
        fn.argtypes = [vpp, i64p, i64, i64, i64, ctypes.c_int32, vp, vp, vp]
    for name, args in [
        ("ymx_plan_splits", [vp, i64p]),
        ("ymx_plan_sched", [vp, i64p]),
        ("ymx_plan_deletes", [vp, i64p]),
        ("ymx_plan_applied_ds", [vp, i64p]),
        ("ymx_plan_links", [vp, i64p, i64p]),
        ("ymx_links", [vp, i64p]),
        ("ymx_heads", [vp, i64p]),
        ("ymx_plan_heads", [vp, i64p, i64p]),
        ("ymx_clients", [vp, i64p]),
        ("ymx_state", [vp, i64p]),
        ("ymx_segs", [vp, i64p, i64p, i64p, i64p, i64p]),
        ("ymx_strings", [vp, u8p]),
        ("ymx_chain", [vp, i64, i64p]),
        ("ymx_ds", [vp, i64p, i64p, i64p]),
    ]:
        getattr(lib, name).restype = None
        getattr(lib, name).argtypes = args
    lib.ymx_frag_counts.restype = None
    lib.ymx_frag_counts.argtypes = [vp, i64p]
    lib.ymx_frag.restype = None
    lib.ymx_frag.argtypes = [vp, i64, i64p, i64p]
    lib.ymx_drop_bufs_from.restype = None
    lib.ymx_drop_bufs_from.argtypes = [vp, i64]
    for name in ("ymx_n_rows", "ymx_n_slots", "ymx_n_segs",
                 "ymx_pending_depth", "ymx_ds_count"):
        getattr(lib, name).restype = i64
        getattr(lib, name).argtypes = [vp]
    lib.ymx_gen.restype = u64
    lib.ymx_gen.argtypes = [vp]
    lib.ymx_strings_len.restype = u64
    lib.ymx_strings_len.argtypes = [vp]
    lib.ymx_chain_len.restype = i64
    lib.ymx_chain_len.argtypes = [vp, i64]
    lib.ymx_has_pending.restype = ctypes.c_int
    lib.ymx_has_pending.argtypes = [vp]
    lib.ymx_rows.restype = None
    lib.ymx_rows.argtypes = [vp, i64] + [i64p] * 21
    lib.ymx_copy_bytes.restype = ctypes.c_int
    lib.ymx_copy_bytes.argtypes = [vp, i64, i64, i64, u8p]
    lib.ymx_encode_state_vector.restype = i64
    lib.ymx_encode_state_vector.argtypes = [vp, ctypes.c_char_p, u64]
    lib.ymx_encode_bound.restype = i64
    lib.ymx_encode_bound.argtypes = [vp]
    lib.ymx_encode_diff.restype = i64
    lib.ymx_encode_diff.argtypes = [vp, i64p, i64p, i64, i64p, i64,
                                    ctypes.c_int, u8p, u64]
    lib.ymx_encode_diff_v2.restype = i64
    lib.ymx_encode_diff_v2.argtypes = [vp, i64p, i64p, i64, i64p, i64,
                                       ctypes.c_int, u8p, u64]
    # one call a flush encodes every planned room's step update into the
    # calling thread's arena; ymx_plan_seq numbers a mirror's plans
    lib.ymx_encode_steps_many.restype = i64
    lib.ymx_encode_steps_many.argtypes = [vpp, i64] + [i64p] * 9
    lib.ymx_encode_arena.restype = vp
    lib.ymx_encode_arena.argtypes = []
    lib.ymx_plan_seq.restype = u64
    lib.ymx_plan_seq.argtypes = [vp]
    # the formatting clean-up after a remote transaction, for the room's
    # current plan: (client, clock, length) triples, -1 to fall back
    lib.ymx_format_cleanup.restype = i64
    lib.ymx_format_cleanup.argtypes = [vp, i64, i64p, i64, i64p]
    lib.ymx_compact_self.restype = i64
    lib.ymx_compact_self.argtypes = [vp, ctypes.c_int, i32p, u8p, i32p, i64]
    # the question before it, a look's candidates in one call: a byte a
    # room, 1 where the compaction would change something
    lib.ymx_compact_changes_many.restype = None
    lib.ymx_compact_changes_many.argtypes = [vpp, i64, ctypes.c_int, u8p]
    # the most threads a ymx_prepare_many call may plan on (the width a
    # flush used is last_flush_metrics["plan_threads"], the call's own)
    lib.ymx_plan_threads.restype = ctypes.c_int
    lib.ymx_plan_threads.argtypes = []
    # one ctypes crossing registers every staged buffer of a flush
    lib.ymx_add_bufs_many.restype = None
    lib.ymx_add_bufs_many.argtypes = [
        vpp, ctypes.POINTER(ctypes.c_char_p), u64p, i64, i64p,
    ]
    # deep state clone: the frontier-keyed plan cache replays a cached
    # post-prepare mirror state onto another doc's handle
    lib.ymx_clone_state.restype = i64
    lib.ymx_clone_state.argtypes = [vp, vp]
    # emit_row chain-run anchor adoption: Python diffs the hit/lookup
    # totals around each flush for the shared metrics schema
    lib.ymx_plan_segment_stats.restype = None
    lib.ymx_plan_segment_stats.argtypes = [i64p]
    _lib = lib
    return _lib


# content-source kinds for ytpu_encode_v1 (must match transcode.cpp)
SRC_NONE, SRC_DELETED, SRC_FRAMED, SRC_UTF8, SRC_SPILL = 0, 1, 2, 3, 4
# element-range kinds emitted by the native plan builder (plancore.cpp):
# `length` elements at [ofs,end) — ContentAny any-values / ContentJSON
# var_strings; SRC_V2LAZY marks V2-framed embed/format/type payloads that
# must be re-framed via the Python spill path when writing V1
SRC_ANYS, SRC_JSONS, SRC_V2LAZY = 5, 6, 7


def encode_v1_update(
    bufs: list[bytes],
    group_client, group_start, group_len,
    row_cols: dict,
    strings: bytes,
    ds_group_client, ds_group_start, ds_group_len,
    ds_clock, ds_len,
    out_cap: int,
) -> bytes:
    """Assemble a V1 update natively from pre-marshalled columns.  All
    array arguments are int64 numpy arrays; ``row_cols`` holds the 18
    per-row columns in ABI order.  Raises NativeDecodeError when the
    library is unavailable or encoding fails (caller falls back to the
    Python encoder)."""
    lib = load()
    if lib is None:
        raise NativeDecodeError("native transcoder unavailable")
    u8p = ctypes.POINTER(ctypes.c_uint8)
    n_bufs = len(bufs)
    buf_arrs = [np.frombuffer(b, dtype=np.uint8) for b in bufs]
    buf_ptrs = (u8p * max(1, n_bufs))(
        *(a.ctypes.data_as(u8p) for a in buf_arrs)
    )
    buf_lens = np.asarray([len(b) for b in bufs], np.uint64)
    strings_a = np.frombuffer(strings, dtype=np.uint8) if strings else np.zeros(1, np.uint8)
    out = np.empty(out_cap, np.uint8)
    row_order = (
        "clock", "length", "offset",
        "origin_client", "origin_clock", "right_client", "right_clock",
        "content_ref", "name_ofs", "name_len", "sub_ofs", "sub_len",
        "parent_client", "parent_clock",
        "src_kind", "src_buf", "src_ofs", "src_end",
    )
    # materialize every array first: the ctypes pointers do not keep their
    # backing buffers alive
    keep = (
        [np.ascontiguousarray(a, np.int64)
         for a in (group_client, group_start, group_len)]
        + [np.ascontiguousarray(row_cols[k], np.int64) for k in row_order]
        + [np.ascontiguousarray(a, np.int64)
           for a in (ds_group_client, ds_group_start, ds_group_len,
                     ds_clock, ds_len)]
    )
    i64ptr = lambda a: a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
    rc = lib.ytpu_encode_v1(
        buf_ptrs,
        buf_lens.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        n_bufs,
        i64ptr(keep[0]), i64ptr(keep[1]), i64ptr(keep[2]),
        len(keep[0]),
        *(i64ptr(a) for a in keep[3:21]),
        strings_a.ctypes.data_as(u8p), len(strings),
        i64ptr(keep[21]), i64ptr(keep[22]), i64ptr(keep[23]),
        len(keep[21]),
        i64ptr(keep[24]), i64ptr(keep[25]),
        out.ctypes.data_as(u8p), out_cap,
    )
    if rc < 0:
        raise NativeDecodeError(f"native encode failed: {rc}")
    return out[:rc].tobytes()


class NativeDecodeError(Exception):
    pass


def decode_v1_columns(update: bytes):
    """Decode a V1 update into int64 column arrays via the native scanner.

    Returns (structs: dict[str, np.ndarray], ds: dict[str, np.ndarray]).
    Raises NativeDecodeError if the library is unavailable or parsing fails
    (caller falls back to the Python decoder).
    """
    lib = load()
    if lib is None:
        raise NativeDecodeError("native transcoder unavailable")
    buf = np.frombuffer(update, dtype=np.uint8)
    bp = buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
    n_structs = ctypes.c_uint64()
    n_ds = ctypes.c_uint64()
    rc = lib.ytpu_count_v1(bp, len(update), ctypes.byref(n_structs), ctypes.byref(n_ds))
    if rc != 0:
        raise NativeDecodeError(f"count pass failed: {rc}")
    ns, nd = n_structs.value, n_ds.value
    cols = {
        k: np.empty(ns, np.int64)
        for k in (
            "client", "clock", "length",
            "origin_client", "origin_clock", "right_client", "right_clock",
            "info", "parent_name_ofs", "parent_name_len",
            "parent_id_client", "parent_id_clock",
            "parent_sub_ofs", "parent_sub_len", "content_ofs", "content_end",
        )
    }
    ds = {k: np.empty(nd, np.int64) for k in ("client", "clock", "len")}
    ptr = lambda a: a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
    rc = lib.ytpu_decode_v1(
        bp, len(update),
        ptr(cols["client"]), ptr(cols["clock"]), ptr(cols["length"]),
        ptr(cols["origin_client"]), ptr(cols["origin_clock"]),
        ptr(cols["right_client"]), ptr(cols["right_clock"]),
        ptr(cols["info"]),
        ptr(cols["parent_name_ofs"]), ptr(cols["parent_name_len"]),
        ptr(cols["parent_id_client"]), ptr(cols["parent_id_clock"]),
        ptr(cols["parent_sub_ofs"]), ptr(cols["parent_sub_len"]),
        ptr(cols["content_ofs"]), ptr(cols["content_end"]),
        ptr(ds["client"]), ptr(ds["clock"]), ptr(ds["len"]),
    )
    if rc != 0:
        raise NativeDecodeError(f"decode pass failed: {rc}")
    return cols, ds


_V2_COLS = (
    "client", "clock", "length",
    "origin_client", "origin_clock", "right_client", "right_clock",
    "info", "parent_name_ofs", "parent_name_len",
    "parent_id_client", "parent_id_clock",
    "parent_sub_ofs", "parent_sub_len",
    "content_ofs", "content_end", "content_ofs2", "content_end2",
    "content_count",
)


def decode_v2_columns(update: bytes):
    """Decode a V2 columnar update (the 9-stream container, reference
    UpdateDecoder.js:270-293) into int64 column arrays via the native
    scanner.  String contents stay lazy as byte ranges into the in-buffer
    UTF-8 arena; rest-stream payloads (binary/embed/any) as self-delimiting
    byte ranges.  Raises NativeDecodeError when unavailable, on malformed
    input, or on legacy ContentJSON / subdoc ContentDoc payloads (caller
    falls back to the Python decoder)."""
    lib = load()
    if lib is None:
        raise NativeDecodeError("native transcoder unavailable")
    buf = np.frombuffer(update, dtype=np.uint8)
    bp = buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
    n_structs = ctypes.c_uint64()
    n_ds = ctypes.c_uint64()
    rc = lib.ytpu_count_v2(bp, len(update), ctypes.byref(n_structs), ctypes.byref(n_ds))
    if rc != 0:
        raise NativeDecodeError(f"v2 count pass failed: {rc}")
    ns, nd = n_structs.value, n_ds.value
    cols = {k: np.empty(ns, np.int64) for k in _V2_COLS}
    ds = {k: np.empty(nd, np.int64) for k in ("client", "clock", "len")}
    ptr = lambda a: a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
    rc = lib.ytpu_decode_v2(
        bp, len(update),
        *(ptr(cols[k]) for k in _V2_COLS),
        ptr(ds["client"]), ptr(ds["clock"]), ptr(ds["len"]),
    )
    if rc != 0:
        raise NativeDecodeError(f"v2 decode pass failed: {rc}")
    return cols, ds


def validate_many(updates: list[bytes], v2s) -> np.ndarray | None:
    """The count pass over every update in one native call: a row
    ``[rc, structs, ds_ranges, clients]`` an update, ``rc`` 0 where the
    structural walk accepts it (``transcode.cpp`` ``ytpu_validate_many``
    gives the other codes).  None when the library is unavailable."""
    lib = load()
    if lib is None:
        return None
    n = len(updates)
    ofs = np.zeros(n + 1, np.uint64)
    np.cumsum(np.fromiter(map(len, updates), np.uint64, n), out=ofs[1:])
    flags = np.fromiter(v2s, np.uint8, n)
    out = np.empty((n, 4), np.int64)
    lib.ytpu_validate_many(
        b"".join(updates),
        ofs.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        flags.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        n,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
    )
    return out
