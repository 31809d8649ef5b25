"""A plain reader of a crashed write-ahead log: the reference of
``yws-crash``.

The format is the one the README states ("Durability"): a segment file
``wal-<8 digits>.log`` begins with the eight bytes ``YTPUWAL1``; a
record is a 14-byte little-endian header (magic ``a1 7e``, kind u8,
flags u8, guid length u16, payload length u32, CRC-32 u32 over the ten
bytes from kind to payload length, then the guid, then the payload),
the guid in UTF-8 and the payload.  Kind 1 is an acknowledged update,
kind 4 a room's release.

A process that is killed leaves at most one damaged record, the last of
the last segment: the write the kill cut short.  :func:`read_crashed`
reads a directory a room at a time and a record at a time, stops at the
first record of the last segment that is short or fails its CRC, and
raises :class:`DamagedLog` on damage anywhere else, on a checkpoint
file (the deployment writes none) and on a record kind a y-websocket
shaped server does not write.  What it gives per room is the payloads
that are whole on disk, in the log's order; :func:`replay` feeds them to
a CPU ``Y.Doc`` one by one, and that is the state vector and text a
recovered room must hold.

Nothing here imports ``yjs_tpu.persistence``.  :func:`record` writes one
record, for the benchmark's own crashed copies (the bytes of a write a
kill cut short are the first half of one).
"""

from __future__ import annotations

import struct
import zlib
from pathlib import Path
from typing import NamedTuple

SEGMENT_HEADER = b"YTPUWAL1"
MAGIC = b"\xa1\x7e"
HEADER = struct.Struct("<2sBBHII")
KIND_UPDATE, KIND_RELEASE = 1, 4


class DamagedLog(ValueError):
    """The directory is not what a killed process leaves behind."""


class Crashed(NamedTuple):
    rooms: dict[str, list[bytes]]  # guid -> payloads whole on disk, in order
    records: int                   # whole records read, of every kind
    torn_at: tuple[Path, int] | None  # the last segment's first bad byte
    files: int
    bytes: int


def record(kind: int, guid: str, payload: bytes) -> bytes:
    """One record's bytes (a v1 update: flags 0)."""
    g = guid.encode("utf-8")
    body = struct.pack("<BBHI", kind, 0, len(g), len(payload))
    crc = zlib.crc32(payload, zlib.crc32(g, zlib.crc32(body)))
    return HEADER.pack(MAGIC, kind, 0, len(g), len(payload), crc) + g + payload


def _record_at(data: bytes, pos: int):
    """``(kind, guid, payload, end)`` of the record at ``pos``, or None
    where it is short, is no record or fails its CRC."""
    if len(data) - pos < HEADER.size:
        return None
    magic, kind, flags, glen, plen, crc = HEADER.unpack_from(data, pos)
    body = pos + HEADER.size
    end = body + glen + plen
    if magic != MAGIC or end > len(data):
        return None
    if zlib.crc32(data[body:end], zlib.crc32(data[pos + 2 : pos + 10])) != crc:
        return None
    return kind, data[body : body + glen].decode("utf-8"), data[body + glen : end], end


def read_crashed(wal_dir) -> Crashed:
    wal_dir = Path(wal_dir)
    if any(wal_dir.glob("checkpoint-*")):
        raise DamagedLog(f"{wal_dir}: holds a checkpoint file")
    segments = sorted(wal_dir.glob("wal-*.log"))
    rooms: dict[str, list[bytes]] = {}
    records, torn_at, size = 0, None, 0
    for path in segments:
        last = path == segments[-1]
        data = path.read_bytes()
        size += len(data)
        if data[:8] != SEGMENT_HEADER:
            if last and len(data) < 8:  # killed while the file was made
                torn_at = (path, 0)
                continue
            raise DamagedLog(f"{path}: not a segment")
        pos = 8
        while pos < len(data):
            rec = _record_at(data, pos)
            if rec is None:
                if not last:
                    raise DamagedLog(f"{path}: damaged at byte {pos}")
                torn_at = (path, pos)
                break
            kind, guid, payload, pos = rec
            records += 1
            if kind == KIND_UPDATE:
                rooms.setdefault(guid, []).append(payload)
            elif kind == KIND_RELEASE:
                rooms.pop(guid, None)
            else:
                raise DamagedLog(f"{path}: a record of kind {kind}")
    return Crashed(rooms, records, torn_at, len(segments), size)


def replay(payloads: list[bytes]) -> tuple[dict[int, int], str]:
    """State vector and text of a CPU ``Y.Doc`` fed ``payloads`` in
    order, one ``apply_update`` each."""
    import yjs_tpu as Y

    doc = Y.Doc(gc=False)
    for update in payloads:
        Y.apply_update(doc, update)
    return (
        Y.decode_state_vector(Y.encode_state_vector(doc)),
        doc.get_text("text").to_string(),
    )
