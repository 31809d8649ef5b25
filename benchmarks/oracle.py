"""The comparison that decides ``correct``.

After the window every room that took traffic is held to a statement of
what it must hold, state vector and text, that the provider had no part
in: what the generator's own plain clients hold (``views``), or, for a
room that was sent its committed trace and nothing else, the trace's
entry in ``base_states.json``.  A seeded sample of those rooms, every
big room and a sample of the rooms that took no traffic are besides
replayed on a CPU ``Y.Doc`` fed the room's trace and the updates the
benchmark SENT, and held against that: state vector, host text,
canonical encoded state; the cheap statement of a replayed room has to
agree with its replay.  What the chip holds is compared twice: the
device's rows of every room that took traffic (right links, deleted
flags, list heads) with the host mirror's, in one read-back, and for a
small sample the text walked out of the device's rows (~40 ms a room at
this table width) with the oracle's.  The write-ahead log is read by a
reader of its own (the record format is
``yjs_tpu/persistence/records.py``'s, restated here): every acknowledged
update must be there, per room, in order.  What the provider broadcast
is applied to a listener ``Y.Doc`` per room, which must end where the
oracle did.  Every number compared is printed beside its limit; every
limit is 0: these are exact comparisons.
"""

from __future__ import annotations

import hashlib
import json
import random
import struct
import zlib
from pathlib import Path

import numpy as np


def text_digest(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:24]


def _base_states() -> dict[str, list[tuple[dict[int, int], str]]]:
    """Per kind and trace: the state vector and the text's digest of a
    room that holds the committed trace and nothing else, as a CPU
    ``Y.Doc`` replayed it when the table was made (``python
    benchmarks/oracle.py`` makes it anew; every run checks the entries
    of the rooms it replays)."""
    table = json.loads(
        (Path(__file__).resolve().parent / "base_states.json").read_text()
    )
    return {
        kind: [({int(c): n for c, n in sv}, digest) for sv, digest in entries]
        for kind, entries in table.items()
        if kind != "what"
    }


BASE_STATES = _base_states()
# elements of a trace: the sum of its state vector's clocks, every
# character ever typed there, deleted or not
ELEMENTS = {
    kind: [sum(sv.values()) for sv, _digest in entries]
    for kind, entries in BASE_STATES.items()
}

_HDR = struct.Struct("<2sBBHII")
_MAGIC = b"\xa1\x7e"
_KIND_UPDATE, _KIND_RELEASE = 1, 4


def read_wal(wal_dir) -> dict[str, list[list[bytes]]]:
    """Per room, its lives in log order (a release record ends one), each
    the update payloads journaled in it.  A record that fails its CRC,
    or a file that does not parse to its end, raises."""
    rooms: dict[str, list[list[bytes]]] = {}
    for path in sorted(Path(wal_dir).glob("wal-*.log")):
        data = path.read_bytes()
        if data[:8] != b"YTPUWAL1":
            raise ValueError(f"{path}: not a WAL segment")
        pos = 8
        while pos < len(data):
            magic, kind, flags, glen, plen, crc = _HDR.unpack_from(data, pos)
            body = pos + _HDR.size
            end = body + glen + plen
            if magic != _MAGIC or end > len(data):
                raise ValueError(f"{path}: bad record at {pos}")
            want = zlib.crc32(data[pos + 2 : pos + 10])
            want = zlib.crc32(data[body:end], want)
            if want != crc:
                raise ValueError(f"{path}: CRC mismatch at {pos}")
            guid = data[body : body + glen].decode("utf-8")
            if kind == _KIND_UPDATE:
                rooms.setdefault(guid, [[]])[-1].append(data[body + glen : end])
            elif kind == _KIND_RELEASE:
                rooms.setdefault(guid, [[]]).append([])
            pos = end
    return rooms


def _missing(expected: list[bytes], found: list[bytes]) -> int:
    """How many of ``expected`` are not in ``found`` (as multisets)."""
    left: dict[bytes, int] = {}
    for u in found:
        left[u] = left.get(u, 0) + 1
    n = 0
    for u in expected:
        if left.get(u, 0) > 0:
            left[u] -= 1
        else:
            n += 1
    return n


def items_of(doc, root: str = "text"):
    """``(client, clock, string, deleted)`` per item of a replayed
    document's text, in document order."""
    item = doc.get_text(root)._start
    while item is not None:
        yield (
            item.id.client, item.id.clock,
            getattr(item.content, "str", None) or "\0" * item.length,
            item.deleted,
        )
        item = item.right


class Want:
    """What a room must hold: state vector and text now, the canonical
    encoded state when it is asked for (merging costs ~20 ms a room)."""

    def __init__(self, doc):
        import yjs_tpu as Y

        self.sv = Y.decode_state_vector(Y.encode_state_vector(doc))
        self.text = doc.get_text("text").to_string()
        self.doc = doc
        self._canonical = None

    @property
    def canonical(self) -> bytes:
        import yjs_tpu as Y

        if self._canonical is None:
            self._canonical = Y.merge_updates(
                [Y.encode_state_as_update(self.doc)]
            )
        return self._canonical


class Oracle:
    """CPU ``Y.Doc`` states; rooms that hold one trace and nothing else
    share one."""

    def __init__(self):
        self._of_base: dict[tuple[str, int], Want] = {}

    @staticmethod
    def replay(updates: list[bytes]):
        import yjs_tpu as Y

        doc = Y.Doc(gc=False)
        for u in updates:
            Y.apply_update(doc, u)
        return doc

    def state(self, room, history: list[bytes]) -> Want:
        """What a room that received ``history`` (its trace first) holds."""
        key = (room.kind, room.trace)
        shared = len(history) == 1 and history[0] is room.base
        if shared and key in self._of_base:
            return self._of_base[key]
        want = Want(self.replay(history))
        if shared:
            self._of_base[key] = want
        return want


def evenly_sharded(eng, mesh_devices: int) -> int:
    """Tables not split evenly over the mesh's devices."""
    bad = 0
    for table in (eng._right, eng._deleted, eng._starts):
        shards = table.addressable_shards
        if len({s.device for s in shards}) != mesh_devices or any(
            s.data.nbytes * mesh_devices != table.nbytes for s in shards
        ):
            bad += 1
    return bad




SAMPLE = {
    # rooms replayed on a CPU Y.Doc after the window, drawn from the seed
    "touched": 96,        # of the rooms that took traffic (~25 ms a room)
    "others": 16,         # of the rooms that took none
    "state": 12,          # of those, canonical encoded state (~0.1 s a room)
    "device_text": 16,    # of those, text walked out of the device's rows
    "listeners": 32,      # of the touched, what their peers were sent
}


def device_rows_differ(prov, guids: list[str]) -> int:
    """Rooms whose rows on the device (right links, deleted flags, list
    heads) are not the host mirror's.  One gather and one read-back for
    all of them, to a width that holds the longest; a room that is not
    resident counts as differing."""
    eng = prov.engine
    rooms = [
        (prov.doc_id(guid), eng.mirrors[prov.doc_id(guid)])
        for guid in guids if prov.has_doc(guid)
    ]
    bad = len(guids) - len(rooms)
    if not rooms or eng._right is None:
        return bad + len(rooms)
    rows = max(m.n_rows for _d, m in rooms)
    segs = max(m.n_segs for _d, m in rooms)
    # a power of two, so that few programs serve every run
    width = min(1 << max(rows, 1).bit_length(), eng._right.shape[1])
    docs = np.asarray([d for d, _m in rooms])
    right = np.asarray(eng._right[docs, :width])
    deleted = np.asarray(eng._deleted[docs, :width])
    starts = np.asarray(eng._starts[docs, : min(segs, eng._starts.shape[1])])
    for k, (_d, m) in enumerate(rooms):
        n = m.n_rows
        dead = np.zeros(n, bool)
        dead[list(m._host_deleted_rows)] = True
        heads = np.asarray(m.head_of_seg)
        if not (
            np.array_equal(right[k, :n], np.asarray(m.list_next[:n]))
            and np.array_equal(deleted[k, :n], dead)
            and np.array_equal(starts[k, : len(heads)], heads)
        ):
            bad += 1
    return bad


def check(cell, views: dict | None = None, sample: dict = SAMPLE) -> dict[str, int]:
    """Every compared number, by name.  ``cell`` gives the provider, the
    plan, ``history[guid]`` (the updates acknowledged to each room since
    its last release, its trace first; ``past[guid]`` holds its earlier
    lives), ``broadcasts[guid]`` (what ``on_update`` delivered since
    then), ``left[guid]`` (the state vector each earlier life held when
    the room was released) and ``listener_base[guid]``; ``views[guid]`` is the state
    vector and text the generator's own clients hold of a room.
    Every room that took traffic is compared, and every room's journal;
    the replays are of the rooms of ``sample``, the biggest rooms always
    among them."""
    import yjs_tpu as Y

    views = views or {}
    prov, eng, oracle = cell.prov, cell.prov.engine, cell.oracle
    rng = random.Random(f"check:{cell.seed}")
    by_guid = {r.guid: r for r in cell.plan}
    touched = sorted(cell.touched)
    replayed = rng.sample(touched, min(sample["touched"], len(touched)))
    big = {}
    for r in cell.plan:  # one oracle a kind: the b4 rooms hold one trace
        if r.kind in ("b4", "prepend") and r.guid not in cell.touched:
            big.setdefault(r.kind, []).append(r.guid)
    rest = [
        r.guid for r in cell.plan
        if r.guid not in cell.touched and r.kind in ("distinct", "storm")
    ]
    others = rng.sample(rest, min(sample["others"], len(rest)))
    big_all = [g for guids in big.values() for g in guids]
    big_one = [guids[0] for guids in big.values()]
    n = dict.fromkeys((
        "rooms_state_vector_differs", "rooms_state_differs",
        "rooms_host_text_differs", "rooms_device_rows_differ",
        "rooms_device_text_differs", "rooms_statement_differs",
        "acknowledged_not_in_wal", "rooms_wal_differs",
        "acknowledged_not_broadcast",
    ), 0)
    took = {}
    clock = cell.clock
    t = clock()
    want = {
        guid: oracle.state(by_guid[guid], cell.history[guid])
        for guid in replayed + big_all + others
    }
    # what each compared room must hold: state vector, digest of its text
    expect: dict[str, tuple[dict, str]] = {}
    for guid in touched:
        room, history = by_guid[guid], cell.history[guid]
        if guid in views:
            sv, text = views[guid]
            expect[guid] = (sv, text_digest(text))
        elif (
            len(history) == 1 and history[0] is room.base
            and room.kind in BASE_STATES
        ):
            expect[guid] = BASE_STATES[room.kind][room.trace]
        elif guid not in want:
            want[guid] = oracle.state(room, history)
    for guid, w in want.items():
        # a replayed room's cheap statements have to agree with its replay
        # (the table is also what a reload's work is counted from)
        replay = (w.sv, text_digest(w.text))
        stated = [expect.setdefault(guid, replay)]
        room = by_guid[guid]
        if len(cell.history[guid]) == 1 and room.kind in BASE_STATES:
            stated.append(BASE_STATES[room.kind][room.trace])
        if any(s != replay for s in stated):
            n["rooms_statement_differs"] += 1
    took["oracle"] = clock() - t
    t = clock()
    eng.export_from_device = False
    for guid, (sv, digest) in expect.items():
        if not prov.has_doc(guid) or prov.state_vector(guid) != sv:
            n["rooms_state_vector_differs"] += 1
        if not prov.has_doc(guid) or text_digest(prov.text(guid)) != digest:
            n["rooms_host_text_differs"] += 1
    took["state vector and host text"] = clock() - t
    t = clock()
    for guid in replayed[: sample["state"]] + big_one + others[:4]:
        have = Y.merge_updates([prov.encode_state_as_update(guid)])
        if have != want[guid].canonical:
            n["rooms_state_differs"] += 1
    took["encoded state"] = clock() - t
    t = clock()
    n["rooms_device_rows_differ"] = device_rows_differ(prov, touched + others)
    took["device rows"] = clock() - t
    t = clock()
    eng.export_from_device = True
    for guid in replayed[: sample["device_text"]] + big_all + others[:4]:
        if prov.text(guid) != want[guid].text:
            n["rooms_device_text_differs"] += 1
    eng.export_from_device = False
    took["device text"] = clock() - t
    t = clock()

    journal = read_wal(cell.wal_dir)
    for guid, history in cell.history.items():
        lives = cell.past.get(guid, []) + [history]
        found = journal.get(guid, [])
        if found != lives:
            n["rooms_wal_differs"] += 1
            n["acknowledged_not_in_wal"] += _missing(
                [u for life in lives for u in life],
                [u for life in found for u in life],
            )

    took["journal"] = clock() - t
    t = clock()
    # what the room's peers were sent: a listener that holds the room as
    # it stood when listening began, plus every broadcast since
    for guid in replayed[: sample["listeners"]]:
        listener = oracle.replay(
            cell.listener_base[guid] + cell.broadcasts.get(guid, [])
        )
        have = Y.decode_state_vector(Y.encode_state_vector(listener))
        short = sum(
            max(0, upto - have.get(client, 0))
            for client, upto in want[guid].sv.items()
        )
        if short or listener.get_text("text").to_string() != want[guid].text:
            n["acknowledged_not_broadcast"] += max(1, short)

    # the lives that ended in the window (a room released and loaded
    # again): the state vector each held when it was let go
    lives: dict[tuple, dict] = {}
    n["lives_state_vector_differs"] = 0
    for guid, past in cell.past.items():
        room = by_guid[guid]
        for history, sv in zip(past, cell.left[guid]):
            if len(history) == 1 and history[0] is room.base and (
                room.kind in BASE_STATES
            ):
                held = BASE_STATES[room.kind][room.trace][0]
            else:
                key = (guid, *map(id, history))
                if key not in lives:
                    lives[key] = oracle.state(room, history).sv
                held = lives[key]
            if sv != held:
                n["lives_state_vector_differs"] += 1
    n["rooms_flushed_and_not_broadcast"] = cell.unheard
    n["rooms_missing_at_release"] = cell.missing_at_release
    n["fallback_docs"] = len(eng.fallback)
    n["demotions"] = len(eng.demotions)
    n["rollbacks"] = len(eng.rollbacks)
    n["dead_letters"] = len(eng.dead_letters)
    n["refused_updates"] = len(cell.refused)
    if cell.cfg["mesh_devices"]:
        n["tables_unevenly_sharded"] = evenly_sharded(
            eng, cell.cfg["mesh_devices"]
        )
    took["listeners"] = clock() - t
    cell.log(
        f"compared {len(expect)} rooms ({len(touched)} that took traffic, "
        f"{len(want)} replayed on the oracle), {len(cell.history)} rooms' "
        f"journals; seconds { {k: round(v, 2) for k, v in took.items()} }"
    )
    return n


def make_base_states() -> dict:
    """The table of base states, from the committed traces."""
    from benchmarks.deployment import load_traces

    table = {
        "what": "state vector and the first 24 hex digits of the SHA-256 of "
        "the text of a room that holds one committed trace and nothing else, "
        "per kind and trace, as a CPU Y.Doc replays it; made by `python "
        "benchmarks/oracle.py`; every run checks the entries of the rooms it "
        "replays, and tests/bench/test_harness_cpu.py recounts them all",
    }
    for kind in ("distinct", "storm"):
        table[kind] = []
        for trace in load_traces(f"{kind}_traces"):
            w = Want(Oracle.replay([trace]))
            table[kind].append([sorted(w.sv.items()), text_digest(w.text)])
    return table


if __name__ == "__main__":
    import sys

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    (Path(__file__).resolve().parent / "base_states.json").write_text(
        json.dumps(make_base_states(), separators=(",", ":")) + "\n"
    )
