"""A plain Yjs client that worked offline: the writers of ``offline``.

A writer holds one room's sequence as it left the server (every element
ever inserted, in document order, with its id and whether it has been
deleted since) and goes on working without a connection: inserts and
deletes at positions of what IT sees, a position being an index among
the live elements, as ``Y.Text.insert`` / ``Y.Array.insert`` count them.
Nobody else's work reaches it.  When it comes back it answers the
server's step 1 with ONE update, :meth:`Writer.update`: the v1 update
``encodeStateAsUpdate(doc, sv)`` gives such a client, its own structs
from clock 0 as a default ``Y.Doc`` (``gc: true``) keeps them, and the
delete set of everything it holds deleted, the tombstones it left with
included.

What a Yjs client merges is merged here: two elements of consecutive
clocks are one struct where the second was inserted straight behind the
first, is still its right neighbour, names the same right origin and
shares its fate (both live or both deleted).  A deleted run is written
as ``ContentDeleted`` (the client has collected its content), a live one
as ``ContentString`` (``kind`` text) or ``ContentAny`` (``kind`` array:
whole numbers).  A struct names its left and right neighbour at the
time it was inserted; the right part of a struct that was split names
the element before it and the whole struct's right origin.

Nothing here imports the program.  ``tests/bench/test_plain_offline.py``
holds the updates to a ``Y.Doc`` doing the same operations.

A wave's sessions are typed between timed intervals, and a window holds
the more waves the shorter that takes: :class:`Pool` deals the sessions
(:func:`history`: one task, one session) to processes of this file's own
(``python benchmarks/plain_offline.py --worker``: pickled task lists on
standard input, results on standard output), which sleep on their pipe
while an interval is timed.  A session's operations come from the seed
its task carries, so the same tasks give the same updates at any number
of processes, none included.
"""

from __future__ import annotations

import pickle
import random
import struct
import subprocess
import sys

_CLOCK_BITS = 32
_CLOCK_MASK = (1 << _CLOCK_BITS) - 1
_NONE = -1


def varuint(n: int) -> bytes:
    out = bytearray()
    while n > 0x7F:
        out.append(0x80 | (n & 0x7F))
        n >>= 7
    out.append(n)
    return bytes(out)


def varint(n: int) -> bytes:
    """lib0 ``writeVarInt``: six bits and the sign in the first byte."""
    sign, n = (0x40, -n) if n < 0 else (0, n)
    out = bytearray([(0x80 if n > 63 else 0) | sign | (n & 63)])
    n >>= 6
    while n > 0:
        out.append((0x80 if n > 127 else 0) | (n & 127))
        n >>= 7
    return bytes(out)


def _id(packed: int) -> bytes:
    return varuint(packed >> _CLOCK_BITS) + varuint(packed & _CLOCK_MASK)


def pack(client: int, clock: int) -> int:
    return (client << _CLOCK_BITS) | clock


class Writer:
    """One client's copy of one room and what it did to it offline.

    ``base_ids`` / ``base_dead``: the packed id and the tombstone flag
    of every element the room held when the writer left, in document
    order.  Elements are numbered: the base's in that order, the
    writer's own after them by clock."""

    def __init__(
        self, client: int, base_ids, base_dead, root: str = "text",
        kind: str = "text",
    ):
        if kind not in ("text", "array"):
            raise ValueError(f"kind {kind!r}")
        self.client, self.kind, self.root = client, kind, root.encode()
        self.n_base = nb = len(base_ids)
        self.base_ids = base_ids
        self.dead = bytearray(base_dead)
        # the full sequence as a linked list, the live elements as a list
        self.next = list(range(1, nb)) + [_NONE] if nb else []
        self.head = 0 if nb else _NONE
        dead = self.dead
        self.live = [e for e in range(nb) if not dead[e]]
        # per own element, by clock: what it holds, the element before
        # it when it was inserted and the right origin of its insert
        self.content: list = []
        self.origin: list[int] = []
        self.right: list[int] = []
        self.clock = 0
        self.n_structs = 0  # of the last update written

    # -- what the writer does ------------------------------------------------

    def __len__(self) -> int:
        return len(self.live)

    def insert(self, index: int, content) -> None:
        """``content`` (a string; for an array a list of whole numbers)
        after the ``index``-th live element."""
        live, nxt, dead = self.live, self.next, self.dead
        left = live[index - 1] if index > 0 else _NONE
        right = nxt[left] if left != _NONE else self.head
        if self.kind == "text":
            # Y.Text steps over what is deleted to the right of the place
            while right != _NONE and dead[right]:
                left, right = right, nxt[right]
        n = len(content)
        first = self.n_base + self.clock
        last = first + n - 1
        nxt.extend(range(first + 1, last + 1))
        nxt.append(right)
        if left != _NONE:
            nxt[left] = first
        else:
            self.head = first
        dead.extend(bytes(n))
        live[index:index] = range(first, last + 1)
        self.content.extend(content)
        self.origin.append(left)
        self.origin.extend(range(first, last))
        self.right.extend([right] * n)
        self.clock += n

    def delete(self, index: int, n: int) -> None:
        """The ``n`` live elements after the ``index``-th."""
        doomed = self.live[index : index + n]
        del self.live[index : index + n]
        dead = self.dead
        for e in doomed:
            dead[e] = 1

    # -- what it brings back ---------------------------------------------------

    def _id_of(self, e: int) -> int:
        if e < self.n_base:
            return self.base_ids[e]
        return pack(self.client, e - self.n_base)

    def structs(self) -> list[tuple[int, int]]:
        """The writer's own structs as ``(first clock, length)``."""
        nb, nxt, dead = self.n_base, self.next, self.dead
        origin, right = self.origin, self.right
        out, start = [], 0
        for c in range(1, self.clock):
            e = nb + c
            if not (
                origin[c] == e - 1 and nxt[e - 1] == e
                and right[c] == right[c - 1] and dead[e] == dead[e - 1]
            ):
                out.append((start, c - start))
                start = c
        if self.clock:
            out.append((start, self.clock - start))
        return out

    def delete_set(self) -> dict[int, list[tuple[int, int]]]:
        """Everything the writer holds deleted, ``(clock, length)`` runs
        by client: clients in the order they first appear in the
        sequence (the base's before its own), runs by clock."""
        by_client: dict[int, list[int]] = {}
        dead = self.dead
        for e in range(self.n_base):
            if dead[e]:
                packed = self.base_ids[e]
                by_client.setdefault(packed >> _CLOCK_BITS, []).append(
                    packed & _CLOCK_MASK
                )
        own = [c for c in range(self.clock) if dead[self.n_base + c]]
        if own:
            by_client.setdefault(self.client, []).extend(own)
        out = {}
        for client, clocks in by_client.items():
            clocks.sort()
            runs, start, prev = [], clocks[0], clocks[0]
            for c in clocks[1:]:
                if c != prev + 1:
                    runs.append((start, prev - start + 1))
                    start = c
                prev = c
            runs.append((start, prev - start + 1))
            out[client] = runs
        return out

    def update(self) -> bytes:
        """``encodeStateAsUpdate(doc, sv)`` for the state vector the
        writer left with: its own structs and its whole delete set."""
        nb, dead, content = self.n_base, self.dead, self.content
        structs = self.structs()
        self.n_structs = len(structs)
        out = [varuint(1 if structs else 0)]
        if structs:
            out.append(varuint(len(structs)) + varuint(self.client) + b"\x00")
        text = self.kind == "text"
        for start, n in structs:
            e = nb + start
            left, right = self.origin[start], self.right[start]
            gone = dead[e]
            ref = 1 if gone else 4 if text else 8
            info = ref | (0x80 if left != _NONE else 0) | (
                0x40 if right != _NONE else 0
            )
            out.append(bytes([info]))
            if left != _NONE:
                out.append(_id(self._id_of(left)))
            if right != _NONE:
                out.append(_id(self._id_of(right)))
            if left == _NONE and right == _NONE:
                # the parent: the root type, by its name
                out.append(b"\x01" + varuint(len(self.root)) + self.root)
            if gone:
                out.append(varuint(n))
            elif text:
                s = "".join(content[start : start + n]).encode()
                out.append(varuint(len(s)) + s)
            else:
                out.append(varuint(n) + b"".join(
                    b"\x7d" + varint(v) for v in content[start : start + n]
                ))
        ds = self.delete_set()
        out.append(varuint(len(ds)))
        for client, runs in ds.items():
            out.append(varuint(client) + varuint(len(runs)))
            out.extend(varuint(c) + varuint(n) for c, n in runs)
        return b"".join(out)

    def sequence(self) -> tuple[list[int], bytearray]:
        """Packed ids and tombstones of what the writer holds now, in
        document order: a base another writer can leave from."""
        ids, flags, e = [], bytearray(), self.head
        nxt, dead = self.next, self.dead
        while e != _NONE:
            ids.append(self._id_of(e))
            flags.append(dead[e])
            e = nxt[e]
        return ids, flags


_LETTERS = "etaoinshrdlucmfwypvbgkqjxz"
SHAPES = ("b2.2", "b2.3", "b2.4", "array")


def _word(rng, low: int, high: int) -> str:
    return "".join(rng.choices(_LETTERS, k=rng.randint(low, high)))


def work_offline(writer: Writer, shape: str, operations: int, rng, p=None) -> None:
    """``operations`` operations of one of crdt-benchmarks' B2 shapes at
    random positions of what the writer sees: ``b2.2`` a character,
    ``b2.3`` a word, ``b2.4`` a word or, at equal odds, a delete of some
    characters; ``array`` one whole number (``BASELINE.json`` config 4).
    ``p``: ``word`` and ``delete`` as ``[least, most]`` lengths."""
    p = p or {}
    w_lo, w_hi = p.get("word", (2, 10))
    d_lo, d_hi = p.get("delete", (1, 10))
    insert, randint = writer.insert, rng.randint
    if shape == "b2.2":
        choice = rng.choice
        for _ in range(operations):
            insert(randint(0, len(writer.live)), choice(_LETTERS))
    elif shape == "b2.3":
        for _ in range(operations):
            insert(randint(0, len(writer.live)), _word(rng, w_lo, w_hi))
    elif shape == "b2.4":
        for _ in range(operations):
            n = len(writer.live)
            if n < d_hi or rng.random() < 0.5:
                insert(randint(0, n), _word(rng, w_lo, w_hi))
            else:
                k = randint(d_lo, d_hi)
                writer.delete(randint(0, n - k), k)
    elif shape == "array":
        for _ in range(operations):
            insert(randint(0, len(writer.live)), (randint(0, 999_999),))
    else:
        raise ValueError(f"shape {shape!r}")


def history(task: dict) -> dict:
    """One session: a writer that leaves from ``ids`` / ``dead`` as
    ``client``, works ``operations`` operations of ``shape`` drawn from
    ``seed`` and comes back.  Returns its ``update``, the ``clock`` it
    reached (the elements it brings), its ``structs`` and, where
    ``sequence`` is asked for, what it holds now."""
    writer = Writer(
        task["client"], task["ids"], task["dead"], task["root"], task["kind"]
    )
    work_offline(
        writer, task["shape"], task["operations"],
        random.Random(task["seed"]), task.get("lengths"),
    )
    out = {
        "update": writer.update(), "clock": writer.clock,
        "structs": writer.n_structs,
    }
    if task.get("sequence"):
        out["ids"], out["dead"] = writer.sequence()
    return out


_LEN = struct.Struct("<Q")


def _send(pipe, obj) -> None:
    data = pickle.dumps(obj, pickle.HIGHEST_PROTOCOL)
    pipe.write(_LEN.pack(len(data)) + data)
    pipe.flush()


def _receive(pipe):
    head = pipe.read(_LEN.size)
    if len(head) < _LEN.size:
        return None
    return pickle.loads(pipe.read(_LEN.unpack(head)[0]))


class Pool:
    """``processes`` workers of this file (0: the caller does the work)."""

    def __init__(self, processes: int):
        self.workers = [
            subprocess.Popen(
                [sys.executable, __file__, "--worker"],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            )
            for _ in range(processes)
        ]

    def map(self, tasks: list[dict]) -> list[dict]:
        """``history`` of every task, in the tasks' order."""
        n = len(self.workers)
        if not n:
            return [history(task) for task in tasks]
        for k, worker in enumerate(self.workers):
            _send(worker.stdin, tasks[k::n])
        out: list = [None] * len(tasks)
        for k, worker in enumerate(self.workers):
            done = _receive(worker.stdout)
            if done is None:
                raise RuntimeError(
                    f"offline writer process {k} ended ({worker.poll()})"
                )
            out[k::n] = done
        return out

    def close(self) -> None:
        for worker in self.workers:
            worker.stdin.close()
        for worker in self.workers:
            worker.wait()
        self.workers = []


def _serve() -> None:
    stdin, stdout = sys.stdin.buffer, sys.stdout.buffer
    while (tasks := _receive(stdin)) is not None:
        _send(stdout, [history(task) for task in tasks])


if __name__ == "__main__" and sys.argv[1:] == ["--worker"]:
    _serve()
