"""A plain Yjs text client: the typists of the ``typing`` generator.

A room's text as every client holds it: each character ever inserted,
in document order, with its id (client, clock) and whether it has been
deleted since.  A typist is a cursor in that sequence with a client id
and a clock of its own.  A keystroke changes the sequence and is
written out as the Yjs v1 update a y-websocket client would send for
it: one struct that names its left and right neighbours, or one range
of the delete set.  Nothing here imports the program; the starting
sequence of a room is read off a replayed document by whoever has one
(:func:`PlainText.of_items`).

What a typist holds afterwards (:meth:`PlainText.text`,
:attr:`PlainText.sv`) is a second, independent statement of what the
room must hold, beside the ``Y.Doc`` oracle fed the same updates.
"""

from __future__ import annotations

from array import array

import numpy as np

_CLOCK_BITS = 32
_ROOT = b"text"


def varuint(n: int) -> bytes:
    out = bytearray()
    while n > 0x7F:
        out.append(0x80 | (n & 0x7F))
        n >>= 7
    out.append(n)
    return bytes(out)


def _id(packed: int) -> bytes:
    return varuint(packed >> _CLOCK_BITS) + varuint(
        packed & ((1 << _CLOCK_BITS) - 1)
    )


def insert_update(
    client: int, clock: int, left: int | None, right: int | None, ch: str
) -> bytes:
    """One ContentString struct of one character between the packed ids
    ``left`` and ``right`` (None: the text's edge), empty delete set."""
    info = 4 | (0x80 if left is not None else 0) | (0x40 if right is not None else 0)
    out = b"\x01\x01" + varuint(client) + varuint(clock) + bytes([info])
    if left is not None:
        out += _id(left)
    if right is not None:
        out += _id(right)
    if left is None and right is None:  # the parent: the root type, by name
        out += b"\x01" + varuint(len(_ROOT)) + _ROOT
    s = ch.encode()
    return out + varuint(len(s)) + s + b"\x00"


def delete_update(packed: int) -> bytes:
    """No struct; a delete set of the one character ``packed``."""
    return (
        b"\x00\x01" + varuint(packed >> _CLOCK_BITS) + b"\x01"
        + varuint(packed & ((1 << _CLOCK_BITS) - 1)) + b"\x01"
    )


class PlainText:
    """Ids, characters and tombstones of one text, in document order."""

    __slots__ = ("ids", "chars", "dead", "sv")

    def __init__(self, ids: array, chars: array, dead: bytearray, sv: dict):
        self.ids, self.chars, self.dead, self.sv = ids, chars, dead, sv

    @classmethod
    def of_items(cls, items) -> PlainText:
        """From ``(client, clock, string, deleted)`` per item in document
        order, as a replayed document's linked list gives them."""
        ids, chars, dead, sv = array("q"), array("I"), bytearray(), {}
        for client, clock, s, deleted in items:
            if client >> (63 - _CLOCK_BITS) or (clock + len(s)) >> _CLOCK_BITS:
                raise ValueError(f"id ({client}, {clock}) does not pack")
            base = (client << _CLOCK_BITS) | clock
            ids.extend(range(base, base + len(s)))
            chars.extend(map(ord, s))
            dead.extend((1 if deleted else 0,) * len(s))
            sv[client] = max(sv.get(client, 0), clock + len(s))
        return cls(ids, chars, dead, sv)

    def copy(self) -> PlainText:
        return PlainText(
            array("q", self.ids), array("I", self.chars),
            bytearray(self.dead), dict(self.sv),
        )

    def text(self) -> str:
        live = np.frombuffer(self.dead, np.uint8) == 0
        return (
            np.frombuffer(self.chars, np.uint32)[live]
            .astype("<u4").tobytes().decode("utf-32-le")
        )

    def live(self) -> int:
        return len(self.dead) - sum(self.dead)

    def place(self, pos: int, packed: int, ch: str) -> None:
        self.ids.insert(pos, packed)
        self.chars.insert(pos, ord(ch))
        self.dead.insert(pos, 0)
        client = packed >> _CLOCK_BITS
        self.sv[client] = (packed & ((1 << _CLOCK_BITS) - 1)) + 1


class Typist:
    """One client's cursor in a :class:`PlainText`: ``pos`` characters
    of the sequence, live or dead, lie before it."""

    def __init__(self, text: PlainText, client: int):
        self.t, self.client, self.clock = text, client, 0
        self.pos = len(text.ids)

    def settle(self) -> int:
        """Where an insert at the cursor goes: behind every tombstone
        that follows it, straight before the next live character
        (``Y.Text`` counts live characters to the index and then steps
        over what is deleted to its right)."""
        dead, pos = self.t.dead, self.pos
        end = len(dead)
        while pos < end and dead[pos]:
            pos += 1
        self.pos = pos
        return pos

    def jump(self, live_index: int) -> None:
        """The cursor after the ``live_index``-th live character."""
        if live_index <= 0:
            self.pos = 0
            return
        live = np.flatnonzero(np.frombuffer(self.t.dead, np.uint8) == 0)
        self.pos = int(live[min(live_index, len(live)) - 1]) + 1 if len(live) else 0

    def insert_at(self, pos: int, ch: str) -> tuple[bytes, int]:
        """The update for ``ch`` at the settled ``pos``, and its packed
        id; the sequence is not changed (:meth:`PlainText.place` does)."""
        ids = self.t.ids
        packed = (self.client << _CLOCK_BITS) | self.clock
        self.clock += 1
        update = insert_update(
            self.client, packed & ((1 << _CLOCK_BITS) - 1),
            ids[pos - 1] if pos else None,
            ids[pos] if pos < len(ids) else None, ch,
        )
        return update, packed

    def type(self, ch: str) -> bytes:
        pos = self.settle()
        update, packed = self.insert_at(pos, ch)
        self.t.place(pos, packed, ch)
        self.pos = pos + 1
        return update

    def erase(self) -> bytes | None:
        """Backspace: the nearest live character before the cursor, or
        None where there is none."""
        dead, j = self.t.dead, self.pos - 1
        while j >= 0 and dead[j]:
            j -= 1
        if j < 0:
            return None
        dead[j] = 1
        return delete_update(self.t.ids[j])


def type_together(a: Typist, ch_a: str, b: Typist, ch_b: str) -> list[bytes]:
    """Both typists of one text type a character from the same state,
    then hear each other.  Where both cursors are at one place the two
    structs name the same neighbours, and YATA puts the lower client id
    first."""
    if a.t is not b.t or a.client >= b.client:
        raise ValueError("two typists of one text, the lower client id first")
    pa, pb = a.settle(), b.settle()
    ua, id_a = a.insert_at(pa, ch_a)
    ub, id_b = b.insert_at(pb, ch_b)
    a.t.place(pa, id_a, ch_a)
    a.pos = pa + 1
    if pb >= pa:
        pb += 1
    a.t.place(pb, id_b, ch_b)
    b.pos = pb + 1
    if pb < a.pos:
        a.pos += 1
    return [ua, ub]
