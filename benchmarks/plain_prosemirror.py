"""A plain y-prosemirror client: the typists of the ``prosemirror``
generator.

A room's ProseMirror document as its clients hold it: the tree under
the ``Y.XmlFragment`` named ``prosemirror`` (y-prosemirror's README
binds ``ydoc.getXmlFragment('prosemirror')``), every node a
:class:`Element` (node name, attributes with the id of the item that
holds each, children in document order, deleted ones too) and every run
of text nodes a :class:`Text`: each character and each format item ever
inserted, in document order, with its id (client, clock) and whether it
has been deleted since.  A typist is a cursor in one block's text with
a client id and a clock of its own.  Each ProseMirror transaction
changes the tree and is written out as the one Yjs v1 update
y-prosemirror sends for it (``src/plugins/sync-plugin.js``: a node is a
``Y.XmlElement`` whose attributes are the node's attrs, text is a
``Y.XmlText`` whose marks are format attributes ``{name: attrs}``, and
a ProseMirror transaction is one Yjs transaction):

- a character typed: one ``ContentString`` struct between its
  neighbours (``YText.insert`` without attributes inherits the marks at
  the cursor); in an empty block the ``Y.XmlText`` comes with it;
- a backspace: a delete set of the character, and of the format items
  the client's own ``cleanupFormattingGap`` finds redundant around it;
- Enter: the block split at the cursor, a new ``Y.XmlElement`` after it
  (a ``list_item`` with its ``paragraph`` where the block is a list
  item's), the node's attributes copied, and, where the cursor stood
  inside the text, the tail deleted there and inserted again in a new
  ``Y.XmlText`` under the new element; at a block's end an empty
  ``paragraph``;
- a mark toggled over the word behind the cursor: ``YText.format``, so
  two ``ContentFormat`` structs to put it on; to take it off the opening
  one deleted (and, as Yjs 13.4's ``formatText`` stops at the range's
  end, one written before the closing one: the server's clean-up, below,
  takes both away);
- an attribute set: one ``ContentAny`` struct under the key as
  ``parentSub``, its origin the item it overwrites, which the delete set
  names (``typeMapSet`` deletes the value it replaces).

The rules are the CPU core's (``yjs_tpu/types/ytext.py``, Yjs 13.4:
``findPosition``, ``minimizeAttributeChanges``, ``insertAttributes``,
``formatText``, ``deleteText`` with ``cleanupFormattingGap``), restated
here; nothing here imports the program.  Clients collect garbage, as
y-prosemirror's do by default: a character deleted by an earlier
transaction is a ``ContentDeleted`` to ``cleanupFormattingGap``, not a
string.  The starting tree is read off a replayed document by whoever
has one (:meth:`PlainDoc.of_tree`).

A y-websocket server is a ``Y.Doc`` too, and cleans formatting after a
remote transaction that brought or deleted a format item
(``YText._callObserver`` -> ``cleanupYTextFormatting``); it broadcasts
the deletions and the clients apply them.  :meth:`Text.server_cleanup`
is that pass, run where the server runs it, so that what the typists
hold is what the room holds.

What the typists hold afterwards (:meth:`PlainDoc.xml`,
:meth:`PlainDoc.statement`, :attr:`PlainDoc.sv`) is a second,
independent statement of what the room must hold, beside the ``Y.Doc``
oracle fed the same updates.

Assumed, and not y-prosemirror's: typists work in blocks that hold one
text and nothing else (no inline image beside it), never backspace past
a block's start (no join) and never press Enter in a ``code_block``
(ProseMirror types a newline there); text is ASCII (a character is one
UTF-16 unit).
"""

from __future__ import annotations

import json

_CLOCK_BITS = 32
_CLOCK_MASK = (1 << _CLOCK_BITS) - 1
ROOT = "prosemirror"
TEXTBLOCKS = ("paragraph", "heading", "code_block")
MARK_ON: dict = {}  # a mark without attrs, as y-prosemirror writes `strong`


# -- the wire -----------------------------------------------------------------


def varuint(n: int) -> bytes:
    out = bytearray()
    while n > 0x7F:
        out.append(0x80 | (n & 0x7F))
        n >>= 7
    out.append(n)
    return bytes(out)


def varstring(s: str) -> bytes:
    b = s.encode()
    return varuint(len(b)) + b


def _varint(n: int) -> bytes:
    sign = 0x40 if n < 0 else 0
    n = abs(n)
    out = bytearray([(0x80 if n > 0x3F else 0) | sign | (n & 0x3F)])
    n >>= 6
    while n > 0:
        out.append((0x80 if n > 0x7F else 0) | (n & 0x7F))
        n >>= 7
    return bytes(out)


def any_value(v) -> bytes:
    """lib0's ``any`` encoding of an attribute value."""
    if v is None:
        return b"\x7e"
    if isinstance(v, bool):
        return b"\x78" if v else b"\x79"
    if isinstance(v, int):
        return b"\x7d" + _varint(v)
    if isinstance(v, str):
        return b"\x77" + varstring(v)
    raise TypeError(f"attribute value {v!r}")


def pack(client: int, clock: int) -> int:
    return (client << _CLOCK_BITS) | clock


def _id(packed: int) -> bytes:
    return varuint(packed >> _CLOCK_BITS) + varuint(packed & _CLOCK_MASK)


def _struct(
    ref: int, left: int | None, right: int | None, parent: int | None,
    sub: str | None, content: bytes,
) -> bytes:
    """One Item: info byte, origins, the parent (a type item's id, or
    the root's name where ``parent`` is None) and the ``parentSub`` where
    no origin names a neighbour, then the content."""
    info = (
        ref | (0x80 if left is not None else 0)
        | (0x40 if right is not None else 0)
        | (0x20 if sub is not None else 0)
    )
    out = bytes([info])
    if left is not None:
        out += _id(left)
    if right is not None:
        out += _id(right)
    if left is None and right is None:
        out += (
            b"\x01" + varstring(ROOT) if parent is None
            else b"\x00" + _id(parent)
        )
        if sub is not None:
            out += varstring(sub)
    return out + content


def _json(value) -> str:
    return json.dumps(value, separators=(",", ":"), ensure_ascii=False)


class _Tx:
    """One transaction of one client: structs in clock order, deleted
    ids in the order the client deleted them."""

    def __init__(self, typist):
        self.client, self.start = typist.client, typist.clock
        self.typist = typist
        self.structs: list[bytes] = []
        self.deleted: list[int] = []
        # whether it wrote a format item or deleted one
        self.formats = False

    def next_id(self, length: int = 1) -> int:
        t = self.typist
        packed = pack(t.client, t.clock)
        t.clock += length
        t.doc.sv[t.client] = t.clock
        return packed

    def add(self, ref, left, right, parent, sub, content, length=1) -> int:
        packed = self.next_id(length)
        self.structs.append(_struct(ref, left, right, parent, sub, content))
        return packed

    def update(self) -> bytes:
        if self.structs:
            out = (
                b"\x01" + varuint(len(self.structs)) + varuint(self.client)
                + varuint(self.start) + b"".join(self.structs)
            )
        else:
            out = b"\x00"
        by_client: dict[int, list[int]] = {}
        for packed in self.deleted:
            by_client.setdefault(packed >> _CLOCK_BITS, []).append(
                packed & _CLOCK_MASK
            )
        out += varuint(len(by_client))
        for client, clocks in by_client.items():
            ranges: list[list[int]] = []
            for clock in sorted(clocks):
                if ranges and ranges[-1][0] + ranges[-1][1] == clock:
                    ranges[-1][1] += 1
                else:
                    ranges.append([clock, 1])
            out += varuint(client) + varuint(len(ranges))
            for clock, length in ranges:
                out += varuint(clock) + varuint(length)
        return out


# -- attribute values, as the CPU core compares them --------------------------


def _falsy(v) -> bool:
    return (
        v is None or v is False
        or (isinstance(v, (int, float)) and not isinstance(v, bool) and v == 0)
        or v == ""
    )


def equal_attrs(a, b) -> bool:
    if a is b:
        return True
    if isinstance(a, dict) and isinstance(b, dict):
        return a == b
    if isinstance(a, (dict, list)) or isinstance(b, (dict, list)):
        return False
    if isinstance(a, bool) != isinstance(b, bool):
        return False
    return a == b


def _strict_eq(held, value, at: int) -> bool:
    """JS ``===`` of the attribute ``held`` (``(value, entry)`` or None)
    with the value of the format item at entry ``at``: an object is
    itself and nothing else."""
    a, where = held if held is not None else (None, -1)
    if _falsy(a):
        a = None
    if isinstance(a, (dict, list)) or isinstance(value, (dict, list)):
        return where == at
    if isinstance(a, bool) != isinstance(value, bool):
        return False
    return a == value


# -- the tree -----------------------------------------------------------------

CHAR, FORMAT = 0, 1


class Text:
    """One ``Y.XmlText``: characters and format items in document order."""

    __slots__ = ("id", "parent", "dead", "ids", "kind", "val", "gone")

    def __init__(self, packed: int, parent, dead: bool = False):
        self.id, self.parent, self.dead = packed, parent, dead
        self.ids: list[int] = []
        self.kind = bytearray()
        self.val: list = []     # a character, or (key, value)
        self.gone = bytearray()  # 1: deleted

    def put(self, at: int, packed: int, kind: int, val) -> None:
        self.ids.insert(at, packed)
        self.kind.insert(at, kind)
        self.val.insert(at, val)
        self.gone.insert(at, 0)

    def live(self) -> int:
        return sum(
            1 for k, g in zip(self.kind, self.gone) if k == CHAR and not g
        )

    def _note(self, attrs: dict, at: int) -> None:
        key, value = self.val[at]
        if value is None:
            attrs.pop(key, None)
        else:
            attrs[key] = (value, at)

    def find(self, index: int) -> tuple[int, dict]:
        """``findPosition``: the entry after ``index`` live characters,
        and the attributes in force there, each with its format item."""
        at, attrs, n = 0, {}, len(self.ids)
        while at < n and index > 0:
            if not self.gone[at]:
                if self.kind[at] == CHAR:
                    index -= 1
                else:
                    self._note(attrs, at)
            at += 1
        return at, attrs

    def minimize(self, at: int, attrs: dict, want: dict) -> int:
        """``minimizeAttributeChanges``: over what is deleted, and over
        format items that say what ``want`` says."""
        n = len(self.ids)
        while at < n:
            if self.gone[at]:
                at += 1
                continue
            if self.kind[at] != FORMAT:
                break
            key, value = self.val[at]
            wanted = want.get(key)
            if not equal_attrs(None if _falsy(wanted) else wanted, value):
                break
            self._note(attrs, at)
            at += 1
        return at

    def runs(self, start: int = 0, attrs: dict | None = None):
        """``toDelta`` from entry ``start``: ``(string, {key: value})``
        runs, broken at every live format item."""
        attrs = dict(attrs or {})
        out, buf = [], []
        for at in range(start, len(self.ids)):
            if self.gone[at]:
                continue
            if self.kind[at] == CHAR:
                buf.append(self.val[at])
            else:
                if buf:
                    out.append(
                        ("".join(buf), {k: v for k, (v, _a) in attrs.items()})
                    )
                    buf = []
                self._note(attrs, at)
        if buf:
            out.append(("".join(buf), {k: v for k, (v, _a) in attrs.items()}))
        return out

    def xml(self) -> str:
        if self.dead:
            return ""
        out = []
        for string, attrs in self.runs():
            names = sorted(attrs)
            s = "".join(
                f"<{name}"
                + "".join(f' {k}="{attrs[name][k]}"' for k in sorted(attrs[name]))
                + ">"
                for name in names
            )
            s += string
            s += "".join(f"</{name}>" for name in reversed(names))
            out.append(s)
        return "".join(out)

    # -- what the client's own transaction cleans -------------------------

    def clean_gap(
        self, start: int, end: int, start_attrs: dict, end_attrs: dict,
        fresh: set[int], tx,
    ) -> None:
        """``cleanupFormattingGap`` on a client that collects garbage:
        ``end`` moves on to the next string, and a character deleted
        before this transaction (not in ``fresh``) is none."""
        n = len(self.ids)
        while end < n and not (
            self.kind[end] == CHAR and (not self.gone[end] or end in fresh)
        ):
            if not self.gone[end] and self.kind[end] == FORMAT:
                self._note(end_attrs, end)
            end += 1
        for at in range(start, end):
            if self.gone[at] or self.kind[at] != FORMAT:
                continue
            key, value = self.val[at]
            if not _strict_eq(end_attrs.get(key), value, at) or _strict_eq(
                start_attrs.get(key), value, at
            ):
                self.gone[at] = 1
                tx.deleted.append(self.ids[at])
                tx.formats = True

    def delete(self, index: int, length: int, tx) -> int:
        """``deleteText``: ``length`` live characters after ``index`` of
        them, then the gap's clean-up.  Returns the entry where the walk
        began."""
        at, attrs = self.find(index)
        start, start_attrs = at, dict(attrs)
        fresh: set[int] = set()
        n = len(self.ids)
        while length > 0 and at < n:
            if not self.gone[at]:
                if self.kind[at] == CHAR:
                    self.gone[at] = 1
                    fresh.add(at)
                    tx.deleted.append(self.ids[at])
                    length -= 1
                else:
                    self._note(attrs, at)
            at += 1
        self.clean_gap(start, at, start_attrs, dict(attrs), fresh, tx)
        return start

    # -- what the server cleans after a remote transaction -----------------

    def server_cleanup(self) -> int:
        """``cleanupYTextFormatting``, which a ``Y.Doc`` runs on a text
        that a remote transaction changed, if the transaction brought a
        format item or deleted one of the text's: between two live
        strings every format item goes that the attributes after the gap
        do not owe to it, or that says what was in force before it."""
        cleaned, start, n = 0, 0, len(self.ids)
        start_attrs: dict = {}
        attrs: dict = {}
        for end in range(n):
            if self.gone[end]:
                continue
            if self.kind[end] == FORMAT:
                self._note(attrs, end)
                continue
            for at in range(start, end):
                if self.gone[at] or self.kind[at] != FORMAT:
                    continue
                key, value = self.val[at]
                if not _strict_eq(attrs.get(key), value, at) or _strict_eq(
                    start_attrs.get(key), value, at
                ):
                    self.gone[at] = 1
                    cleaned += 1
            start_attrs, start = dict(attrs), end
        return cleaned


class Element:
    """One ``Y.XmlElement`` (or the fragment, whose ``id`` is None)."""

    __slots__ = ("id", "parent", "dead", "name", "attrs", "kids")

    def __init__(self, packed, parent, name, dead: bool = False):
        self.id, self.parent, self.name, self.dead = packed, parent, name, dead
        # key -> [value, id of the item that holds it, deleted]
        self.attrs: dict[str, list] = {}
        self.kids: list = []

    def xml(self) -> str:
        inner = "".join(k.xml() for k in self.kids if not k.dead)
        if self.id is None:
            return inner
        if self.dead:
            return ""
        held = {k: v for k, (v, _i, gone) in self.attrs.items() if not gone}
        attrs = " ".join(f'{k}="{held[k]}"' for k in sorted(held))
        name = self.name.lower()
        return f"<{name}{' ' + attrs if attrs else ''}>{inner}</{name}>"

    def live_kids(self) -> list:
        return [k for k in self.kids if not k.dead]


class PlainDoc:
    """The tree of one room and its state vector."""

    def __init__(self, root: Element, sv: dict[int, int]):
        self.root, self.sv = root, sv

    @classmethod
    def of_tree(cls, tree, sv: dict[int, int]) -> PlainDoc:
        """From the fragment's children as a replayed document gives
        them, each ``("element", client, clock, deleted, name, attrs,
        children)`` with ``attrs[key] = (value, client, clock, deleted)``
        or ``("text", client, clock, deleted, items)`` with an item
        ``(client, clock, deleted, string | None, format | None)``, a
        format ``(key, value)``; deleted content reads as ``None``."""
        root = Element(None, None, None)

        def build(node, parent):
            kind, client, clock, dead = node[:4]
            if kind == "text":
                text = Text(pack(client, clock), parent, dead)
                for c, k, gone, string, fmt in node[4]:
                    if fmt is not None:
                        text.put(len(text.ids), pack(c, k), FORMAT, tuple(fmt))
                        text.gone[-1] = 1 if gone else 0
                    else:
                        for j, ch in enumerate(string):
                            text.put(len(text.ids), pack(c, k + j), CHAR, ch)
                            text.gone[-1] = 1 if gone else 0
                return text
            el = Element(pack(client, clock), parent, node[4], dead)
            for key, (value, c, k, gone) in node[5].items():
                el.attrs[key] = [value, pack(c, k), bool(gone)]
            el.kids = [build(kid, el) for kid in node[6]]
            return el

        root.kids = [build(node, root) for node in tree]
        return cls(root, dict(sv))

    def copy(self) -> PlainDoc:
        def dup(node, parent):
            if isinstance(node, Text):
                t = Text(node.id, parent, node.dead)
                t.ids, t.val = list(node.ids), list(node.val)
                t.kind, t.gone = bytearray(node.kind), bytearray(node.gone)
                return t
            el = Element(node.id, parent, node.name, node.dead)
            el.attrs = {k: list(v) for k, v in node.attrs.items()}
            el.kids = [dup(kid, el) for kid in node.kids]
            return el

        return PlainDoc(dup(self.root, None), dict(self.sv))

    def xml(self) -> str:
        return self.root.xml()

    def blocks(self) -> list[Element]:
        """The text blocks a typist may work in, in document order: live
        elements of a text block's name that hold one live text and
        nothing else, or nothing."""
        out = []

        def walk(el):
            for kid in el.kids:
                if kid.dead or isinstance(kid, Text):
                    continue
                if kid.name in TEXTBLOCKS:
                    live = kid.live_kids()
                    if not live or (
                        len(live) == 1 and isinstance(live[0], Text)
                    ):
                        out.append(kid)
                else:
                    walk(kid)

        walk(self.root)
        return out

    def statement(self):
        """State vector and, per live text block in document order, node
        name, sorted attributes and runs of string and marks."""
        out = []

        def walk(el):
            for kid in el.live_kids():
                if isinstance(kid, Text):
                    continue
                if kid.name in TEXTBLOCKS:
                    held = sorted(
                        (k, v) for k, (v, _i, gone) in kid.attrs.items()
                        if not gone
                    )
                    runs = [
                        run for t in kid.live_kids() if isinstance(t, Text)
                        for run in t.runs()
                    ]
                    out.append((kid.name, held, runs))
                else:
                    walk(kid)

        walk(self.root)
        return dict(self.sv), out


def text_of(block: Element) -> Text | None:
    live = block.live_kids()
    return live[0] if live else None


# -- a typist -----------------------------------------------------------------


class Typist:
    """One client's cursor: a text block and the number of live
    characters of its text before the cursor."""

    def __init__(self, doc: PlainDoc, client: int, block: Element, index=None):
        self.doc, self.client, self.clock = doc, client, 0
        self.jump(block, index)

    def jump(self, block: Element, index: int | None = None) -> None:
        self.block = block
        text = text_of(block)
        n = text.live() if text is not None else 0
        self.index = n if index is None else max(0, min(index, n))

    # -- a character -------------------------------------------------------

    def type(self, ch: str) -> bytes:
        tx = _Tx(self)
        block, text = self.block, text_of(self.block)
        if text is None:
            # the block's first character brings its Y.XmlText
            right = block.kids[0].id if block.kids else None
            tid = tx.add(7, None, right, block.id, None, b"\x06")
            text = Text(tid, block)
            block.kids.insert(0, text)
            cid = tx.add(4, None, None, tid, None, varstring(ch))
            text.put(0, cid, CHAR, ch)
            self.index = 1
            return tx.update()
        at, attrs = text.find(self.index)
        want = {k: v for k, (v, _a) in attrs.items()}
        at = text.minimize(at, attrs, want)
        ids = text.ids
        cid = tx.add(
            4, ids[at - 1] if at else None,
            ids[at] if at < len(ids) else None, text.id, None, varstring(ch),
        )
        text.put(at, cid, CHAR, ch)
        self.index += 1
        return tx.update()

    # -- a backspace -------------------------------------------------------

    def erase(self) -> bytes | None:
        """The character before the cursor, or None at a block's start."""
        text = text_of(self.block)
        if text is None or self.index == 0:
            return None
        tx = _Tx(self)
        text.delete(self.index - 1, 1, tx)
        self.index -= 1
        self._heard(text, tx)
        return tx.update()

    @staticmethod
    def _heard(text: Text, tx: _Tx) -> None:
        """The server cleans a text after a transaction that brought a
        format item or deleted one of the text's, and the clients hear
        of it."""
        if tx.formats:
            text.server_cleanup()

    # -- a mark ------------------------------------------------------------

    def word(self) -> tuple[int, int] | None:
        """``(index, length)`` of the word behind the cursor, in live
        characters: the nearest run of characters that are not spaces
        which ends at or before the cursor."""
        text = text_of(self.block)
        if text is None:
            return None
        chars = [
            text.val[at] for at in range(len(text.ids))
            if text.kind[at] == CHAR and not text.gone[at]
        ]
        end = min(self.index, len(chars))
        while end > 0 and chars[end - 1] == " ":
            end -= 1
        start = end
        while start > 0 and chars[start - 1] != " ":
            start -= 1
        return (start, end - start) if end > start else None

    def toggle(self, key: str) -> bytes | None:
        """The mark ``key`` over the word behind the cursor: off where
        every character of the word has it, else on (``YText.format``).
        None where there is no word."""
        word = self.word()
        if word is None:
            return None
        start, length = word
        text = text_of(self.block)
        at, attrs = text.find(start)
        # the marks of the word's characters, by a walk of its own
        probe, held, seen, n = at, dict(attrs), 0, len(text.ids)
        on = True
        while seen < length and probe < n:
            if not text.gone[probe]:
                if text.kind[probe] == CHAR:
                    on = on and key in held
                    seen += 1
                else:
                    text._note(held, probe)
            probe += 1
        value = None if on else MARK_ON
        tx = _Tx(self)
        self._format(text, tx, at, attrs, length, key, value)
        self._heard(text, tx)
        return tx.update()

    @staticmethod
    def _format(text, tx, at, attrs, length, key, value) -> None:
        """``formatText`` of one attribute from the position ``at``."""
        want = {key: value}
        at = text.minimize(at, attrs, want)
        negated: dict = {}

        def put(at, k, v):
            ids = text.ids
            fid = tx.add(
                6, ids[at - 1] if at else None,
                ids[at] if at < len(ids) else None, text.id, None,
                varstring(k) + varstring(_json(v)),
            )
            text.put(at, fid, FORMAT, (k, v))
            tx.formats = True

        held = attrs.get(key)
        current = None if held is None or _falsy(held[0]) else held[0]
        if not equal_attrs(current, value):
            negated[key] = current
            put(at, key, value)
            text._note(attrs, at)
            at += 1
        while length > 0 and at < len(text.ids):
            if not text.gone[at]:
                if text.kind[at] == FORMAT:
                    k, v = text.val[at]
                    if k == key:
                        if equal_attrs(value, v):
                            negated.pop(k, None)
                        else:
                            negated[k] = v
                        text.gone[at] = 1
                        tx.deleted.append(text.ids[at])
                        tx.formats = True
                    else:
                        text._note(attrs, at)
                else:
                    length -= 1
            at += 1
        # insertNegatedAttributes
        while at < len(text.ids):
            if text.gone[at]:
                at += 1
                continue
            if text.kind[at] != FORMAT:
                break
            k, v = text.val[at]
            if not equal_attrs(negated.get(k), v):
                break
            negated.pop(k, None)
            at += 1
        for k, v in negated.items():
            put(at, k, v)
            at += 1

    # -- an attribute ------------------------------------------------------

    def set_attr(self, key: str, value) -> bytes:
        """``setAttribute`` on the cursor's block: last writer wins."""
        tx = _Tx(self)
        block = self.block
        held = block.attrs.get(key)
        if held is None:
            aid = tx.add(
                8, None, None, block.id, key, b"\x01" + any_value(value)
            )
        else:
            aid = tx.add(
                8, held[1], None, block.id, key, b"\x01" + any_value(value)
            )
            if not held[2]:
                tx.deleted.append(held[1])
        block.attrs[key] = [value, aid, False]
        return tx.update()

    # -- Enter -------------------------------------------------------------

    def enter(self) -> bytes:
        """The block split at the cursor; the cursor goes to the start
        of the new block."""
        tx = _Tx(self)
        block, text = self.block, text_of(self.block)
        total = text.live() if text is not None else 0
        inside = 0 < total and self.index < total
        runs = []
        if inside:
            at, attrs = text.find(self.index)
            # ProseMirror joins neighbouring text of the same marks
            for string, marks in text.runs(at, attrs):
                if runs and runs[-1][1] == marks:
                    runs[-1] = (runs[-1][0] + string, marks)
                else:
                    runs.append((string, marks))
            text.delete(self.index, total - self.index, tx)
        # the node that splits: a list item where the block is its child
        node = block
        if block.parent.name == "list_item":
            node = block.parent
        home = node.parent
        where = home.kids.index(node) + 1
        right = home.kids[where].id if where < len(home.kids) else None
        name = block.name if inside else "paragraph"
        if node is block:
            new = Element(
                tx.add(7, node.id, right, home.id, None, b"\x03" + varstring(name)),
                home, name,
            )
            home.kids.insert(where, new)
            new_block = new
        else:
            item = Element(
                tx.add(
                    7, node.id, right, home.id, None,
                    b"\x03" + varstring("list_item"),
                ),
                home, "list_item",
            )
            home.kids.insert(where, item)
            new_block = Element(
                tx.add(7, None, None, item.id, None, b"\x03" + varstring(name)),
                item, name,
            )
            item.kids.append(new_block)
        # y-prosemirror makes the node whole before it is integrated, and
        # Y.XmlElement integrates its children before its attributes
        if runs:
            tid = tx.add(7, None, None, new_block.id, None, b"\x06")
            new_text = Text(tid, new_block)
            new_block.kids.append(new_text)
            # applyDelta: each run between its marks' format items
            for string, marks in runs:
                for k, v in marks.items():
                    self._append(tx, new_text, 6, (k, v))
                self._append(tx, new_text, 4, string)
                for k in marks:
                    self._append(tx, new_text, 6, (k, None))
        if inside:
            for key, (value, _i, gone) in block.attrs.items():
                if not gone:
                    aid = tx.add(
                        8, None, None, new_block.id, key,
                        b"\x01" + any_value(value),
                    )
                    new_block.attrs[key] = [value, aid, False]
            self._heard(text, tx)
        self.block, self.index = new_block, 0
        return tx.update()

    @staticmethod
    def _append(tx, text: Text, ref: int, what) -> None:
        """A string or a format item at the end of a new text."""
        left = text.ids[-1] if text.ids else None
        if ref == 4:
            first = tx.add(
                4, left, None, text.id, None, varstring(what), len(what)
            )
            for j, ch in enumerate(what):
                text.put(len(text.ids), first + j, CHAR, ch)
        else:
            k, v = what
            fid = tx.add(
                6, left, None, text.id, None, varstring(k) + varstring(_json(v))
            )
            text.put(len(text.ids), fid, FORMAT, (k, v))
            tx.formats = True
