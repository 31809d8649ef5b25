"""``benchmarks/plain_prosemirror.py``: every kind of transaction the plain
client writes, applied to a CPU ``Y.Doc``, gives the plain client's own
statement, and is the update y-prosemirror's binding on the CPU core
(``scripts/gen_prosemirror_fixtures.py`` ``Binding``) sends for the same
transaction: byte for byte for a character, a backspace, a mark, an
attribute and an Enter whose tail is one run; an Enter whose tail has
several runs of marks is held to the binding by what both documents read
(the CPU core models Yjs 13.4, whose ``applyDelta`` leaves its position
stale after a run's closing format items; the plain client writes what
a current Yjs does).  What the server's ``Y.Doc`` cleans after each
update, the plain tree has cleaned too: entry for entry."""

import ast
import random
import sys

import pytest

import yjs_tpu as Y
from benchmarks import harness
from benchmarks.generators.prosemirror import tree_of
from benchmarks.plain_prosemirror import (
    ROOT, PlainDoc, Typist, any_value, text_of, varuint,
)

sys.path.insert(0, str(harness.ROOT / "scripts"))
import gen_prosemirror_fixtures as gen  # noqa: E402

SOURCE = harness.HERE / "plain_prosemirror.py"


def test_the_plain_client_imports_nothing_of_the_program():
    tree = ast.parse(SOURCE.read_text())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported == {"__future__", "json"}


def test_wire_primitives_are_lib0s():
    from yjs_tpu.lib0 import encoding as enc

    for n in (0, 1, 127, 128, 300, 2**31 + 5, 2**40):
        e = enc.Encoder()
        enc.write_var_uint(e, n)
        assert varuint(n) == e.to_bytes()
    for v in (None, True, False, 0, 5, -3, 64, -64, 2**20, "left", ""):
        e = enc.Encoder()
        enc.write_any(e, v)
        assert any_value(v) == e.to_bytes()


class Worlds:
    """A writer on the binding and a plain typist doing the same to one
    document, each with a server of its own (a ``Y.Doc`` that keeps
    content and cleans after remote transactions)."""

    def __init__(self, seed: int, session: int = 250):
        gen_session, gen.SESSION = gen.SESSION, session
        try:
            start = Y.encode_state_as_update(gen.write_session(seed))
        finally:
            gen.SESSION = gen_session
        self.client = 777
        self.writer = Y.Doc(gc=True)
        Y.apply_update(self.writer, start, "server")
        self.sent: list[bytes] = []
        self.writer.on(
            "update",
            lambda u, origin, _d: origin == "server" or self.sent.append(u),
        )
        self.b_server = Y.Doc(gc=False)
        Y.apply_update(self.b_server, start)
        self.b_server.on(
            "update", lambda u, _o, _d: Y.apply_update(self.writer, u, "server")
        )
        self.b = gen.Binding(self.writer, self.client)
        self.p_server = Y.Doc(gc=False)
        Y.apply_update(self.p_server, start)
        self.plain = self.read(self.p_server)
        blocks, b_blocks = self.plain.blocks(), self.b.blocks()
        assert len(blocks) == len(b_blocks) > 10
        self.t = Typist(self.plain, self.client, blocks[0])
        self.b.jump(b_blocks[0])

    @staticmethod
    def read(doc) -> PlainDoc:
        sv = Y.decode_state_vector(Y.encode_state_vector(doc))
        return PlainDoc.of_tree(tree_of(doc), sv)

    def jump(self, k: int, index: int) -> None:
        self.t.jump(self.plain.blocks()[k], index)
        self.b.jump(self.b.blocks()[k], index)
        assert self.t.index == self.b.index

    def both(self, plain_op, binding_op):
        """One transaction in both worlds; returns (plain's update, the
        binding's), None where neither could."""
        before = len(self.sent)
        update = plain_op(self.t)
        done = binding_op(self.b)
        if update is None:
            assert done is False and len(self.sent) == before
            return None
        assert len(self.sent) == before + 1
        theirs = self.sent[-1]
        Y.apply_update(self.p_server, update)
        Y.apply_update(self.b_server, theirs, "writer")
        self.check()
        return update, theirs

    def check(self) -> None:
        want = self.p_server.get_xml_fragment(ROOT).to_string()
        assert self.plain.xml() == want
        assert self.b_server.get_xml_fragment(ROOT).to_string() == want
        sv = Y.decode_state_vector(Y.encode_state_vector(self.p_server))
        assert self.plain.sv == sv and self.t.index == self.b.index
        # what the server cleaned, the plain tree has cleaned
        assert flat(self.read(self.p_server)) == flat(self.plain)
        assert self.plain.statement()[1] == self.read(self.p_server).statement()[1]


def flat(doc: PlainDoc) -> list:
    out = []

    def walk(el):
        for kid in el.kids:
            if hasattr(kid, "ids"):
                out.append((kid.id, tuple(kid.ids), bytes(kid.gone)))
            else:
                held = sorted((k, v[0], v[2]) for k, v in kid.attrs.items())
                out.append((kid.id, kid.dead, kid.name, held))
                walk(kid)

    walk(doc.root)
    return out


@pytest.fixture(scope="module")
def worlds():
    return Worlds(3)


def same(pair):
    assert pair is not None and pair[0] == pair[1], (
        pair[0].hex(), pair[1].hex()
    )
    return pair[0]


def test_a_character_is_one_string_struct(worlds):
    w = worlds
    w.jump(2, 3)
    u = same(w.both(lambda t: t.type("q"), lambda b: b.type("q")))
    assert u[:2] == b"\x01\x01" and u.endswith(b"\x01q\x00")  # one struct, no delete set
    info = u[2 + len(varuint(w.client)) + len(varuint(w.t.clock - 1))]
    assert info & 0x1F == 4 and info & 0xC0 == 0xC0  # a string between two neighbours


def test_a_backspace_is_a_delete_set(worlds):
    w = worlds
    w.jump(2, 4)
    u = same(w.both(lambda t: t.erase(), lambda b: b.erase()))
    assert u[0] == 0 and u[1] >= 1  # no struct, a delete set
    w.jump(2, 0)
    assert w.both(lambda t: t.erase(), lambda b: b.erase()) is None


def test_a_mark_is_two_format_structs_and_its_removal_deletes_them(worlds):
    w = worlds
    k = next(
        k for k, blk in enumerate(w.plain.blocks())
        if text_of(blk) is not None and not any(
            m for _s, m in text_of(blk).runs()
        ) and text_of(blk).live() > 12
    )
    w.jump(k, 7)
    on = same(w.both(lambda t: t.toggle("em"), lambda b: b.toggle("em")))
    assert on[:2] == b"\x01\x02" and on.count(b"\x02em") == 2
    assert b"\x02{}" in on and b"\x04null" in on
    # off again: the opening item deleted (Yjs 13.4's formatText stops at
    # the range's end and writes an item before the closing one, which
    # the server's clean-up takes away with it)
    off = same(w.both(lambda t: t.toggle("em"), lambda b: b.toggle("em")))
    assert off.count(b"\x02em") <= 1 and off[-1] == 1  # ends in a delete set
    assert not any(m for _s, m in text_of(w.t.block).runs())
    text = text_of(w.t.block)
    assert not any(
        k == 1 and not g for k, g in zip(text.kind, text.gone)
    )  # no live format item is left


def test_an_attribute_is_a_last_writer_wins_entry(worlds):
    w = worlds
    k = next(
        k for k, blk in enumerate(w.plain.blocks()) if blk.name == "heading"
    )
    w.jump(k, 0)
    for level in (4, 2):
        u = same(w.both(
            lambda t: t.set_attr("level", level),
            lambda b: b.set_attr("level", level),
        ))
        info = u[2 + len(varuint(w.client)) + len(varuint(w.t.clock - 1))]
        # ContentAny under a parentSub, its origin the entry it overwrites,
        # which the delete set names
        assert info == 8 | 0x80 | 0x20 and u[-1] == 1
    assert f'level="{level}"' in w.plain.xml()


def test_enter_at_a_blocks_end_makes_an_empty_paragraph(worlds):
    w = worlds
    k = next(
        k for k, blk in enumerate(w.plain.blocks()) if blk.name == "heading"
    )
    w.jump(k, 10**6)
    n = len(w.plain.blocks())
    u = same(w.both(lambda t: t.enter(), lambda b: b.enter()))
    assert u[:2] == b"\x01\x01" and b"\x03\x09paragraph" in u
    assert len(w.plain.blocks()) == n + 1 and w.t.block.name == "paragraph"
    # its text comes with its first character: a type struct and a string
    first = same(w.both(lambda t: t.type("a"), lambda b: b.type("a")))
    assert first[:2] == b"\x01\x02" and first[-3:] == b"\x01a\x00"


def test_enter_inside_a_text_moves_the_tail(worlds):
    w = worlds
    k = next(
        k for k, blk in enumerate(w.plain.blocks())
        if blk.name == "paragraph" and blk.parent.name is None
        and text_of(blk) is not None and text_of(blk).live() > 20
        and len(text_of(blk).runs()) == 1
    )
    w.jump(k, 9)
    before = w.plain.blocks()[k]
    tail = "".join(s for s, _m in text_of(before).runs())[9:]
    u = same(w.both(lambda t: t.enter(), lambda b: b.enter()))
    assert tail.encode() in u and u.count(b"\x09paragraph") == 1
    assert "".join(s for s, _m in text_of(w.t.block).runs()) == tail
    assert text_of(before).live() == 9


def test_enter_in_a_list_item_splits_the_item(worlds):
    w = worlds
    k = next(
        k for k, blk in enumerate(w.plain.blocks())
        if blk.parent.name == "list_item" and text_of(blk) is not None
        and text_of(blk).live() > 6
    )
    w.jump(k, 4)
    pair = w.both(lambda t: t.enter(), lambda b: b.enter())
    assert b"\x09list_item" in pair[0] and b"\x09paragraph" in pair[0]
    assert w.t.block.parent.name == "list_item"


@pytest.mark.parametrize("seed", [1, 2, 4, 5])
def test_a_seeded_session_holds_in_both_worlds(seed):
    """A thousand transactions of the flood's kinds: the plain client's
    updates give a ``Y.Doc`` server the document the typist holds, the
    binding's give its server the same, and until an Enter with a tail
    of several runs the two send the same bytes."""
    w = Worlds(seed)
    rng = random.Random(f"plain-pm:{seed}")
    equal = diverged = made = 0
    kinds = set()
    for _ in range(1000):
        r = rng.random()
        if r < 0.1:
            blocks = w.plain.blocks()
            k = rng.randrange(len(blocks))
            text = text_of(blocks[k])
            w.jump(k, rng.randint(0, text.live() if text else 0))
            continue
        if r < 0.55:
            ch = " " if rng.random() < 0.2 else rng.choice("abcdef")
            kind, pair = "type", w.both(lambda t: t.type(ch), lambda b: b.type(ch))
        elif r < 0.8:
            kind, pair = "erase", w.both(lambda t: t.erase(), lambda b: b.erase())
        elif r < 0.88:
            if w.t.block.name == "code_block":
                continue
            kind, pair = "enter", w.both(lambda t: t.enter(), lambda b: b.enter())
        elif r < 0.96:
            key = rng.choice(("strong", "em"))
            kind, pair = "mark", w.both(
                lambda t: t.toggle(key), lambda b: b.toggle(key)
            )
        elif w.t.block.name == "heading":
            v = rng.randint(1, 6)
            kind, pair = "attr", w.both(
                lambda t: t.set_attr("level", v), lambda b: b.set_attr("level", v)
            )
        elif w.t.block.name == "paragraph":
            v = rng.choice(("left", "center"))
            kind, pair = "attr", w.both(
                lambda t: t.set_attr("textAlign", v),
                lambda b: b.set_attr("textAlign", v),
            )
        else:
            continue
        if pair is None:
            continue
        made += 1
        kinds.add(kind)
        if not diverged:
            if pair[0] == pair[1]:
                equal += 1
            else:
                # ids differ from here on: the worlds are compared by
                # what their documents read
                assert kind == "enter", (kind, pair[0].hex(), pair[1].hex())
                diverged = made
    assert kinds == {"type", "erase", "enter", "mark", "attr"} and made > 700
    assert equal >= 5 and (not diverged or equal == diverged - 1)
