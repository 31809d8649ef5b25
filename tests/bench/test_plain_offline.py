"""``benchmarks/plain_offline.py``: the plain client that worked offline
writes the update a ``Y.Doc`` doing the same operations writes
(``encode_state_as_update(doc, sv)`` of a default, collecting document):
the same structs, merged and split where Yjs merges and splits them, and
the same delete set; it imports nothing of the program; its worker
processes give what the caller alone gives."""

import ast
import random
import sys
from pathlib import Path

import pytest

import yjs_tpu as Y
from benchmarks import harness, plain_offline
from benchmarks.oracle import items_of
from benchmarks.plain_client import PlainText
from benchmarks.plain_offline import Pool, Writer, history, work_offline

TEXT = "the quick brown fox jumps over the lazy dog"


def text_room(deleted=True):
    """A ``Y.Doc`` that holds a small text with tombstones, its update,
    and the sequence a writer leaves with."""
    doc = Y.Doc(gc=False)
    doc.client_id = 7
    text = doc.get_text("text")
    text.insert(0, TEXT)
    if deleted:
        text.delete(0, 2)   # tombstones at the head
        text.delete(10, 5)
        text.delete(len(text) - 3, 3)  # and at the tail
    plain = PlainText.of_items(items_of(doc))
    return Y.encode_state_as_update(doc), list(plain.ids), bytes(plain.dead)


class Shadow:
    """A writer whose every operation a ``Y.Doc`` repeats."""

    def __init__(self, client, base, ids, dead, kind):
        self.doc = Y.Doc()  # gc on: what a client runs
        self.doc.client_id = client
        if base:
            Y.apply_update(self.doc, base)
        self.sv = Y.encode_state_vector(self.doc)
        self.kind = kind
        self.type = (
            self.doc.get_array(kind) if kind == "array"
            else self.doc.get_text(kind)
        )
        self.writer = w = Writer(client, ids, dead, kind, kind)
        insert, delete = w.insert, w.delete

        def both_insert(index, content):
            insert(index, content)
            self.type.insert(
                index, list(content) if kind == "array" else content
            )

        def both_delete(index, n):
            delete(index, n)
            self.type.delete(index, n)

        w.insert, w.delete = both_insert, both_delete

    def check(self):
        mine = self.writer.update()
        theirs = Y.encode_state_as_update(self.doc, self.sv)
        # byte for byte but for the order of the delete set's clients,
        # which follows the order a store met them in
        assert len(mine) == len(theirs)
        assert Y.merge_updates([mine]) == Y.merge_updates([theirs])
        sv = Y.decode_state_vector(Y.encode_state_vector(self.doc))
        assert self.writer.clock == sv.get(self.writer.client, 0)
        return mine, theirs


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("shape", ["b2.2", "b2.3", "b2.4"])
def test_text_history_is_the_y_docs_update(shape, seed):
    base, ids, dead = text_room()
    s = Shadow(3_000_000 + seed, base, ids, dead, "text")
    work_offline(s.writer, shape, 250, random.Random(f"{shape}:{seed}"))
    mine, _theirs = s.check()
    # and a server that held the base ends with the client's text
    server = Y.Doc(gc=False)
    Y.apply_update(server, base)
    Y.apply_update(server, mine)
    assert server.get_text("text").to_string() == s.type.to_string()
    assert len(s.writer) == len(s.type.to_string())


@pytest.mark.parametrize("seed", range(3))
def test_array_history_is_the_y_docs_update(seed):
    author = Shadow(2_500_000 + seed, None, [], b"", "array")
    work_offline(author.writer, "array", 120, random.Random(f"base:{seed}"))
    base, _ = author.check()
    ids, dead = author.writer.sequence()
    s = Shadow(3_000_000 + seed, base, ids, dead, "array")
    work_offline(s.writer, "array", 250, random.Random(f"array:{seed}"))
    mine, theirs = s.check()
    assert mine == theirs  # no tombstone: one client in the delete set
    server = Y.Doc(gc=False)
    Y.apply_update(server, base)
    Y.apply_update(server, mine)
    assert server.get_array("array").to_json() == s.type.to_json()
    assert all(isinstance(v, int) for v in s.type.to_json())


def test_what_yjs_merges_is_one_struct():
    base, ids, dead = text_room(deleted=False)
    s = Shadow(3_000_001, base, ids, dead, "text")
    w = s.writer
    for k, ch in enumerate("abc"):  # typed left to right: one struct
        w.insert(5 + k, ch)
    assert w.structs() == [(0, 3)]
    w.insert(20, "xyz")             # somewhere else: a second
    assert w.structs() == [(0, 3), (3, 3)]
    w.insert(6, "Q")                # into the first: split for good
    assert w.structs() == [(0, 1), (1, 2), (3, 3), (6, 1)]
    w.delete(22, 1)                 # the y of xyz (Q moved it on by one)
    assert w.structs() == [(0, 1), (1, 2), (3, 1), (4, 1), (5, 1), (6, 1)]
    w.delete(22, 1)                 # the z: the two deleted halves merge
    assert w.structs() == [(0, 1), (1, 2), (3, 1), (4, 2), (6, 1)]
    w.delete(21, 1)                 # and the x: the struct is whole again
    assert w.structs() == [(0, 1), (1, 2), (3, 3), (6, 1)]
    mine, theirs = s.check()
    assert mine == theirs
    # the deleted run went out as ContentDeleted: its letters are gone
    assert b"xyz" not in mine and b"Q" in mine


def test_a_text_insert_steps_over_tombstones_and_an_array_insert_does_not():
    base, ids, dead = text_room()
    s = Shadow(3_000_002, base, ids, dead, "text")
    s.writer.insert(0, "A")    # behind the head's tombstones
    s.writer.insert(11, "B")   # behind the gap in the middle
    s.writer.insert(len(s.writer), "C")
    mine, theirs = s.check()
    assert mine == theirs
    assert s.writer.origin[0] != -1  # its left is the last dead element


def test_varint_is_lib0s():
    from yjs_tpu.lib0 import encoding

    for n in (0, 1, 63, 64, 65, 127, 128, 8191, 8192, 999_999, -1, -64, -65):
        enc = encoding.Encoder()
        encoding.write_var_int(enc, n)
        assert plain_offline.varint(n) == enc.to_bytes(), n


def test_it_imports_nothing_of_the_program():
    tree = ast.parse(Path(plain_offline.__file__).read_text())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or "").split(".")[0])
    assert names <= {"__future__", "pickle", "random", "struct", "subprocess", "sys"}


def test_the_worker_processes_give_what_the_caller_gives():
    base, ids, dead = text_room()
    tasks = [
        {
            "client": 3_000_000 + k, "ids": ids, "dead": dead, "root": "text",
            "kind": "text", "shape": shape, "operations": 60,
            "seed": f"pool:{k}", "lengths": {"word": [2, 4], "delete": [1, 3]},
            "sequence": k == 0,
        }
        for k, shape in enumerate(("b2.2", "b2.3", "b2.4", "b2.3", "b2.2"))
    ]
    alone = Pool(0).map(tasks)
    assert alone == [history(t) for t in tasks]
    assert "ids" in alone[0] and "ids" not in alone[1]
    assert alone[1]["clock"] <= 60 * 4  # words of 2-4 characters
    pool = Pool(2)
    try:
        assert pool.map(tasks) == alone
        assert pool.map(tasks[:1]) == alone[:1]  # a worker with no task
    finally:
        pool.close()
    assert pool.workers == []
    # the same seed, the same update; another client id, another update
    again = history({**tasks[2], "client": 3_000_099})
    assert again["clock"] == alone[2]["clock"]
    assert again["update"] != alone[2]["update"]


@pytest.mark.parametrize("shape, low, high", [
    ("b2.2", 6000, 6000), ("b2.3", 34_000, 38_000), ("b2.4", 16_500, 19_500),
])
def test_a_sessions_elements_at_the_configurations_size(shape, low, high):
    """What one session of ``yws-offline`` brings back: N = 6000
    operations on a committed trace.  32 + 32 + 32 sessions of these and
    48 of 6000 array inserts are a wave's ~2.2 M elements."""
    from benchmarks.deployment import load_traces

    cfg = harness.load_data("configs", "yws-offline", (harness.HERE,))
    doc = Y.Doc(gc=False)
    Y.apply_update(doc, load_traces("distinct_traces")[0])
    plain = PlainText.of_items(items_of(doc))
    w = Writer(3_000_000, plain.ids, bytes(plain.dead))
    work_offline(w, shape, cfg["offline_operations"], random.Random(shape))
    assert low <= w.clock <= high
    update = w.update()
    assert 4500 <= w.n_structs <= 10_000 and 60_000 < len(update) < 170_000
    # a merged room stays far under the tables' width
    assert len(w.next) < cfg["room_shapes"]["cap"] // 3


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
