"""Generate the ProseMirror documents of the ``yws-prosemirror`` deployment.

``benchmarks/configs/yws-prosemirror.json`` names, under
``prosemirror_document_seeds``, the documents its typed rooms hold.  Each
is one writing session of ``SESSION`` ProseMirror transactions (the
length of the committed ``distinct`` traces) through
:class:`Binding`, a y-prosemirror-shaped binding on the CPU core
(``src/plugins/sync-plugin.js`` from memory: a node is a ``Y.XmlElement``
named by its type whose attributes are the node's attrs, a run of text
nodes is one ``Y.XmlText`` whose marks are format attributes ``{name:
attrs}``, the document is ``ydoc.getXmlFragment('prosemirror')``, a
ProseMirror transaction is one Yjs transaction).  The schema is
prosemirror-schema-basic + prosemirror-schema-list: ``paragraph``,
``heading{level}``, ``blockquote``, ``bullet_list``/``ordered_list`` >
``list_item`` > ``paragraph``, ``code_block``, ``horizontal_rule``,
inline ``image{src,alt}``; marks ``strong``, ``em``, ``code``,
``link{href}``.  The writer's document collects garbage, as a client's
does; every update it emits goes to a server ``Y.Doc`` that keeps
content (``gc=False``), whose own updates (its formatting clean-up after
a remote transaction) go back to the writer, as over a y-websocket
connection.  The fixture is the server's state, one V1 update.

Writes ``benchmarks/prosedocs/pm-<seed>.bin.z`` (zlib of the update) and
``benchmarks/prosedocs/documents.json``: per document its clients, state
vector, the digest of its XML string as ``benchmarks.oracle.text_digest``
makes it, rows and segments as the engine's host mirror counts them, the
text blocks a typist may work in, and the update's length and SHA-256.
A file that is there is kept.

Usage: python scripts/gen_prosemirror_fixtures.py [config]
"""

from __future__ import annotations

import hashlib
import json
import os
import random
import sys
import zlib
from concurrent.futures import ProcessPoolExecutor
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

OUT = ROOT / "benchmarks" / "prosedocs"
SESSION = 1500
FRAGMENT = "prosemirror"
_LETTERS = "etaoinshrdlucmfwypvbgkqjxz"
_ALIGN = ("left", "center", "right", "justify")
TEXTBLOCKS = ("paragraph", "heading", "code_block")


def clients(seed: int) -> tuple[int, int]:
    """The writer's two client ids: it reloads its page mid-session."""
    return (2_000_000 + 2 * seed, 2_000_001 + 2 * seed)


class Binding:
    """What y-prosemirror does to the ``Y.Doc`` for each ProseMirror
    transaction of a writer, restated over the CPU core's types.  The
    cursor is a text block and a count of characters before it."""

    def __init__(self, doc, client: int):
        self.doc = doc
        doc.client_id = client
        self.frag = doc.get_xml_fragment(FRAGMENT)
        self.block, self.index = None, 0

    # -- reading -----------------------------------------------------------

    def blocks(self, el=None) -> list:
        """Text blocks that hold one text and nothing else, or nothing."""
        from yjs_tpu.types.yxml import YXmlText

        out = []
        for kid in (self.frag if el is None else el).to_array():
            if isinstance(kid, YXmlText):
                continue
            if kid.node_name in TEXTBLOCKS:
                kids = kid.to_array()
                if not kids or (len(kids) == 1 and isinstance(kids[0], YXmlText)):
                    out.append(kid)
            else:
                out += self.blocks(kid)
        return out

    def text(self, block=None):
        kids = (self.block if block is None else block).to_array()
        return kids[0] if kids else None

    def chars(self, block=None) -> str:
        t = self.text(block)
        return "".join(
            op["insert"] for op in t.to_delta()
        ) if t is not None else ""

    def jump(self, block, index=None) -> None:
        self.block = block
        n = len(self.chars())
        self.index = n if index is None else max(0, min(index, n))

    # -- transactions ------------------------------------------------------

    def type(self, ch: str, marks: dict | None = None) -> None:
        from yjs_tpu.types.yxml import YXmlText

        def run(_t):
            t = self.text()
            if t is None:
                t = YXmlText()
                t.insert(0, ch, marks)
                self.block.insert(0, [t])
            else:
                t.insert(self.index, ch, marks)

        self.doc.transact(run)
        self.index += len(ch)

    def erase(self) -> bool:
        if self.text() is None or self.index == 0:
            return False
        self.text().delete(self.index - 1, 1)
        self.index -= 1
        return True

    def word(self):
        chars = self.chars()
        end = min(self.index, len(chars))
        while end > 0 and chars[end - 1] == " ":
            end -= 1
        start = end
        while start > 0 and chars[start - 1] != " ":
            start -= 1
        return (start, end - start) if end > start else None

    def toggle(self, key: str, value=None) -> bool:
        word = self.word()
        if word is None:
            return False
        start, length = word
        at, on = 0, True
        for op in self.text().to_delta():
            n = len(op["insert"])
            if at < start + length and at + n > start:
                on = on and key in op.get("attributes", {})
            at += n
        self.text().format(
            start, length, {key: None if on else ({} if value is None else value)}
        )
        return True

    def set_attr(self, key: str, value) -> None:
        self.block.set_attribute(key, value)

    def _element(self, name: str, attrs=None, kids=()):
        from yjs_tpu.types.yxml import YXmlElement

        el = YXmlElement(name)
        for k, v in (attrs or {}).items():
            el.set_attribute(k, v)
        if kids:
            el.insert(0, list(kids))
        return el

    def _text(self, runs):
        from yjs_tpu.types.yxml import YXmlText

        t = YXmlText()
        t.apply_delta([
            {"insert": s, "attributes": dict(m)} if m else {"insert": s}
            for s, m in runs
        ])
        return t

    def enter(self) -> None:
        """Split the block at the cursor."""
        block, t = self.block, self.text()
        chars = self.chars()
        inside = 0 < len(chars) and self.index < len(chars)

        def run(_t):
            runs = []
            if inside:
                at = 0
                for op in t.to_delta():
                    s, m = op["insert"], op.get("attributes", {})
                    cut = s[max(0, self.index - at):]
                    at += len(s)
                    if not cut:
                        continue
                    if runs and runs[-1][1] == m:
                        runs[-1] = (runs[-1][0] + cut, m)
                    else:
                        runs.append((cut, m))
                t.delete(self.index, len(chars) - self.index)
            name = block.node_name if inside else "paragraph"
            attrs = block.get_attributes() if inside else {}
            new = self._element(
                name, attrs, [self._text(runs)] if runs else ()
            )
            node = block
            if getattr(block.parent, "node_name", None) == "list_item":
                node = block.parent
                new_block, new = new, self._element("list_item", None, [new])
            else:
                new_block = new
            home = node.parent
            home.insert(home.to_array().index(node) + 1, [new])
            self.block = new_block

        self.doc.transact(run)
        self.index = 0

    # -- what only a session does -----------------------------------------

    def insert_block(self, after, name: str, attrs=None, runs=()) -> None:
        """A new text block after the top-level node ``after`` (None: at
        the document's start), with its text."""
        el = self._element(name, attrs, [self._text(runs)] if runs else ())
        self._insert_top(after, el)
        self.jump(el)

    def _insert_top(self, after, el) -> None:
        kids = self.frag.to_array()
        self.frag.insert(0 if after is None else kids.index(after) + 1, [el])

    def insert_list(self, after, name: str, items) -> None:
        """A list of ``items``, each the runs of its paragraph; an item
        that is a list itself nests (three parents deep)."""

        def item(runs):
            if runs and isinstance(runs[0], list):
                return self._element("list_item", None, [
                    self._element("paragraph", None, [self._text(runs[0])]),
                    self._element(name, None, [item(r) for r in runs[1:]]),
                ])
            return self._element(
                "list_item", None,
                [self._element("paragraph", None, [self._text(runs)])],
            )

        self._insert_top(after, self._element(name, None, [item(r) for r in items]))

    def insert_quote(self, after, paragraphs) -> None:
        self._insert_top(after, self._element("blockquote", None, [
            self._element("paragraph", None, [self._text(runs)])
            for runs in paragraphs
        ]))

    def insert_rule(self, after) -> None:
        self._insert_top(after, self._element("horizontal_rule"))

    def insert_image(self, src: str, alt: str) -> None:
        """An inline image at the cursor: the text cut there, the image,
        and the tail in a text of its own."""
        block, t = self.block, self.text()
        chars = self.chars()

        def run(_t):
            kids = [self._element("image", {"src": src, "alt": alt})]
            if t is not None and self.index < len(chars):
                kids.append(self._text([(chars[self.index:], {})]))
                t.delete(self.index, len(chars) - self.index)
            block.insert(1 if t is not None else 0, kids)

        self.doc.transact(run)

    def delete_top(self, node) -> None:
        self.frag.delete(self.frag.to_array().index(node), 1)


def _char(rng) -> str:
    return " " if rng.random() < 0.18 else rng.choice(_LETTERS)


def _words(rng, n: int) -> str:
    return " ".join(
        "".join(rng.choice(_LETTERS) for _ in range(rng.randint(2, 9)))
        for _ in range(n)
    )


def _runs(rng, n_words: int):
    """Text of ``n_words`` words with some of them marked."""
    runs = []
    for _ in range(max(1, n_words // 4)):
        marks = {}
        r = rng.random()
        if r < 0.12:
            marks = {"strong": {}}
        elif r < 0.2:
            marks = {"em": {}}
        elif r < 0.24:
            marks = {"code": {}}
        elif r < 0.28:
            marks = {"link": {"href": f"https://example.org/{rng.randint(1, 99)}"}}
        runs.append((_words(rng, 4) + " ", marks))
    return runs


def write_session(seed: int):
    """One writer's session; returns the server's document."""
    import yjs_tpu as Y

    rng = random.Random(f"prosemirror-doc:{seed}")
    first, second = clients(seed)
    server = Y.Doc(gc=False)
    sent = {"n": 0}

    def connect(client):
        writer = Y.Doc(gc=True)
        Y.apply_update(writer, Y.encode_state_as_update(server), "server")

        def up(update, origin, _doc):
            if origin != "server":
                sent["n"] += 1
                Y.apply_update(server, update, writer)

        writer.on("update", up)
        return writer, Binding(writer, client)

    heard = {}

    def down(update, origin, _doc):
        w = heard.get("writer")
        if w is not None:
            Y.apply_update(w, update, "server")

    server.on("update", down)
    writer, b = connect(first)
    heard["writer"] = writer

    # the outline: a title, sections of paragraphs, lists, a quote, code
    b.insert_block(None, "heading", {"level": 1}, [(_words(rng, 5), {})])
    last = b.block
    for _ in range(rng.randint(6, 9)):
        b.insert_block(last, "heading", {"level": rng.randint(2, 3)},
                       [(_words(rng, 3), {})])
        last = b.block
        for _ in range(rng.randint(2, 4)):
            b.insert_block(last, "paragraph", None, _runs(rng, rng.randint(12, 40)))
            last = b.block
        r = rng.random()
        if r < 0.4:
            name = rng.choice(("bullet_list", "ordered_list"))
            items = [_runs(rng, rng.randint(3, 8)) for _ in range(rng.randint(2, 4))]
            items.append([_runs(rng, 4)] + [_runs(rng, 4) for _ in range(2)])
            b.insert_list(last, name, items)
            last = b.frag.to_array()[b.frag.to_array().index(last) + 1]
        elif r < 0.6:
            b.insert_quote(last, [_runs(rng, 10) for _ in range(rng.randint(1, 2))])
            last = b.frag.to_array()[b.frag.to_array().index(last) + 1]
        elif r < 0.75:
            b.insert_block(last, "code_block", None, [(_words(rng, 12), {})])
            last = b.block
        elif r < 0.85:
            b.insert_rule(last)
            last = b.frag.to_array()[b.frag.to_array().index(last) + 1]

    # the writing: runs of typing and erasing at a cursor that moves,
    # with structure edits among them
    run_left, erasing = 0, False
    while sent["n"] < SESSION:
        if sent["n"] == SESSION // 2 and b.doc.client_id == first:
            # the page reloads: a new client id, the document from the server
            heard["writer"] = None
            writer, b = connect(second)
            heard["writer"] = writer
            run_left = 0
        blocks = b.blocks()
        if b.block is None or b.block not in blocks:
            b.jump(rng.choice(blocks), None)
        r = rng.random()
        if run_left > 0:
            run_left -= 1
            if erasing:
                if not b.erase():
                    b.type(_char(rng))
            else:
                b.type(_char(rng))
            continue
        if r < 0.80:
            erasing = rng.random() < 0.3
            run_left = rng.randint(2, 8) if erasing else rng.randint(4, 18)
            if rng.random() < 0.25:
                blk = rng.choice(blocks)
                b.jump(blk, rng.randint(0, len(b.chars(blk))))
        elif r < 0.86:
            if b.block.node_name != "code_block":
                b.enter()
        elif r < 0.93:
            key = rng.choice(("strong", "strong", "em", "em", "code", "link"))
            value = (
                {"href": f"https://example.org/{rng.randint(1, 99)}"}
                if key == "link" else None
            )
            if not b.toggle(key, value):
                b.type(_char(rng))
        elif r < 0.96:
            if b.block.node_name == "heading":
                b.set_attr("level", rng.randint(1, 6))
            elif b.block.node_name == "paragraph":
                b.set_attr("textAlign", rng.choice(_ALIGN))
            else:
                b.type(_char(rng))
        elif r < 0.975:
            if b.block.node_name == "paragraph" and b.block.parent is b.frag:
                b.insert_image(f"img/{rng.randint(1, 500)}.png", _words(rng, 2))
                b.block = None
            else:
                b.type(_char(rng))
        elif r < 0.99:
            top = b.frag.to_array()
            after = rng.choice(top)
            if rng.random() < 0.5:
                b.insert_block(after, "paragraph", None, _runs(rng, 6))
            else:
                b.insert_list(after, "bullet_list",
                              [_runs(rng, 4) for _ in range(2)])
        else:
            top = b.frag.to_array()
            if len(top) > 8:
                victim = rng.choice(top[1:])
                if b.block is not None and _within(b.block, victim):
                    b.block = None
                b.delete_top(victim)
    return server


def _within(node, top) -> bool:
    while node is not None:
        if node is top:
            return True
        node = getattr(node, "parent", None)
    return False


def describe(seed: int, update: bytes) -> dict:
    import yjs_tpu as Y
    from benchmarks.generators.prosemirror import tree_of
    from benchmarks.oracle import text_digest
    from benchmarks.plain_prosemirror import PlainDoc
    from yjs_tpu.ops.columns import DocMirror

    doc = Y.Doc(gc=False)
    Y.apply_update(doc, update)
    if Y.merge_updates([Y.encode_state_as_update(doc)]) != Y.merge_updates([update]):
        raise SystemExit(f"pm-{seed}: a replay changes the document")
    sv = Y.decode_state_vector(Y.encode_state_vector(doc))
    xml = doc.get_xml_fragment(FRAGMENT).to_string()
    plain = PlainDoc.of_tree(tree_of(doc), sv)
    if plain.xml() != xml:
        raise SystemExit(f"pm-{seed}: the plain tree reads another XML string")
    mirror = DocMirror(FRAGMENT)
    mirror.ingest(update)
    mirror.prepare_step()
    return {
        "seed": seed, "clients": list(clients(seed)),
        "state_vector": sorted(sv.items()),
        "xml_digest": text_digest(xml), "xml_chars": len(xml),
        "rows": mirror.n_rows, "segments": mirror.n_segs,
        "text_blocks": len(plain.blocks()),
        "update_bytes": len(update),
        "update_sha256": hashlib.sha256(update).hexdigest(),
    }


def make(seed: int) -> dict:
    import yjs_tpu as Y

    path = OUT / f"pm-{seed}.bin.z"
    if path.exists():
        update = zlib.decompress(path.read_bytes())
    else:
        update = Y.encode_state_as_update(write_session(seed))
        path.write_bytes(zlib.compress(update, 9))
    return describe(seed, update)


def main() -> None:
    config = sys.argv[1] if len(sys.argv) > 1 else "yws-prosemirror"
    cfg = json.loads(
        (ROOT / "benchmarks" / "configs" / f"{config}.json").read_text()
    )
    OUT.mkdir(exist_ok=True)
    seeds = cfg["prosemirror_document_seeds"][: cfg["prosemirror_documents"]]
    with ProcessPoolExecutor(max_workers=os.cpu_count()) as pool:
        documents = list(pool.map(make, seeds))
    table = {
        "what": "the ProseMirror documents of benchmarks/configs/"
        "yws-prosemirror.json, made by `python scripts/"
        "gen_prosemirror_fixtures.py`: per document the state vector, the "
        "first 24 hex digits of the SHA-256 of the XML string of its "
        "fragment 'prosemirror' as a CPU Y.Doc replays the one update of "
        "pm-<seed>.bin.z, and the rows and segments of its host mirror",
        "documents": {f"pm-{d['seed']}": d for d in documents},
    }
    (OUT / "documents.json").write_text(json.dumps(table, indent=1) + "\n")
    rows = sorted(d["rows"] for d in documents)
    segs = sorted(d["segments"] for d in documents)
    print(
        f"{len(documents)} documents: rows {rows[0]} / {rows[len(rows) // 2]} / "
        f"{rows[-1]}, segments {segs[0]} / {segs[len(segs) // 2]} / {segs[-1]}, "
        f"{sum(d['update_bytes'] for d in documents)} bytes"
    )


if __name__ == "__main__":
    main()
