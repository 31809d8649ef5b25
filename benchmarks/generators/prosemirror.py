"""Traffic generator ``prosemirror``: a closed-loop flood of ProseMirror
transactions into rooms that hold ProseMirror documents.

The deployment's configuration states ``prosemirror_rooms`` and the
seeds of the committed documents under ``benchmarks/prosedocs/``: trees
of ``Y.XmlElement`` and ``Y.XmlText`` under the ``Y.XmlFragment``
``prosemirror``, as y-prosemirror writes them.  The harness deals every
room one root ``Y.Text``; at set-up, untimed, this generator homes a
document in each typed room it picks (release, ``receive_update``,
``flush()``; ``cell.history`` holds the bytes really sent), as
``longtail`` homes its long documents: room k of the pick holds document
k mod ``prosemirror_documents``, so the hot rooms (``duet_rooms`` then
``solo_rooms``) hold every document the same number of times over in
every seed, and the rest take no traffic afterwards (they leave
``cell.touched``, so that the oracle's sample of untouched rooms draws
from them).

Each hot room has plain typists of the benchmark's own
(``benchmarks/plain_prosemirror.py``) that start from the room's
document, read off one replay of it.  An update is one ProseMirror
transaction, as y-prosemirror sends it.  A unit is a fixed number of
updates, then one ``flush()`` whose fan-out reaches the room's peers; a
circuit is ``units_per_circuit`` units; the timed interval is one
circuit and the window whole circuits.

Every unit has the same make-up, whatever the seed (``unit`` in the
parameters): ``duets`` duet rooms in which both typists type a character
at once from one state, each in a block of its own (two updates), then
``typed`` characters typed, ``erased`` backspaces, ``enter`` block
splits, ``marks`` mark toggles (``strong`` or ``em`` over the word
behind the cursor) and ``attrs`` attribute sets (a heading's ``level``,
a paragraph's ``textAlign``), one solo room each.  A solo typist works
in runs as the flood's: ``typing_run`` visits that type, then
``erasing_run`` that erase; before one typing run in ``jump_every_runs``
it moves to another block.  The structure operations take the place of
``typed_structure`` typing visits and of the rest erasing visits of a
unit, drawn by the seed.  A typist that cannot do what its visit asks
where it stands (nothing to erase at a block's start, no word, Enter in
a ``code_block``, an attribute on a block that has none) moves first to
a block where it can.

Rehearsal is ``typing``'s: whole circuits until one met no new program
and left the provider's SLO windows settled, with a ladder of other
unit sizes after the first, and after the ladder one wide unit
(``wide_unit``: more backspaces, more Enters and each inside a text),
whose flush is a bucket of lanes wider in deletes and in list heads
than a unit's: a window's widest flush outdoes the rehearsal's by a
lane in one run of 14.

Into ``cell.counts``, summed over the window's flushes, what this
cell's per-layer readers read from ``last_flush_metrics`` where the
program keeps them: ``rows_planned``, ``rows_nested``, ``rows_format``,
``rows_attr``, ``rows_type``, ``segs_created``, ``lww_overwritten``,
``format_cleanup_deleted``, ``emit_batched``, ``emit_fallback``; and
the largest ``n_segs_max`` beside ``seg_cap``.

After the window every hot room's XML string, from the provider by the
root's name, from the host mirror and walked out of the device's rows,
is held to its typists' own; a seeded sample of the idle typed rooms'
to their documents' committed entries; a room that differs goes to
``cell.refused`` (limit 0).  ``oracle.check`` replays its sample of all
of them on a CPU ``Y.Doc`` besides; :meth:`Generator.replayed_xml`
holds the XML string of such replays to the provider's.
"""

from __future__ import annotations

import gc
import json
import random
import zlib
from pathlib import Path
from typing import NamedTuple

from benchmarks.deployment import BenchError, pick_rooms
from benchmarks.oracle import Oracle, text_digest
from benchmarks.plain_prosemirror import ROOT, PlainDoc, Typist, text_of

PROSEDOCS = Path(__file__).resolve().parent.parent / "prosedocs"
_LETTERS = "etaoinshrdlucmfwypvbgkqjxz"
_ALIGN = ("left", "center", "right", "justify")
SUMMED = (
    "rows_planned", "rows_nested", "rows_format", "rows_attr", "rows_type",
    "segs_created", "lww_overwritten", "format_cleanup_deleted",
    "emit_batched", "emit_fallback",
)


def _char(rng: random.Random) -> str:
    return " " if rng.random() < 0.18 else rng.choice(_LETTERS)


def tree_of(doc, root: str = ROOT) -> list:
    """The children of a replayed document's fragment, as
    :meth:`PlainDoc.of_tree` takes them."""

    def node(item):
        content = item.content
        t = getattr(content, "type", None)
        head = (item.id.client, item.id.clock, bool(item.deleted))
        if t is None:  # content collected, or not a type: a dead stub
            return ("element", *head[:2], True, "", {}, [])
        if type(t).__name__ == "YXmlText":
            items, n = [], t._start
            while n is not None:
                c = n.content
                fmt = (c.key, c.value) if hasattr(c, "key") else None
                string = None if fmt else (
                    getattr(c, "str", None) or "\0" * n.length
                )
                items.append(
                    (n.id.client, n.id.clock, bool(n.deleted), string, fmt)
                )
                n = n.right
            return ("text", *head, items)
        attrs = {
            key: (
                it.content.get_content()[-1] if not it.deleted else None,
                it.id.client, it.id.clock + it.length - 1, bool(it.deleted),
            )
            for key, it in t._map.items()
        }
        return ("element", *head, t.node_name, attrs, kids(t))

    def kids(t):
        out, n = [], t._start
        while n is not None:
            out.append(node(n))
            n = n.right
        return out

    return kids(doc.get(root))


class Document(NamedTuple):
    name: str      # pm-<seed>, as the fixture's file
    update: bytes  # the document, one update
    entry: dict    # its committed entry: state vector, XML digest


def documents(cfg: dict) -> list[Document]:
    table = json.loads((PROSEDOCS / "documents.json").read_text())["documents"]
    seeds = cfg["prosemirror_document_seeds"][: cfg["prosemirror_documents"]]
    if len(seeds) != cfg["prosemirror_documents"]:
        raise BenchError(f"{cfg['name']}: too few document seeds")
    out = []
    for seed in seeds:
        name = f"pm-{seed}"
        update = zlib.decompress((PROSEDOCS / f"{name}.bin.z").read_bytes())
        out.append(Document(name, update, table[name]))
    return out


class HotRoom:
    """One hot room's clients: the tree as they hold it, their cursors."""

    def __init__(self, index: int, room, doc: PlainDoc, typists: int, rng):
        self.room, self.doc = room, doc
        blocks = rng.sample(doc.blocks(), typists)  # each a block of its own
        self.typists = [
            Typist(doc, 1_000_000 + 2 * index + k, block)
            for k, block in enumerate(blocks)
        ]


class Generator:
    def __init__(self, params: dict, cell):
        self.p = p = params
        self.cell = cell
        cfg = cell.cfg
        self.rng = rng = random.Random(f"prosemirror:{cell.seed}")
        n_solo, n_duet = int(p["solo_rooms"]), int(p["duet_rooms"])
        n_rooms = int(cfg["prosemirror_rooms"])
        self.documents = documents(cfg)
        n_docs = len(self.documents)
        if (n_solo + n_duet) % n_docs or n_rooms < n_solo + n_duet:
            raise BenchError(
                f"prosemirror: {n_solo + n_duet} hot of {n_rooms} typed rooms "
                f"do not hold {n_docs} documents equally often"
            )
        picked = pick_rooms(cell.plan, cfg, "distinct", n_rooms, rng)
        # guid -> the document the room holds
        self.home = {
            room.guid: self.documents[k % n_docs]
            for k, room in enumerate(picked)
        }
        # the same documents are duet rooms in every seed
        self.duet_specs = picked[:n_duet]
        self.solo_specs = picked[n_duet : n_duet + n_solo]
        self.idle_specs = picked[n_duet + n_solo :]
        u = p["unit"]
        self.duets = int(u["duets"])
        self.typed, self.erased = int(u["typed"]), int(u["erased"])
        self.structure = (
            ["enter"] * int(u["enter"]) + ["mark"] * int(u["marks"])
            + ["attr"] * int(u["attrs"])
        )
        self.typed_structure = int(u["typed_structure"])
        self.units = int(p["units_per_circuit"])
        solo_a_unit = self.typed + self.erased + len(self.structure)
        self.updates_a_unit = 2 * self.duets + solo_a_unit
        self.run_t, self.run_e = int(p["typing_run"]), int(p["erasing_run"])
        self.jump_every = int(p["jump_every_runs"])
        period = self.run_t + self.run_e
        lap_units = n_solo // solo_a_unit
        typing_a_unit = self.typed + self.typed_structure
        if (
            n_solo % period or n_solo % solo_a_unit
            or n_solo // period * self.run_t != lap_units * typing_a_unit
            or not 0 <= self.typed_structure <= len(self.structure)
        ):
            raise BenchError(
                f"prosemirror: {n_solo} solo rooms in runs of {self.run_t} "
                f"typing and {self.run_e} erasing visits do not deal units "
                f"of {self.typed} + {self.erased} + {len(self.structure)}"
            )
        self.lap = 0
        self.solo_units: list[list[tuple[HotRoom, str]]] = []
        self.duet_lap: list[HotRoom] = []
        self.circuit: list[list[tuple[str, bytes]]] = []
        self.window_rates: list[float] = []
        self.window_work = 0
        self.made = dict.fromkeys(
            ("duet", "typed", "erased", "enter", "mark", "attr", "moved", "wide"), 0
        )
        self.n_segs_max = 0

    # -- set-up ------------------------------------------------------------

    def prepare(self) -> None:
        """Every typed room given its document, and the typists, each
        room's from one replay of its document."""
        cell = self.cell
        t = cell.clock()
        try:  # a program that reads a room's one root name cannot serve these
            cell.prov.xml_string(next(iter(self.home)), ROOT)
        except TypeError as e:
            raise BenchError(
                f"this provider reads no root by its name ({e}): it cannot "
                "serve a room whose document is the fragment 'prosemirror'"
            ) from e
        homing = [(guid, doc.update) for guid, doc in self.home.items()]
        for guid, _update in reversed(homing):
            cell.release(guid)
        cell.send_all(homing)
        cell.flush()
        # the first client back brings what the server already holds: the
        # idle rooms are sent their document once more (no news).  Their
        # history is then two updates, and ``oracle.check`` replays it:
        # a room with one update in its history it holds to the state of
        # the trace the harness dealt it (PERF.md, Open questions)
        cell.send_all(
            [(r.guid, self.home[r.guid].update) for r in self.idle_specs],
            news=False,
        )
        cell.flush()  # and the compaction a load is followed by
        cell.fence()
        # the idle typed rooms take no traffic from here on
        cell.touched.difference_update(r.guid for r in self.idle_specs)
        t_homed = cell.clock()
        bases: dict[str, PlainDoc] = {}

        def hot(i, room, typists):
            doc = self.home[room.guid]
            if doc.name not in bases:
                import yjs_tpu as Y

                replay = Oracle.replay([doc.update])
                sv = Y.decode_state_vector(Y.encode_state_vector(replay))
                bases[doc.name] = PlainDoc.of_tree(tree_of(replay), sv)
            return HotRoom(i, room, bases[doc.name].copy(), typists, self.rng)

        self.duet = [hot(i, r, 2) for i, r in enumerate(self.duet_specs)]
        self.solo = [
            hot(len(self.duet) + i, r, 1) for i, r in enumerate(self.solo_specs)
        ]
        eng = cell.prov.engine
        segs = sorted(
            eng.mirrors[cell.prov.doc_id(guid)].n_segs for guid in self.home
        )
        cell.log(
            f"prosemirror: {len(self.home)} typed rooms homed over "
            f"{len(self.documents)} documents in {t_homed - t:.3f} s "
            f"(segments {segs[0]} / {segs[len(segs) // 2]} / {segs[-1]}, "
            f"seg_cap {eng._seg_cap}); {len(self.solo)} solo and "
            f"{len(self.duet)} duet rooms (clients built in "
            f"{cell.clock() - t_homed:.3f} s), {self.updates_a_unit} updates "
            f"a unit, {self.units} units a circuit"
        )

    # -- typing ------------------------------------------------------------

    def _deal_lap(self) -> None:
        """One visit to every solo room, dealt to units of the fixed
        make-up: which rooms type and which erase follows from the lap;
        the seed draws which visits of a unit are its structure
        operations."""
        period, rng = self.run_t + self.run_e, self.rng
        typing, erasing = [], []
        for k, room in enumerate(self.solo):
            at = (k + self.lap) % period
            if at >= self.run_t:
                erasing.append((room, "erased"))
            elif at == 0 and ((k + self.lap) // period) % self.jump_every == 0:
                typing.append((room, "jumped"))
            else:
                typing.append((room, "typed"))
        self.lap += 1
        rng.shuffle(typing)
        rng.shuffle(erasing)
        n_t = self.typed + self.typed_structure
        n_e = self.erased + len(self.structure) - self.typed_structure
        for u in range(len(typing) // n_t):
            kinds = list(self.structure)
            rng.shuffle(kinds)
            t_part = typing[u * n_t : (u + 1) * n_t]
            e_part = erasing[u * n_e : (u + 1) * n_e]
            # the first visits of each part are the unit's structure
            # operations (the parts are shuffled already)
            k_t = self.typed_structure
            k_e = len(kinds) - k_t
            unit = (
                [(room, kinds[j]) for j, (room, _k) in enumerate(t_part[:k_t])]
                + t_part[k_t:]
                + [
                    (room, kinds[k_t + j])
                    for j, (room, _k) in enumerate(e_part[:k_e])
                ]
                + e_part[k_e:]
            )
            rng.shuffle(unit)
            self.solo_units.append(unit)

    def _move(self, typist: Typist, fits) -> None:
        """To a block, and a place in it, where ``fits(typist)``."""
        rng, doc = self.rng, typist.doc
        blocks = doc.blocks()
        for _ in range(64):
            block = rng.choice(blocks)
            text = text_of(block)
            n = text.live() if text is not None else 0
            typist.jump(block, rng.randint(0, n))
            if fits(typist):
                self.made["moved"] += 1
                return
        raise BenchError("prosemirror: no block fits a typist's visit")

    def _visit(self, room: HotRoom, kind: str) -> bytes:
        rng, typist = self.rng, room.typists[0]
        if kind == "jumped":
            self._move(typist, lambda t: True)
            kind = "typed"
        if kind == "typed":
            self.made["typed"] += 1
            return typist.type(_char(rng))
        if kind == "erased":
            if typist.index == 0:
                self._move(typist, lambda t: t.index > 0)
            self.made["erased"] += 1
            return typist.erase()
        if kind == "split":  # the wide unit's: an Enter inside a text
            self._move(
                typist,
                lambda t: t.block.name != "code_block"
                and 0 < t.index < text_of(t.block).live(),
            )
            kind = "enter"
        if kind == "enter":
            if typist.block.name == "code_block":
                self._move(typist, lambda t: t.block.name != "code_block")
            self.made["enter"] += 1
            return typist.enter()
        if kind == "mark":
            if typist.word() is None:
                self._move(typist, lambda t: t.word() is not None)
            self.made["mark"] += 1
            return typist.toggle(rng.choice(("strong", "em")))
        if typist.block.name == "code_block":
            self._move(typist, lambda t: t.block.name != "code_block")
        self.made["attr"] += 1
        block = typist.block
        if block.name == "heading":
            held = block.attrs.get("level", [None])[0]
            return typist.set_attr(
                "level", rng.choice([n for n in range(1, 7) if n != held])
            )
        held = block.attrs.get("textAlign", [None])[0]
        return typist.set_attr(
            "textAlign", rng.choice([a for a in _ALIGN if a != held])
        )

    def _type_unit(self) -> list[tuple[str, bytes]]:
        rng, out = self.rng, []
        for _ in range(self.duets):
            if not self.duet_lap:
                self.duet_lap = list(self.duet)
                rng.shuffle(self.duet_lap)
            room = self.duet_lap.pop()
            # each in a block of its own: the two updates name no common
            # neighbour, and either order of arrival gives one tree
            out += [(room.room.guid, t.type(_char(rng))) for t in room.typists]
            self.made["duet"] += 2
        if not self.solo_units:
            self._deal_lap()
        for room, kind in self.solo_units.pop():
            out.append((room.room.guid, self._visit(room, kind)))
        return out

    def _type_units(self, n: int) -> list[list[tuple[str, bytes]]]:
        return [self._type_unit() for _ in range(n)]

    # -- rehearsal (as ``typing``'s) ---------------------------------------

    def _held(self) -> tuple[int, int] | None:
        try:
            w = self.cell.prov.slo_snapshot()["windows"]
            return int(w["long"]["total"]), int(w["short"]["total"])
        except (AttributeError, KeyError, TypeError):
            return None

    def rehearse(self) -> None:
        cell, p = self.cell, self.p
        within = float(p["settled_within"])
        held, settled = self._held(), False
        for lap in range(int(p["rehearsal_circuits_max"])):
            self.untimed(lap)
            before = cell.compiles.programs
            t = cell.clock()
            work = self.timed(lap, rehearsal=True)
            cell.fence()
            seconds = cell.clock() - t
            met = cell.compiles.programs - before
            was, held = held, self._held()
            settled = held is None or (
                held[0] <= was[0] and abs(held[1] - was[1]) <= within * was[1]
            )
            cell.log(
                f"rehearsal circuit {lap}: {met} programs first met, "
                f"{seconds:.3f} s, {work / seconds:.1f} updates/s, the "
                f"provider's SLO windows hold {held}"
            )
            if lap == 0:
                self._ladder()
            if settled and met == 0:
                break
        if not settled:
            cell.log(
                "rehearsal: the provider's SLO windows were still filling "
                "after the last circuit: the window begins all the same"
            )
        eng = cell.prov.engine
        widest = max(
            eng.mirrors[cell.prov.doc_id(r.room.guid)].n_segs
            for r in self.duet + self.solo
        )
        cell.log(
            f"the window opens with {widest} segments in the widest room "
            f"under seg_cap {eng._seg_cap}: {eng._seg_cap - widest} to grow into"
        )
        gc.collect()
        gc.freeze()

    def _ladder(self) -> None:
        """Units of other sizes (``ladder``, as shares of the unit's own):
        a flush's lane widths follow its count of link writes, list
        heads and deletes, and the ladder meets the neighbouring
        buckets' programs here and not in the window.  A step ends
        early rather than hold two transactions of one solo room."""
        cell = self.cell
        sizes = [
            max(1, round(share * self.updates_a_unit))
            for share in self.p["ladder"]
        ]
        before = cell.compiles.programs
        t = cell.clock()
        flat = [
            u
            for unit in self._type_units(-(-sum(sizes) // self.updates_a_unit))
            for u in unit
        ]
        duet = {r.room.guid for r in self.duet}
        steps = 0
        while flat:  # everything typed is sent: later transactions build on it
            size = sizes[steps % len(sizes)]
            step, seen = [], set()
            while flat and len(step) < size:
                guid = flat[0][0]
                if guid in seen and guid not in duet:
                    break
                seen.add(guid)
                step.append(flat.pop(0))
            with cell.unit():
                cell.send_all(step)
                self._flush()
            steps += 1
        wide = self._wide_unit()
        cell.fence()
        cell.log(
            f"ladder of {steps} units of {sizes} updates and a wide unit of "
            f"{wide}: {cell.compiles.programs - before} programs first met, "
            f"{cell.clock() - t:.3f} s"
        )

    def _wide_unit(self) -> dict:
        """One unit of another make-up (``wide_unit``), a solo room each:
        fewer characters typed, more backspaces, and more Enters, each
        inside a text (the tail deleted and written again under a new
        element with its attributes).  Its flush deletes more rows and
        writes more list heads than a unit of the window will, by a
        bucket of lanes each, and links about as many: the program it
        meets covers them all.  The widest flush of a run keeps being
        outdone (a long tail split off by an Enter: 80 rows deleted in
        one flush of 14,000 where every other stayed under 64; 15 list
        heads where one in 14 has more than 8), and without this unit
        one window in 14 met that program."""
        cell, wide = self.cell, self.p["wide_unit"]
        kinds = [
            kind for key, kind in
            (("typed", "typed"), ("erased", "erased"), ("enter", "split"))
            for _ in range(int(wide[key]))
        ]
        rooms = self.rng.sample(self.solo, len(kinds))
        made = dict(self.made)  # ``made`` counts the units of the one make-up
        step = [
            (room.room.guid, self._visit(room, kind))
            for room, kind in zip(rooms, kinds)
        ]
        self.made = {**made, "wide": len(step)}
        with cell.unit():
            cell.send_all(step)
            self._flush()
        return dict(wide)

    # -- sending -----------------------------------------------------------

    def _flush(self) -> None:
        cell = self.cell
        cell.flush()
        if not cell.in_window:
            return
        m = cell.prov.engine.last_flush_metrics
        for key in SUMMED:
            if key in m:
                cell.counts[key] = cell.counts.get(key, 0) + m[key]
        self.n_segs_max = max(self.n_segs_max, m.get("n_segs_max", 0))

    def untimed(self, i: int) -> None:
        """The next circuit, typed."""
        self.circuit = self._type_units(self.units)

    def timed(self, i: int, rehearsal: bool = False) -> int:
        cell = self.cell
        t = cell.clock()
        work = 0
        for unit in self.circuit:
            with cell.unit():
                cell.send_all(unit)
                self._flush()
            work += len(unit)
        self.circuit = []
        if not rehearsal:
            cell.fence()
            self.window_rates.append(work / (cell.clock() - t))
            self.window_work += work
        return work

    # -- after the window --------------------------------------------------

    def finish(self) -> None:
        """Every hot room's XML string against its typists', a sample of
        the idle typed rooms' against their documents' entries: by the
        root's name from the provider, from the host mirror and from the
        device's rows."""
        gc.unfreeze()
        cell, prov = self.cell, self.cell.prov
        eng = prov.engine
        cell.counts["n_segs_max"] = self.n_segs_max
        cell.counts["seg_cap"] = eng._seg_cap
        cell.log(
            "circuit rates in the window, updates/s: "
            + " ".join(f"{r:.1f}" for r in self.window_rates)
        )
        cell.log(
            f"transactions typed since set-up: {self.made}; in the window "
            + " ".join(f"{k} {cell.counts.get(k, 0)}" for k in SUMMED)
            + f" n_segs_max {self.n_segs_max} seg_cap {eng._seg_cap}"
        )
        t = cell.clock()
        rng = random.Random(f"prosemirror-check:{cell.seed}")
        sample = rng.sample(
            self.idle_specs,
            min(int(self.p["idle_device_sample"]), len(self.idle_specs)),
        )
        walked = {r.guid for r in sample}
        differ = 0
        expect = [
            (room.room.guid, text_digest(room.doc.xml()), True)
            for room in self.duet + self.solo
        ] + [
            (r.guid, self.home[r.guid].entry["xml_digest"], r.guid in walked)
            for r in self.idle_specs
        ]
        for guid, digest, from_device in expect:
            ok = prov.has_doc(guid)
            for device in (False, True) if from_device else (False,):
                eng.export_from_device = device
                ok = ok and text_digest(prov.xml_string(guid, ROOT)) == digest
            eng.export_from_device = False
            if not ok:
                differ += 1
                cell.refused.append(guid)
        differ += self.replayed_xml()
        cell.log(
            f"prosemirror: {len(expect)} typed rooms' XML strings held to "
            f"their typists' or their documents' ({len(self.duet + self.solo)} "
            f"hot and {len(sample)} idle also from the device's rows) in "
            f"{cell.clock() - t:.3f} s, {differ} differ"
        )

    def replayed_xml(self) -> int:
        """A seeded sample of hot rooms replayed on a CPU ``Y.Doc`` fed
        the bytes sent: its fragment's string against the provider's."""
        cell, prov = self.cell, self.cell.prov
        rng = random.Random(f"prosemirror-replay:{cell.seed}")
        rooms = rng.sample(
            self.duet + self.solo,
            min(int(self.p["replay_sample"]), len(self.duet + self.solo)),
        )
        differ = 0
        for room in rooms:
            guid = room.room.guid
            doc = Oracle.replay(cell.history[guid])
            want = doc.get_xml_fragment(ROOT).to_string()
            if prov.xml_string(guid, ROOT) != want or room.doc.xml() != want:
                differ += 1
                cell.refused.append(guid)
        return differ

    def work(self) -> int:
        return self.window_work

    def views(self) -> dict[str, tuple[dict, str]]:
        """What every typed room's clients hold: state vector, and the
        root ``text`` these rooms do not have."""
        return {
            room.room.guid: (room.doc.sv, "")
            for room in self.duet + self.solo
        }
