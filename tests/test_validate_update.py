"""The verdict of ``validate_update`` is ``decode_update_refs``'s.

``validate_updates`` asks the native scanner's count pass, which builds
nothing, and hands what that refuses to ``decode_update_refs``, whose
pure-Python decoder arbitrates.  So on every input it must accept
exactly what ``decode_update_refs`` decodes, give the summary that the
decoded refs give, refuse with the decoder's own words, and read the
same for a batch as for its members one at a time.  A case a corpus.
"""

from __future__ import annotations

import base64
import json
import random
from pathlib import Path

import pytest

import yjs_tpu as Y
from benchmarks.deployment import load_traces
from yjs_tpu.ops.columns import decode_update_refs
from yjs_tpu.resilience import ChaosConfig, ChaosInjector
from yjs_tpu.updates import InvalidUpdate, validate_update, validate_updates

FIXTURES = Path(__file__).parent / "fixtures"
SEED = 43


def traces(stem: str, n: int = 64) -> list[bytes]:
    return load_traces(stem)[:n]


def as_v2(update: bytes) -> bytes:
    doc = Y.Doc(gc=False)
    Y.apply_update(doc, update)
    return Y.encode_state_as_update_v2(doc)


def both(updates: list[bytes]) -> list[tuple[bytes, bool]]:
    """Every update as it is and encoded again as V2."""
    return [(u, False) for u in updates] + [(as_v2(u), True) for u in updates]


def compat() -> list[bytes]:
    docs = json.loads((FIXTURES / "compat_v1.json").read_text())
    return [base64.b64decode(d["oldDoc"]) for d in docs.values()]


def mixed_doc() -> bytes:
    """A few structs of every content kind a client writes today, from
    two clients, with a delete set."""
    a, b = Y.Doc(gc=False), Y.Doc(gc=False)
    a.client_id, b.client_id = 7, 300
    text = a.get_text("text")
    text.insert(0, "héllo \U0001f600")
    text.format(0, 2, {"bold": True})
    text.insert_embed(1, {"img": "x"})
    a.get_map("map").set("key", [1, 2.5, None, "s", {"k": b"\x00\x01"}])
    a.get_array("arr").insert(0, [b"\x01\x02", 3])
    el = Y.XmlElement("p")
    a.get_xml_fragment("xml").insert(0, [el])
    el.set_attribute("align", "left")
    el.insert(0, [Y.XmlText("t")])
    Y.apply_update(b, Y.encode_state_as_update(a))
    b.get_text("text").delete(2, 3)
    b.get_map("map").set("key", 2)
    return Y.encode_state_as_update(b)


def small() -> list[tuple[bytes, bool]]:
    """The sample whose every bit and every prefix is tried."""
    return both([(FIXTURES / "corrupt" / "valid_base.bin").read_bytes(),
                 mixed_doc()])


def wide_doc(name: str) -> Y.Doc:
    """A root text and a map entry under ``name``: the two strings the
    ref scanner decodes eagerly (``parent_name``, ``parent_sub``)."""
    doc = Y.Doc(gc=False)
    doc.client_id = 7
    doc.get_text(name).insert(0, "x")
    doc.get_map(name + "m").set(name, 1)
    return doc


# a well-formed character and the ill-formed sequences a loose scanner
# (continuation bytes alone) takes for it: overlong forms, a surrogate,
# a code point past U+10FFFF; then sequences no scanner takes
_SWAPS = [
    ("ࠀ", b"\xe0\xa0\x80", [b"\xe0\x80\x80", b"\xed\xa0\x80",
                                b"\xe0\xa0\x41", b"\x80\xa0\x80"]),
    ("\U00010000", b"\xf0\x90\x80\x80", [b"\xf0\x80\x80\x80",
                                        b"\xf4\x90\x80\x80",
                                        b"\xf5\x90\x80\x80"]),
    ("é", b"\xc3\xa9", [b"\xc0\xa9", b"\xc1\xa9", b"\xc3\x29"]),
]


def corpus_bad_utf8() -> list[tuple[bytes, bool]]:
    out = []
    for ch, good, bads in _SWAPS:
        doc = wide_doc(ch)
        for v2, enc in ((False, Y.encode_state_as_update),
                        (True, Y.encode_state_as_update_v2)):
            u = enc(doc)
            assert u.count(good) >= 2
            out.append((u, v2))
            for bad in bads:
                out.append((u.replace(good, bad), v2))
                # the root name alone, then the parentSub alone
                out.append((u.replace(good, bad, 1), v2))
                head, _, tail = u.rpartition(good)
                out.append((head + bad + tail, v2))
    return out


def corpus_legacy_json() -> list[tuple[bytes, bool]]:
    """A ``ContentJSON`` item (content ref 2), which the native V2
    scanner leaves to the Python decoder."""
    v1 = bytes([1, 1, 7, 0, 0x02, 1, 1, ord("a"), 2, 1, ord("1"), 1,
                ord("2"), 0])
    doc = Y.Doc(gc=False)
    Y.apply_update(doc, v1)
    assert doc.get_array("a").to_json() == [1, 2]
    return [(v1, False), (Y.encode_state_as_update_v2(doc), True)]


def corpus_mutated() -> list[tuple[bytes, bool]]:
    """``resilience/chaos.py``'s two mutators over the traces, and a
    seeded sample of single-bit flips and cuts of whole rooms."""
    inj = ChaosInjector(ChaosConfig(seed=SEED), kind="update")
    rng = random.Random(SEED)
    out = []
    for u in traces("distinct_traces", 16) + traces("storm_traces", 16):
        out += [(inj.corrupt(u), False), (inj.truncate(u), False)]
    for u, v2 in both(traces("distinct_traces", 2) + traces("storm_traces", 2)):
        for _ in range(96):
            flipped = bytearray(u)
            flipped[rng.randrange(len(u))] ^= 1 << rng.randrange(8)
            out.append((bytes(flipped), v2))
            out.append((u[: rng.randrange(len(u))], v2))
    return out


def corpus_bitflips() -> list[tuple[bytes, bool]]:
    out = []
    for u, v2 in small():
        for bit in range(8 * len(u)):
            flipped = bytearray(u)
            flipped[bit >> 3] ^= 1 << (bit & 7)
            out.append((bytes(flipped), v2))
    return out


def corpus_trailing() -> list[tuple[bytes, bool]]:
    rng = random.Random(SEED)
    out = []
    for u, v2 in small() + both(traces("distinct_traces", 2)):
        out += [(u + b"\x00", v2), (u + u, v2),
                (u + rng.randbytes(rng.randrange(1, 32)), v2)]
    return out


CORPORA = {
    "corrupt_fixtures": lambda: [
        (p.read_bytes(), False)
        for p in sorted((FIXTURES / "corrupt").glob("*.bin"))
    ],
    "compat_v1": lambda: both(compat()),
    "distinct_traces": lambda: both(traces("distinct_traces")),
    "storm_traces": lambda: both(traces("storm_traces")),
    "every_bit_flip": corpus_bitflips,
    "every_truncation": lambda: [
        (u[:cut], v2) for u, v2 in small() for cut in range(len(u))
    ],
    "chaos_mutators": corpus_mutated,
    "bad_utf8_root_and_parent_sub": corpus_bad_utf8,
    "legacy_content_json": corpus_legacy_json,
    "trailing_garbage": corpus_trailing,
    "not_updates": lambda: [
        (b"", False), (b"", True), (b"\x00", False), (b"\x00", True),
        (b"\x00\x00", False), (bytes(11), True), (b"\xff" * 9, False),
        (bytearray(b"\x00\x00"), False), (memoryview(b"\x00\x00"), False),
        ("text", False), (None, True),
    ],
}


def told(verdict):
    """A verdict as something that compares: the summary, or the
    refusal's type and words."""
    if isinstance(verdict, Exception):
        return (type(verdict).__name__, str(verdict))
    return verdict


def decoder_says(update, v2):
    """What ``decode_update_refs`` makes of it, in ``validate_update``'s
    terms."""
    if not isinstance(update, (bytes, bytearray, memoryview)):
        return ("InvalidUpdate",
                f"not a bytes payload: {type(update).__name__}")
    update = bytes(update)
    if not update:
        return ("InvalidUpdate", "empty update")
    try:
        refs, ds = decode_update_refs(update, v2)
    except Exception as e:
        return ("InvalidUpdate", f"{type(e).__name__}: {e}")
    return {
        "clients": len(refs),
        "structs": sum(len(rs) for rs in refs.values()),
        "ds_ranges": len(ds),
        "bytes": len(update),
    }


@pytest.mark.parametrize("name", sorted(CORPORA))
def test_verdict_is_the_decoders(name):
    corpus = CORPORA[name]()
    assert corpus
    singles = []
    for update, v2 in corpus:
        try:
            singles.append(validate_update(update, v2))
        except InvalidUpdate as e:
            singles.append(e)
    tally: dict = {}
    batch = validate_updates(
        [u for u, _ in corpus], [v2 for _, v2 in corpus], tally
    )
    assert [told(v) for v in batch] == [told(v) for v in singles]
    for (update, v2), verdict in zip(corpus, singles):
        assert told(verdict) == decoder_says(update, v2), (
            bytes(update)[:64].hex() if update else update, v2
        )
    refused = sum(isinstance(v, Exception) for v in singles)
    walked = sum(
        bool(u) and isinstance(u, (bytes, bytearray, memoryview))
        for u, _ in corpus
    )
    assert tally["validated_native"] + tally["validated_fallback"] == walked
    # the slow path takes what the native walk refuses and no more
    assert tally["validated_native"] <= len(corpus) - refused


def test_the_native_walk_gives_the_verdict_on_sound_traffic():
    """Whole rooms and keystrokes, V1 and V2: none takes the slow path
    (``validated_fallback`` 0 is what ``crash-recover`` must read)."""
    from yjs_tpu import native

    if native.load() is None:
        pytest.skip(f"no native core: {native.load_error()}")
    doc = Y.Doc(gc=False)
    keys: list[bytes] = []
    doc.on("update", lambda u, origin, d: keys.append(bytes(u)))
    text = doc.get_text("text")
    for i in range(64):
        text.insert(i, "k")
    text.delete(3, 2)
    corpus = both(traces("distinct_traces", 8) + traces("storm_traces", 8) + keys)
    tally: dict = {}
    out = validate_updates(
        [u for u, _ in corpus], [v2 for _, v2 in corpus], tally
    )
    assert not any(isinstance(v, Exception) for v in out)
    assert tally == {"validated_native": len(corpus), "validated_fallback": 0}
