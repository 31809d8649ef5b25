"""The plain typists against the program's CPU ``Y.Doc``: the update a
keystroke is written out as is byte for byte the one a ``Y.Doc`` client
emits for it, and both end on the same text and state vector."""

import random

import pytest

from benchmarks import deployment, oracle
from benchmarks.plain_client import PlainText, Typist, type_together


def replayed(base):
    import yjs_tpu as Y

    doc = Y.Doc(gc=False)
    Y.apply_update(doc, base)
    return doc


@pytest.fixture(scope="module")
def bases():
    return {
        "distinct": deployment.load_traces("distinct_traces")[3],
        "storm": deployment.load_traces("storm_traces")[5],
    }


@pytest.mark.parametrize("kind", ["distinct", "storm"])
@pytest.mark.parametrize("seed", [1, 2**31 + 7])
def test_keystrokes_are_the_updates_a_ydoc_client_sends(bases, kind, seed):
    import yjs_tpu as Y

    rng = random.Random(seed)
    doc = replayed(bases[kind])
    doc.client_id = 1_000_000
    sent = []
    doc.on("update", lambda u, _o, _d: sent.append(u))
    ytext = doc.get_text("text")
    plain = PlainText.of_items(oracle.items_of(doc))
    assert plain.text() == ytext.to_string()
    typist = Typist(plain, 1_000_000)
    cursor = len(ytext)
    for _ in range(400):
        roll = rng.random()
        if roll < 0.1:
            cursor = rng.randint(0, len(ytext))
            typist.jump(cursor)
        if roll < 0.6 or cursor == 0:
            ch = rng.choice("etaoin ")
            ytext.insert(cursor, ch)
            cursor += 1
            assert typist.type(ch) == sent[-1]
        else:
            ytext.delete(cursor - 1, 1)
            cursor -= 1
            assert typist.erase() == sent[-1]
    assert plain.text() == ytext.to_string()
    assert plain.sv == Y.decode_state_vector(Y.encode_state_vector(doc))
    assert plain.live() == len(ytext)


@pytest.mark.parametrize("seed", [3, 2**31 + 9])
def test_two_typists_from_one_state_converge_with_a_ydoc(bases, seed):
    """Concurrent keystrokes, now and then at one place and beside a
    tombstone: the plain text ends where a ``Y.Doc`` that is sent the
    updates does."""
    import yjs_tpu as Y

    rng = random.Random(seed)
    server = replayed(bases["distinct"])
    plain = PlainText.of_items(oracle.items_of(server))
    a, b = Typist(plain, 1_000_000), Typist(plain, 1_000_001)
    b.jump(rng.randint(0, plain.live()))
    for step in range(300):
        sent = []
        if step % 25 == 0:  # both at one place: the conflict rule
            b.pos = a.settle()
        elif rng.random() < 0.1:
            sent.append(a.erase())
        sent += type_together(a, rng.choice("ab "), b, rng.choice("cd "))
        for u in sent:
            if u is not None:
                Y.apply_update(server, u)
    assert plain.text() == server.get_text("text").to_string()
    assert plain.sv == Y.decode_state_vector(Y.encode_state_vector(server))
