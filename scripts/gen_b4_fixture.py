"""Generate the B4-scale editing-trace fixture for bench.py.

The real crdt-benchmarks B4 dataset (a prosemirror paper-editing session,
~182k single-char inserts and ~77k single-char deletes — statistics cited in
reference INTERNALS.md:128-130) is not retrievable in this image, so this
synthesizes a trace with the same op counts and the same editing texture:
single-character ops at a mostly-sequential cursor (typing runs,
backspace-style delete runs, occasional cursor jumps), from two clients that
sync periodically.

Writes tests/fixtures/b4_trace.bin (the merged V1 update) and
tests/fixtures/b4_trace.json (op counts + the converged text's length and
sha256 + state vector, used by bench.py's convergence check).

Usage: python scripts/gen_b4_fixture.py [n_inserts n_deletes]
"""

from __future__ import annotations

import hashlib
import json
import random
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import yjs_tpu as Y

ALPHABET = "abcdefghijklmnopqrstuvwxyz     eettaaoinshr"


def generate(
    n_inserts: int = 182_000, n_deletes: int = 77_000, seed: int = 13,
    clients: tuple[int, int] = (101, 202),
):
    gen = random.Random(seed)
    a = Y.Doc(gc=False)
    a.client_id = clients[0]
    b = Y.Doc(gc=False)
    b.client_id = clients[1]

    def sync():
        ua = Y.encode_state_as_update(a, Y.encode_state_vector(b))
        ub = Y.encode_state_as_update(b, Y.encode_state_vector(a))
        Y.apply_update(b, ua)
        Y.apply_update(a, ub)

    ins = dels = 0
    # per-doc cursor (kept local across ops: the B4 texture)
    cursors = {id(a): 0, id(b): 0}
    active, other = a, b
    since_sync = 0
    t0 = time.time()
    while ins < n_inserts or dels < n_deletes:
        # stay on one client for a whole editing run
        if gen.random() < 0.02:
            active, other = other, active
        d = active
        t = d.get_text("text")
        ln = len(t)
        cur = min(cursors[id(d)], ln)
        if gen.random() < 0.05:  # jump to a new edit site
            cur = gen.randint(0, ln)
        # choose run type by remaining budget
        want_insert = ins < n_inserts and (
            dels >= n_deletes or gen.random() < n_inserts / (n_inserts + n_deletes)
        )
        run = gen.randint(2, 18)
        if want_insert:
            for _ in range(run):
                if ins >= n_inserts:
                    break
                t.insert(cur, gen.choice(ALPHABET))
                cur += 1
                ins += 1
        else:
            for _ in range(run):
                if dels >= n_deletes or cur == 0:
                    break
                t.delete(cur - 1, 1)  # backspace
                cur -= 1
                dels += 1
        cursors[id(d)] = cur
        since_sync += run
        if since_sync >= 2000:
            sync()
            since_sync = 0
        if (ins + dels) % 20000 < run:
            print(f"  {ins} ins / {dels} del  ({time.time()-t0:.0f}s)", flush=True)
    sync()
    text_a = a.get_text("text").to_string()
    assert text_a == b.get_text("text").to_string()
    update = Y.encode_state_as_update(a)
    meta = {
        "n_inserts": ins,
        "n_deletes": dels,
        "text_len": len(text_a),
        "text_sha256": hashlib.sha256(text_a.encode()).hexdigest(),
        "state_vector": {
            str(c): v for c, v in Y.get_state_vector(a.store).items() if v > 0
        },
        "seed": seed,
    }
    return update, meta


def main():
    n_ins = int(sys.argv[1]) if len(sys.argv) > 1 else 182_000
    n_del = int(sys.argv[2]) if len(sys.argv) > 2 else 77_000
    update, meta = generate(n_ins, n_del)
    fixtures = Path(__file__).resolve().parent.parent / "tests" / "fixtures"
    (fixtures / "b4_trace.bin").write_bytes(update)
    (fixtures / "b4_trace.json").write_text(json.dumps(meta, indent=1))
    print(json.dumps({**meta, "update_bytes": len(update)}, indent=1))


if __name__ == "__main__":
    main()
