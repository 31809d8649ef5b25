"""Generate the long documents of the ``yws-longtail`` deployment.

``benchmarks/configs/yws-longtail.json`` names, under
``long_document_seeds``, the distinct long documents one restart group
holds: B4 stand-ins (``gen_b4_fixture.generate(seed=...)``: B4's
published 182k inserts and 77k deletes, as ``tests/fixtures/b4_trace.bin``
is seed 13) and fragmented prepends of 100,000 characters
(``bench.gen_prepend_fragmented``'s construction, as
``tests/fixtures/prepend_frag_100000.bin.z`` is seed 3 under client 77).
Each document is typed under client ids of its own, so that no two
long rooms of a timed load share a byte of their updates.

Writes ``benchmarks/longdocs/<kind>-<seed>.bin.z`` (zlib of the one V1
update) and ``benchmarks/longdocs/documents.json``: per document its
clients, state vector, the digest of its text as
``benchmarks.oracle.text_digest`` makes it, and the update's length and
SHA-256.  A file that is there is kept.

Usage: python scripts/gen_longtail_fixtures.py [config] [n_chars]
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
sys.path.insert(0, str(ROOT / "scripts"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

OUT = ROOT / "benchmarks" / "longdocs"
PREPEND_CHARS = 100_000


def b4_clients(seed: int) -> tuple[int, int]:
    return (100 * seed + 1, 100 * seed + 2)


def prepend_client(seed: int) -> int:
    return 7000 + seed


def gen_prepend(n_chars: int, seed: int, client_id: int) -> bytes:
    """``bench.gen_prepend_fragmented`` with the client id a parameter
    (``seed=3, client_id=77`` is that function's own document; the
    tests hold the two to each other)."""
    import yjs_tpu as Y

    gen = random.Random(seed)
    d = Y.Doc(gc=False)
    d.client_id = client_id
    t = d.get_text("text")
    for _ in range(n_chars):
        t.insert(0, chr(gen.randint(97, 122)))
    return Y.encode_state_as_update(d)


def describe(kind: str, seed: int, clients, update: bytes) -> dict:
    import yjs_tpu as Y
    from benchmarks.oracle import text_digest

    doc = Y.Doc(gc=False)
    Y.apply_update(doc, update)
    sv = Y.decode_state_vector(Y.encode_state_vector(doc))
    return {
        "kind": kind, "seed": seed, "clients": list(clients),
        "state_vector": sorted(sv.items()),
        "text_digest": text_digest(doc.get_text("text").to_string()),
        "update_bytes": len(update),
        "update_sha256": hashlib.sha256(update).hexdigest(),
    }


def make(job) -> dict:
    kind, seed, n_chars = job
    path = OUT / f"{kind}-{seed}.bin.z"
    clients = b4_clients(seed) if kind == "b4" else (prepend_client(seed),)
    if path.exists():
        update = zlib.decompress(path.read_bytes())
    else:
        if kind == "b4":
            import gen_b4_fixture

            update, _meta = gen_b4_fixture.generate(seed=seed, clients=clients)
        else:
            update = gen_prepend(n_chars, seed, clients[0])
        path.write_bytes(zlib.compress(update, 9))
    return describe(kind, seed, clients, update)


def main() -> None:
    config = sys.argv[1] if len(sys.argv) > 1 else "yws-longtail"
    n_chars = int(sys.argv[2]) if len(sys.argv) > 2 else PREPEND_CHARS
    cfg = json.loads(
        (ROOT / "benchmarks" / "configs" / f"{config}.json").read_text()
    )
    OUT.mkdir(exist_ok=True)
    jobs = [
        (kind, seed, n_chars)
        for kind, seeds in cfg["long_document_seeds"].items() for seed in seeds
    ]
    with ProcessPoolExecutor(max_workers=os.cpu_count()) as pool:
        documents = list(pool.map(make, jobs))
    table = {
        "what": "the long documents of benchmarks/configs/yws-longtail.json, "
        "made by `python scripts/gen_longtail_fixtures.py`: per document the "
        "state vector and the first 24 hex digits of the SHA-256 of its text "
        "as a CPU Y.Doc replays the one update of <kind>-<seed>.bin.z",
        "documents": {f"{d['kind']}-{d['seed']}": d for d in documents},
    }
    (OUT / "documents.json").write_text(json.dumps(table, indent=1) + "\n")
    for d in documents:
        print(d["kind"], d["seed"], d["update_bytes"], sum(n for _c, n in d["state_vector"]))


if __name__ == "__main__":
    main()
