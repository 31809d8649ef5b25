"""The element lanes derive each lane's doc on the device
(``kernels._doc_lanes``): the lanes are laid out room after room, so a
lane's doc is the number of docs that end at or before it, counted from
the per-doc counts in one fused pass.  Held here to ``numpy.repeat``
and to the binary search a lane that it replaced, and ``apply_plan2`` at
a bulk key to a plain numpy write of the same ``(doc, row, value)``
triples, on one device and through ``sharded_apply_plan``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from yjs_tpu.ops import kernels

NULL = -1
OOB = 99_999


def searched(counts, k, cap_oob):
    """What ``_doc_lanes`` was before PR 49: ``searchsorted`` a lane."""
    b = counts.shape[0]
    cum = jnp.cumsum(counts)
    idx = jnp.arange(k, dtype=jnp.int32)
    d = jnp.searchsorted(cum, idx, side="right").astype(jnp.int32)
    d = jnp.minimum(d, b - 1)
    within = idx - (cum[d] - counts[d])
    within = jnp.where(idx < cum[b - 1], within, cap_oob)
    return d, within


def repeated(counts, k, cap_oob):
    """The lanes as numpy lays them out: doc ``j`` ``counts[j]`` times,
    then padding aimed out of bounds in the last doc."""
    counts = np.asarray(counts, np.int64)
    b, total = len(counts), int(counts.sum())
    d = np.full(k, b - 1, np.int32)
    d[:total] = np.repeat(np.arange(b), counts)
    within = np.full(k, cap_oob, np.int32)
    within[:total] = np.concatenate(
        [np.arange(c) for c in counts] or [np.zeros(0, np.int64)]
    )
    return d, within


CASES = {
    "empty_docs_at_the_head": ([0, 0, 0, 5, 3, 7, 1, 9], 32),
    "empty_docs_in_the_middle": ([4, 0, 0, 0, 6, 0, 2, 8], 32),
    "empty_docs_at_the_tail": ([3, 9, 1, 6, 0, 0, 0, 0], 32),
    "every_other_doc_empty": ([0, 5, 0, 5, 0, 5, 0, 5], 24),
    "all_docs_empty": ([0] * 8, 16),
    "total_equal_to_k": ([8, 0, 24, 16, 0, 16], 64),
    "total_equal_to_k_last_doc_empty": ([40, 24, 0, 0], 64),
    "total_under_k": ([5, 0, 11, 2], 64),
    "one_lane": ([0, 1, 0], 8),
    "one_doc": ([17], 32),
    "one_doc_empty": ([0], 8),
    "one_doc_full": ([32], 32),
}


@pytest.mark.parametrize("dtype", [np.int32, np.int16], ids=["int32", "int16"])
@pytest.mark.parametrize("case", CASES)
def test_doc_lanes_are_numpy_repeat(case, dtype):
    counts, k = CASES[case]
    d, within = kernels._doc_lanes(jnp.asarray(counts, dtype), k, OOB)
    want_d, want_within = repeated(counts, k, OOB)
    assert d.dtype == jnp.int32 and within.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(d), want_d)
    np.testing.assert_array_equal(np.asarray(within), want_within)
    # padding stays addressable: a doc in range, a row out of it
    assert 0 <= int(d.min()) and int(d.max()) < len(counts)


@pytest.mark.parametrize("b,k", [
    (8, 64), (8, 1000), (64, 64), (64, 4096), (64, 2 ** 17),
    (1024, 1000), (4096, 60), (4096, 2048), (4096, 2 ** 16),
])
def test_doc_lanes_are_what_the_search_found(b, k):
    """Every lane of both outputs, padding included, against the search
    the derivation replaced: full, sparse, and lopsided counts."""
    rng = np.random.default_rng(b * 31 + k)
    for fill in ("spread", "few_rooms", "head_empty", "tail_empty", "full"):
        counts = rng.integers(0, max(1, 2 * k // b) + 1, b)
        if fill == "few_rooms":
            counts[rng.random(b) < 0.9] = 0
        elif fill == "head_empty":
            counts[: b // 2] = 0
        elif fill == "tail_empty":
            counts[b // 2:] = 0
        while counts.sum() > k:
            counts //= 2
        if fill == "full":
            counts[rng.integers(b)] += k - counts.sum()
        counts = jnp.asarray(counts, jnp.int32)
        got = kernels._doc_lanes(counts, k, OOB)
        want = searched(counts, k, OOB)
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


# -- apply_plan2 at a bulk key against a plain write of the triples --------

B, N1, S1 = 64, 4097, 9
KEY = (64, 2 ** 17, 24, 8192)  # k_dn, k_sp, k_h, k_d


def bulk_lanes(rng, docs, dtype=np.int32):
    """One lanes block for the rooms ``docs`` of a ``B``-doc shard, and
    the triples it names: ``(table, doc, row, value)``."""
    k_dn, k_sp, k_h, k_d = KEY
    cnt = np.zeros((4, B), np.int64)
    dense_v, sp_r, sp_v, h_s, h_v, d_r = [], [], [], [], [], []
    triples = []
    for j, doc in enumerate(docs):
        if j == 0:  # one room rides the dense section: rows 0..n-1
            n = 40
            vals = rng.integers(NULL, N1 - 1, n)
            cnt[0, doc] = n
            dense_v.extend(vals)
            triples += [("right", doc, r, v) for r, v in enumerate(vals)]
            continue
        n = int(rng.integers(1, 2 * k_sp // len(docs)))
        n = min(n, N1 - 1, k_sp - len(sp_r))
        rows = rng.permutation(N1 - 1)[:n]
        vals = rng.integers(NULL, N1 - 1, n)
        cnt[1, doc] = n
        sp_r.extend(rows)
        sp_v.extend(vals)
        triples += [("right", doc, r, v) for r, v in zip(rows, vals)]
        if j % 3 == 0 and len(h_s) < k_h:
            cnt[2, doc] = 1
            h_s.append(int(rng.integers(0, S1 - 1)))
            h_v.append(int(rng.integers(0, N1 - 1)))
            triples.append(("starts", doc, h_s[-1], h_v[-1]))
        m = min(int(rng.integers(0, 2 * k_d // len(docs))), k_d - len(d_r))
        rows = rng.permutation(N1 - 1)[:m]
        cnt[3, doc] = m
        d_r.extend(rows)
        triples += [("deleted", doc, r, True) for r in rows]

    def padded(vals, k, fill):
        out = np.full(k, fill, np.int64)
        out[: len(vals)] = vals
        return out

    lanes = np.concatenate([
        cnt.reshape(-1), padded(dense_v, k_dn, NULL),
        padded(sp_r, k_sp, N1), padded(sp_v, k_sp, NULL),
        padded(h_s, k_h, S1), padded(h_v, k_h, NULL), padded(d_r, k_d, N1),
    ])
    assert np.abs(lanes).max() <= np.iinfo(dtype).max
    return lanes.astype(dtype), triples


def tables(rng, b):
    """Tables that already hold rows: the lanes write into rooms."""
    return (
        rng.integers(NULL, N1 - 1, (b, N1)).astype(np.int32),
        rng.random((b, N1)) < 0.1,
        rng.integers(NULL, N1 - 1, (b, S1)).astype(np.int32),
    )


def written(dyn, triples, first_doc=0):
    """The plain write: each triple set in a numpy copy of the tables."""
    out = {"right": dyn[0].copy(), "deleted": dyn[1].copy(),
           "starts": dyn[2].copy()}
    for table, doc, row, value in triples:
        out[table][first_doc + doc, row] = value
    return out["right"], out["deleted"], out["starts"]


def same_tables(got, want):
    for g, w in zip(got, want):
        g = np.asarray(g)
        assert g.dtype == w.dtype and g.tobytes() == w.tobytes()


@pytest.mark.parametrize("dtype", [np.int32, np.int16], ids=["int32", "int16"])
def test_apply_plan2_at_a_bulk_key_is_the_plain_write(dtype):
    """B 64, 2^17 sparse lanes over rooms with empty slots between them,
    a dense room, heads and deletes; int16 lanes widen on the device."""
    rng = np.random.default_rng(49)
    docs = np.sort(rng.choice(B, 40, replace=False))
    lanes, triples = bulk_lanes(rng, docs, dtype)
    dyn = tables(rng, B)
    got = kernels.apply_plan2(
        tuple(jnp.asarray(t) for t in dyn), jnp.asarray(lanes), *KEY
    )
    same_tables(got, written(dyn, triples))


def test_sharded_apply_plan_at_a_bulk_key_is_the_plain_write():
    """The same body under ``shard_map``: four shards of 64 docs, each
    its own lanes block, one of them with no lanes at all."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from yjs_tpu.parallel import doc_mesh
    from yjs_tpu.parallel.mesh import sharded_apply_plan

    try:
        mesh = doc_mesh(4, backend="cpu")
    except RuntimeError as e:  # YTPU_TEST_PLATFORM=tpu: one chip
        pytest.skip(f"no CPU mesh beside this backend: {e}")
    rng = np.random.default_rng(50)
    dyn = tables(rng, 4 * B)
    want = dyn
    blocks = []
    for shard, n_rooms in enumerate((40, 0, 64, 3)):
        docs = np.sort(rng.choice(B, n_rooms, replace=False))
        lanes, triples = bulk_lanes(rng, docs)
        blocks.append(lanes)
        want = written(want, triples, first_doc=shard * B)
    sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
    fn = sharded_apply_plan(mesh, mesh.axis_names[0], *KEY)
    got, metrics = fn(
        tuple(jax.device_put(t, sharding) for t in dyn),
        jax.device_put(np.stack(blocks), sharding),
    )
    same_tables(got, want)
    assert int(metrics["integrated"]) == sum(
        int(b[: 2 * B].sum()) for b in blocks
    )


# -- the search cannot come back unnoticed ----------------------------------


def equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from equations(inner)


def traced(monkeypatch=None, **patched):
    for name, f in patched.items():
        monkeypatch.setattr(kernels, name, f)
    dyn = (
        jax.ShapeDtypeStruct((B, N1), jnp.int32),
        jax.ShapeDtypeStruct((B, N1), jnp.bool_),
        jax.ShapeDtypeStruct((B, S1), jnp.int32),
    )
    lanes = jax.ShapeDtypeStruct(
        (4 * B + KEY[0] + 2 * KEY[1] + 2 * KEY[2] + KEY[3],), jnp.int32
    )
    fn = jax.jit(
        lambda dyn, lanes: kernels.apply_lanes(dyn, lanes, *KEY)
    )
    return (
        list(equations(jax.make_jaxpr(fn)(dyn, lanes).jaxpr)),
        fn.lower(dyn, lanes).as_text(),
    )


LOOPS = {"while", "scan"}


def names(eqns):
    return {e.primitive.name for e in eqns}


def test_apply_lanes_at_a_bulk_key_holds_no_loop():
    eqns, text = traced()
    assert not names(eqns) & LOOPS
    assert "stablehlo.while" not in text
    assert "scatter" in names(eqns)  # it is the apply that was traced


def test_the_guard_sees_the_search(monkeypatch):
    """The same reading of the program as it was: the guard's own test."""
    eqns, text = traced(monkeypatch, _doc_lanes=searched)
    assert names(eqns) & LOOPS
    assert "stablehlo.while" in text
