"""JAX device kernels for the batched CRDT engine.

The reference integrates one Item at a time into a pointer-chased linked list
(reference src/structs/Item.js:403-517).  Here YATA runs on the host: the
planner (``ops/native_mirror.py``, or ``ops/columns.py`` without a compiler)
resolves every conflict and hands the device the FINAL link values, so a
flush chunk is one conflict-free scatter over the whole doc batch
(``apply_plan2``; ``parallel.mesh.sharded_apply_plan`` on a mesh) for the
rooms that held rows, one whole-row write a width class for the rooms it
loads into empty slots (``apply_plan2_rows``;
``parallel.mesh.sharded_load_rows``), and a compaction, a hydration or a
release is one whole-row write too (``scatter_rows``, ``blank_rows``).
The lanes of that scatter ship no doc column: they lie room after room
behind a header of per-doc counts, and the device derives each lane's
doc from the doc boundaries (``_doc_lanes``: each lane compared with
every doc's end in one fused pass, no search and no gather a lane).
The one program that reads the tables back ranks document order from the
right links (``list_ranks``): the export that verification holds the tables
to.  State vectors, diffs and every other read are the host mirrors'.

All row arrays carry one extra trailing scratch row (index N); its contents
are never read meaningfully.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.prof import profiled

NULL = -1


# ---------------------------------------------------------------------------
# bulk apply: host-resolved final links in one scatter (the device write path)
# ---------------------------------------------------------------------------


def _doc_lanes(counts, k, cap_oob):
    """Per-lane (doc, within-doc index) derived on device from per-doc
    counts — the doc-id column never crosses the host->device link.
    Lanes beyond the true total get an out-of-bounds index (dropped).

    The lanes lie room after room, so lane ``i`` belongs to doc ``d``
    exactly when ``cum[d-1] <= i < cum[d]``: a lane's doc is the number
    of docs that end at or before it.  ``method="compare_all"`` counts
    them outright: k x B compares that XLA fuses into their sum, with no
    gather and no loop.  The default method is a binary search, which
    runs on a TPU as ``log2(B)`` dependent rounds of one gather a lane
    each: 90 ms of the 130 that a bulk merge's widest flush (852 k
    lanes, B 4096) held the device, where the compares take 12, and
    0.04 ms of a served flush's 0.09, where they take 0.002 (PERF.md,
    PR 49, which also says why marks under a running sum were not
    kept: 0.2 ms at the widest flush, and 0.18 ms at every other)."""
    b = counts.shape[0]
    cum = jnp.cumsum(counts, dtype=jnp.int32)
    idx = jnp.arange(k, dtype=jnp.int32)
    d = jnp.searchsorted(cum, idx, side="right", method="compare_all")
    d = jnp.minimum(d.astype(jnp.int32), b - 1)
    within = idx - (cum[d] - counts[d])
    within = jnp.where(idx < cum[b - 1], within, cap_oob)
    return d, within


@profiled("apply_plan2")
@functools.partial(
    jax.jit, static_argnums=(2, 3, 4, 5), donate_argnums=(0,)
)
def apply_plan2(dyn, lanes, k_dn, k_sp, k_h, k_d):
    """Bulk apply with device-derived indices, minimizing transfer bytes:

    lanes layout (ONE i32 transfer):
      [cnt_dense|cnt_sparse|cnt_heads|cnt_dels]  4 x [B] per-doc counts
      [dense_v]*k_dn    full-table link loads: doc d's section i sets
                        right_link[d, i] = v (row index derived on device —
                        fresh/full flushes ship VALUES ONLY)
      [r|v]*k_sp        sparse link writes at explicit rows
      [s|v]*k_h         segment-head writes
      [r]*k_d           delete marks
    """
    return apply_lanes(dyn, lanes, k_dn, k_sp, k_h, k_d)


def apply_lanes(dyn, lanes, k_dn, k_sp, k_h, k_d):
    """The apply_plan2 body as a plain traceable function — reused by the
    sharded mesh step (each shard applies its own lanes block locally).

    ``lanes`` may arrive int16 (engines whose row/seg capacity fits —
    halves the flush transfer); widened on device."""
    lanes = lanes.astype(jnp.int32)
    right_link, deleted, starts = dyn
    b = right_link.shape[0]
    n1 = right_link.shape[1]
    o = 4 * b
    cnt_dn, cnt_sp = lanes[0:b], lanes[b : 2 * b]
    cnt_h, cnt_d = lanes[2 * b : 3 * b], lanes[3 * b : 4 * b]
    if k_dn:
        dense_v = lanes[o : o + k_dn]
        d, r = _doc_lanes(cnt_dn, k_dn, n1)
        right_link = right_link.at[d, r].set(
            dense_v, mode="drop", unique_indices=True
        )
    o += k_dn
    if k_sp:
        r = lanes[o : o + k_sp]
        v = lanes[o + k_sp : o + 2 * k_sp]
        d, _ = _doc_lanes(cnt_sp, k_sp, n1)
        right_link = right_link.at[d, r].set(
            v, mode="drop", unique_indices=True
        )
    o += 2 * k_sp
    if k_h:
        s = lanes[o : o + k_h]
        v = lanes[o + k_h : o + 2 * k_h]
        d, _ = _doc_lanes(cnt_h, k_h, starts.shape[1])
        starts = starts.at[d, s].set(v, mode="drop", unique_indices=True)
    o += 2 * k_h
    if k_d:
        r = lanes[o : o + k_d]
        d, _ = _doc_lanes(cnt_d, k_d, n1)
        deleted = deleted.at[d, r].set(
            True, mode="drop", unique_indices=True
        )
    return right_link, deleted, starts


def _put_rows(table, idx, block):
    """``table[idx, :w] = block`` for a block ``w`` wide; columns ``>= w``
    of those docs are left as they are, and a row aimed past the last doc
    is dropped (a padded block's spare rows).  A block may arrive
    narrower than the table's cells (int16): widened here."""
    return table.at[idx, : block.shape[1]].set(
        block.astype(table.dtype), mode="drop"
    )


def load_rows(dyn, idx, new_right, new_deleted, new_starts):
    """The whole-row write as a plain traceable function: the body of
    ``apply_plan2_rows`` and of ``scatter_rows``, reused by the sharded
    mesh step (each shard writes its own block locally)."""
    right_link, deleted, starts = dyn
    return (
        _put_rows(right_link, idx, new_right),
        _put_rows(deleted, idx, new_deleted),
        _put_rows(starts, idx, new_starts),
    )


@profiled("apply_plan2_rows")
@functools.partial(jax.jit, donate_argnums=(0,))
def apply_plan2_rows(dyn, idx, new_right, new_deleted, new_starts):
    """Bulk apply of rooms loaded whole into empty slots: the row form of
    ``apply_plan2``.  A room whose plan writes every row it has, into a
    slot that held none, is one row of a block: its final right links
    with ``NULL`` behind them, its ``deleted`` row with its tombstones
    set, its ``starts`` row with its list heads, and the device writes
    ``table[idx, :w] = block`` for the three tables, where the element
    lanes would write the same cells one link at a time.

    The invariant this rests on: a slot that holds no row is at fill in
    every cell (``NULL`` links and heads, ``False`` tombstones): a new
    engine allocates it so, table growth fills the new columns so, and
    ``blank_rows`` returns a released slot to it.  So the ``NULL`` and
    ``False`` a block carries past a room's last row, and in the heads it
    does not set, are what those cells hold already, and the tables come
    out bit for bit as the lanes leave them.  It must never be given a
    room that had rows before the flush: the plan does not name that
    room's older tombstones (``BatchEngine._stage_row_loads`` reads both
    facts from the plan).

    ``idx``: the slots, one a block row; a spare row is aimed past the
    last slot and dropped.  Blocks of rooms no longer than 32767 rows
    arrive int16 (every link and head is a row of its own room)."""
    return load_rows(dyn, idx, new_right, new_deleted, new_starts)


@profiled("apply_plan_shared")
@functools.partial(jax.jit, static_argnums=(2, 3, 4), donate_argnums=(0,))
def apply_plan_shared(dyn, lanes, k_l, k_h, k_d):
    """Broadcast bulk apply: ONE doc's resolved deltas fanned out to every
    doc in the batch (the B4 replay shape).  Device work is the minimal
    B x K state write; XLA broadcasts the single delta copy.

    lanes: ONE i32 array — [rows|vals]*k_l links, [segs|hvals]*k_h heads,
    [dels]*k_d deletes (single transfer, see apply_plan)."""
    right_link, deleted, starts = dyn
    o = 0
    rows, vals = lanes[o : o + k_l], lanes[o + k_l : o + 2 * k_l]
    o += 2 * k_l
    segs, hvals = lanes[o : o + k_h], lanes[o + k_h : o + 2 * k_h]
    o += 2 * k_h
    dels = lanes[o : o + k_d]
    right_link = right_link.at[:, rows].set(
        jnp.broadcast_to(vals, (right_link.shape[0], k_l)),
        mode="drop",
        unique_indices=True,
    )
    starts = starts.at[:, segs].set(
        jnp.broadcast_to(hvals, (starts.shape[0], k_h)),
        mode="drop",
        unique_indices=True,
    )
    deleted = deleted.at[:, dels].set(True, mode="drop", unique_indices=True)
    return right_link, deleted, starts


@profiled("scatter_rows")
@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def scatter_rows(right, deleted, starts, idx, new_right, new_deleted,
                 new_starts):
    """Rebuild scatter: replace the head of docs ``idx``'s link/deleted/
    head rows with freshly packed host columns (compaction rebuilds,
    deferred warm-promotion hydrations).

    A staged block is as wide as the rooms in it need, not as the table:
    ``table[idx, :w]`` is written from a block of width ``w`` (any ``w``
    up to the table's own; each table takes its own block's width), and
    columns ``>= w`` of those docs are left as they are.  The caller
    picks ``w`` to cover every cell the docs have written since their
    slots were last blanked (``BatchEngine._scatter_rebuilt``), so a
    narrow block leaves the same tables as a full-width one.

    The resident tables are donated, so the rebuild updates device state
    in place instead of materializing a second B x cap copy per array —
    the same donation contract as the flush dispatch kernels (ISSUE 12)."""

    return load_rows(
        (right, deleted, starts), idx, new_right, new_deleted, new_starts
    )


@profiled("blank_rows")
@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def blank_rows(right, deleted, starts, doc):
    """Release blanking: doc ``doc``'s whole link/deleted/head rows
    (scratch column included) back to the fills a new engine allocates.

    ``doc`` is a traced scalar — one program for every slot — and the
    resident tables are donated, so a release writes one row of each
    table in place instead of copying the tables.  On doc-sharded tables
    the partitioner keeps the write on the shard that owns the row (no
    collective, nothing gathered): the other shards' blocks pass
    through.  ``0 <= doc < n_docs`` is the caller's to hold
    (``BatchEngine.reset_doc`` raises): the write clamps its start, so
    a slot out of range would blank the first or the last row."""

    def put(table, fill):
        row = jnp.full((1, table.shape[1]), fill, table.dtype)
        return lax.dynamic_update_slice(table, row, (doc, 0))

    return put(right, NULL), put(deleted, False), put(starts, NULL)


# ---------------------------------------------------------------------------
# export: the one program that reads the tables back
# ---------------------------------------------------------------------------


def list_ranks(right_link, valid):
    """Document order from right links by pointer doubling: d[i] = distance
    to the list tail; sorting valid rows by descending d gives the order.

    right_link: [B, N+1] i32; valid: [B, N+1] bool host-known membership
    (non-GC mirrored rows; scratch cells excluded).  Returns d with -1 on
    invalid rows.
    """
    b, n1 = right_link.shape
    d = jnp.where(right_link != NULL, 1, 0).astype(jnp.int32)
    p = right_link
    n_rounds = max(1, math.ceil(math.log2(max(2, n1))))

    # fori_loop rather than a Python-unrolled loop: unrolling log2(N) gather
    # rounds makes HLO size (and XLA:CPU compile time) grow superlinearly
    # with row capacity — ~80s at N=8192 on one host core, which stalled the
    # suite on wide docs.  The rolled loop compiles in constant time.
    def _round(_, dp):
        d, p = dp
        safe_p = jnp.where(p != NULL, p, 0)
        d = d + jnp.where(p != NULL, jnp.take_along_axis(d, safe_p, axis=1), 0)
        p = jnp.where(p != NULL, jnp.take_along_axis(p, safe_p, axis=1), NULL)
        return d, p

    d, _ = jax.lax.fori_loop(0, n_rounds, _round, (d, p))
    return jnp.where(valid, d, NULL)


list_ranks = profiled("list_ranks")(jax.jit(list_ranks))
