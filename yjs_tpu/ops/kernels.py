"""JAX device kernels for the batched CRDT engine.

The reference integrates one Item at a time into a pointer-chased linked list
(reference src/structs/Item.js:403-517).  Here the same YATA semantics run as
a ``lax.scan`` over a *static* item table (the host pre-split pass guarantees
no splits are needed mid-kernel), vmapped over the document batch: each
sequential scan step integrates one item in every document of the batch, so
the TPU's parallelism is over docs while the per-doc causal chain stays
sequential — the parallelism split called out in SURVEY.md §7 ("concurrency
across docs (vmap)").

Set semantics without sets: the reference's ``itemsBeforeOrigin`` /
``conflictingItems`` (Item.js:447-470) only ever grow between clears, so they
are modelled with a per-row visit counter: a row is in ``itemsBeforeOrigin``
iff ``visit[row] >= scan_base`` and in ``conflictingItems`` iff
``visit[row] >= clear_mark``.  No O(N) clears, O(1) membership.

All row arrays carry one extra trailing scratch row (index N) that absorbs
masked scatter writes; its contents are never read meaningfully.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..obs.prof import profiled
from .columns import GATHER_SUCC

NULL = -1


def _upd(arr, idx, val, cond, dummy):
    """Masked scatter: write ``val`` at ``idx`` when ``cond`` else write the
    scratch row."""
    safe_idx = jnp.where(cond, idx, dummy)
    return arr.at[safe_idx].set(jnp.where(cond, val, arr[dummy]))


def _ids_eq(s1, k1, s2, k2):
    """compare_ids on (slot, clock) columns; NULL slot == null id."""
    return (s1 == s2) & ((s1 == NULL) | (k1 == k2))


# ---------------------------------------------------------------------------
# per-doc step kernel (vmapped over the batch by `batch_step`)
# ---------------------------------------------------------------------------


def _doc_step(statics, dyn, splits, sched, delete_rows):
    """Run one integration step for a single doc.

    statics: dict of [N+1] columns (client_key u32, origin_slot/clock,
        right_slot/clock, origin_row  i32)
    dyn: (right_link[N+1], deleted[N+1], starts[S+1]) — starts holds each
        segment's list head (root lists and per-map-key chains alike); no
        left-link array: the head test is starts[seg]==row and document
        order is ranked from right links alone
    splits: [S, 2] i32 (orig_row, new_row), NULL-padded, right-to-left per
        original row
    sched: [M, 4] i32 (row, left_row, right_row, seg), NULL-padded, causal
        order
    delete_rows: [D] i32, NULL-padded
    """
    right_link, deleted, starts = dyn
    n1 = right_link.shape[0]
    dummy = n1 - 1

    # -- split pre-pass: link surgery for host-computed run splits ----------
    # (the device half of splitItem, reference src/structs/Item.js:84-120)
    def split_body(carry, instr):
        rl, dl = carry
        orig, new = instr[0], instr[1]
        valid = orig >= 0
        safe_orig = jnp.where(valid, orig, dummy)
        old_right = rl[safe_orig]
        rl = _upd(rl, new, old_right, valid, dummy)
        rl = _upd(rl, orig, new, valid, dummy)
        dl = _upd(dl, new, dl[safe_orig], valid, dummy)
        return (rl, dl), None

    (right_link, deleted), _ = lax.scan(
        split_body, (right_link, deleted), splits
    )

    # -- integration scan: one item per sequential step ---------------------
    integrate_item = _make_integrate_item(statics, dummy)

    def integ_body(carry, s):
        carry = integrate_item(carry, s[0], s[1], s[2], s[3])
        return carry, None

    (right_link, starts), _ = lax.scan(
        integ_body, (right_link, starts), sched
    )

    deleted = _apply_deletes(deleted, delete_rows, dummy)
    return right_link, deleted, starts


def _make_integrate_item(statics, dummy):
    """The single-item YATA integrate (conflict scan + splice) as a carry
    transformer — shared by the sequential path and the level path's
    deferred (true-conflict) loop."""
    client_key = statics["client_key"]
    oslot = statics["origin_slot"]
    oclock = statics["origin_clock"]
    rslot = statics["right_slot"]
    rclock = statics["right_clock"]
    origin_row = statics["origin_row"]

    def integrate_item(carry, k, left0, right0, seg):
        rl, starts = carry
        n1 = rl.shape[0]
        s_dummy = starts.shape[0] - 1
        safe_seg = jnp.where(seg >= 0, seg, s_dummy)
        st = starts[safe_seg]  # this segment's list head
        # per-scan conflict sets: fresh visit marks, so no cross-scan counter
        visit = jnp.full((n1,), -1, jnp.int32)
        counter = jnp.int32(0)
        valid = k >= 0
        safe_k = jnp.where(valid, k, dummy)
        safe_l = jnp.where(left0 >= 0, left0, dummy)

        # fast path, the negation of reference Item.js:432-434: skip the
        # conflict scan when left is null and right is the current list head
        # (st == right0), or when left.right is still exactly right
        skip = jnp.where(
            left0 == NULL,
            (right0 != NULL) & (st == right0),
            rl[safe_l] == right0,
        )

        scan_base = counter
        o0 = jnp.where(
            valid & ~skip,
            jnp.where(left0 == NULL, st, rl[safe_l]),
            NULL,
        )

        def cond_fn(cs):
            o, _left, _clear, _cnt, _visit, done = cs
            return (~done) & (o != NULL) & (o != right0)

        def body_fn(cs):
            o, left, clear, cnt, visit, done = cs
            visit = visit.at[o].set(cnt)
            cnt = cnt + 1
            # case 1: same origin -> lower client id goes left
            same_origin = _ids_eq(oslot[safe_k], oclock[safe_k], oslot[o], oclock[o])
            c1_left = same_origin & (client_key[o] < client_key[safe_k])
            c1_break = same_origin & ~c1_left & _ids_eq(
                rslot[safe_k], rclock[safe_k], rslot[o], rclock[o]
            )
            # case 2: o's origin lies between this.origin and this
            orow = origin_row[o]
            has_origin = oslot[o] != NULL
            safe_orow = jnp.where(has_origin, orow, dummy)
            in_before = has_origin & (visit[safe_orow] >= scan_base)
            c2 = ~same_origin & in_before
            c2_left = c2 & ~(visit[safe_orow] >= clear)
            # case 3: unrelated item -> done
            c3_break = ~same_origin & ~in_before
            take_left = c1_left | c2_left
            left = jnp.where(take_left, o, left)
            clear = jnp.where(take_left, cnt, clear)
            done = c1_break | c3_break
            o = jnp.where(done, o, rl[o])
            return (o, left, clear, cnt, visit, done)

        o, left, _clear, counter, visit, _done = lax.while_loop(
            cond_fn,
            body_fn,
            (
                o0.astype(jnp.int32),
                left0.astype(jnp.int32),
                scan_base.astype(jnp.int32),
                counter.astype(jnp.int32),
                visit,
                jnp.bool_(False),
            ),
        )

        # splice into the list (reference Item.js:473-489)
        safe_left = jnp.where(left >= 0, left, dummy)
        right2 = jnp.where(left == NULL, st, rl[safe_left])
        rl = _upd(rl, left, k, valid & (left != NULL), dummy)
        starts = _upd(starts, safe_seg, k, valid & (left == NULL), s_dummy)
        rl = _upd(rl, k, right2, valid, dummy)
        return (rl, starts)

    return integrate_item


def _apply_deletes(deleted, delete_rows, dummy):
    # (reference DeleteSet.js readAndApplyDeleteSet tail)
    valid_d = delete_rows >= 0
    deleted = deleted.at[jnp.where(valid_d, delete_rows, dummy)].set(
        jnp.where(valid_d, True, deleted[dummy])
    )
    return deleted


def _doc_step_levels(statics, dyn, splits, lv_sched, delete_rows, scratch_base):
    """Level-parallel integration for a single doc.

    ``scratch_base`` is this doc's row count: rows beyond it are unused
    padding, used as per-lane scratch so masked bulk scatters have UNIQUE
    indices (duplicate scatter indices serialize on TPU).  The engine
    guarantees >= W spare slots and masks phantom rows at export.

    ``lv_sched`` is the 8-field schedule packed level-major, [L, W, 8]
    NULL-padded rows of (row, left, right, check, succ, seg, fb_left,
    fb_right); items in one
    dependency level (host-assigned, see StepPlan.assign_levels) have
    distinct splice gaps and already-placed deps, so every fast-path item
    in a level splices in ONE vectorized pass; items sharing a gap are
    pre-chained by the host (ascending client = YATA case-1 order,
    reference Item.js:447-455) via the ``succ`` field, and only true
    conflicts (stale pointers — concurrent edits at one position) fall
    back to the sequential YATA scan.  Collapses the per-item lax.scan of
    `_doc_step` (~#items steps) into ~#levels steps of width ~W.
    """
    right_link, deleted, starts = dyn
    n1 = right_link.shape[0]
    dummy = n1 - 1
    s_dummy = starts.shape[0] - 1

    # split pre-pass (identical to _doc_step)
    def split_body(carry, instr):
        rl, dl = carry
        orig, new = instr[0], instr[1]
        valid = orig >= 0
        safe_orig = jnp.where(valid, orig, dummy)
        old_right = rl[safe_orig]
        rl = _upd(rl, new, old_right, valid, dummy)
        rl = _upd(rl, orig, new, valid, dummy)
        dl = _upd(dl, new, dl[safe_orig], valid, dummy)
        return (rl, dl), None

    (right_link, deleted), _ = lax.scan(
        split_body, (right_link, deleted), splits
    )

    integrate_item = _make_integrate_item(statics, dummy)

    def level_body(carry, lv):
        rl, starts = carry
        k = lv[:, 0]
        l0 = lv[:, 1]  # left write target; NULL = head, NO_LEFT_WRITE = chained
        r0 = lv[:, 2]
        chk = lv[:, 3]  # shared gap left (NULL = head gap)
        succ = lv[:, 4]  # next chain member, or GATHER_SUCC = old gap successor
        seg = lv[:, 5]  # segment (root list / map-key chain) of the row
        fb_l = lv[:, 6]  # the row's ORIGINAL YATA gap, for the deferred
        fb_r = lv[:, 7]  # fallback (differs from chk/r0 on stitched chains)
        w = k.shape[0]
        mask = k >= 0
        safe_chk = jnp.where(chk >= 0, chk, dummy)
        safe_seg = jnp.where(seg >= 0, seg, s_dummy)
        st = starts[safe_seg]  # per-lane segment head

        # vectorized fast-path check across the level: the splice gap is
        # intact iff the gap-left's successor is still exactly `right`
        # (head gap: starts[seg] == r0 — covers the empty-segment r0==NULL
        # case too).  All members of one chain share (chk, r0), so a chain
        # is fast or deferred as a whole.
        fast = mask & jnp.where(chk == NULL, st == r0, rl[safe_chk] == r0)

        # bulk splice of all fast items (gaps are distinct by construction):
        # ONE scatter for both writes (rl[l0]=k for chain heads and
        # rl[k]=succ for every member; GATHER_SUCC resolves to r0 because
        # fast means rl[chk]==r0).  masked lanes write to unique scratch
        # slots — duplicate indices would serialize the scatter on TPU
        lanes = scratch_base + jnp.arange(2 * w, dtype=jnp.int32)
        succ_v = jnp.where(succ == GATHER_SUCC, r0, succ)
        cond1 = fast & (l0 >= 0)
        idx = jnp.concatenate([
            jnp.where(cond1, l0, lanes[:w]),
            jnp.where(fast, k, lanes[w:]),
        ])
        val = jnp.concatenate([
            jnp.where(cond1, k, NULL),
            jnp.where(fast, succ_v, NULL),
        ])
        rl = rl.at[idx].set(val, unique_indices=True)
        # head writes: one segment head at most per (level, seg) by
        # construction; masked lanes pile onto the scratch cell (junk)
        starts = _upd(starts, seg, k, fast & (l0 == NULL), s_dummy)

        # deferred: true conflicts run the sequential YATA scan one by one
        # with the original YATA inputs (row, gap-left, right, seg); chain
        # members are processed in ascending-client order (their index
        # order), which the conflict scan keeps correct
        pending = mask & ~fast

        def defer_cond(cs):
            pending, _carry = cs
            return jnp.any(pending)

        def defer_body(cs):
            pending, carry = cs
            j = jnp.argmax(pending)
            carry = integrate_item(carry, k[j], fb_l[j], fb_r[j], seg[j])
            return pending.at[j].set(False), carry

        _, (rl, starts) = lax.while_loop(
            defer_cond, defer_body, (pending, (rl, starts))
        )
        return (rl, starts), None

    (right_link, starts), _ = lax.scan(
        level_body,
        (right_link, starts),
        lv_sched,
    )

    deleted = _apply_deletes(deleted, delete_rows, dummy)
    return right_link, deleted, starts


@profiled("batch_step")
@functools.partial(jax.jit, donate_argnums=(1,))
def batch_step(statics, dyn, splits, sched, delete_rows):
    """vmapped per-item integration step over the doc batch.

    All arguments are dicts/tuples of arrays with a leading doc axis [B, ...].
    """
    return jax.vmap(_doc_step)(statics, dyn, splits, sched, delete_rows)


@profiled("batch_step_levels")
@functools.partial(jax.jit, donate_argnums=(1,))
def batch_step_levels(statics, dyn, splits, lv_sched, delete_rows, scratch_base):
    """vmapped level-parallel integration step (the default engine path).

    lv_sched: [B, L, W, 8] level-major sched8 schedule, NULL-padded.
    scratch_base: [B] i32 per-doc row count (see _doc_step_levels).
    """
    return jax.vmap(_doc_step_levels)(
        statics, dyn, splits, lv_sched, delete_rows, scratch_base
    )


@profiled("batch_step_levels_shared")
@functools.partial(jax.jit, donate_argnums=(1,))
def batch_step_levels_shared(
    statics, dyn, splits, lv_sched, delete_rows, scratch_base
):
    """Level-parallel step where ALL docs share one schedule + static table
    (the broadcast-replay shape: one update fanned out to a whole batch).

    statics/splits/lv_sched/delete_rows carry NO doc axis; vmap in_axes=None
    lets XLA fuse the implicit broadcast, so HBM and the host->device link
    hold ONE copy of the static columns instead of B.
    """
    return jax.vmap(
        _doc_step_levels, in_axes=(None, 0, None, None, None, 0)
    )(statics, dyn, splits, lv_sched, delete_rows, scratch_base)


# ---------------------------------------------------------------------------
# bulk apply: host-resolved final links in one scatter (the default path)
# ---------------------------------------------------------------------------


def _doc_lanes(counts, k, cap_oob):
    """Per-lane (doc, within-doc index) derived on device from per-doc
    counts — the doc-id column never crosses the host->device link.
    Lanes beyond the true total get an out-of-bounds index (dropped)."""
    b = counts.shape[0]
    cum = jnp.cumsum(counts)
    idx = jnp.arange(k, dtype=jnp.int32)
    d = jnp.searchsorted(cum, idx, side="right").astype(jnp.int32)
    d = jnp.minimum(d, b - 1)
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

    def put(table, block):
        return table.at[idx, : block.shape[1]].set(block)

    return (
        put(right, new_right),
        put(deleted, new_deleted),
        put(starts, new_starts),
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
# segment-sorted planning kernels (ISSUE 9)
# ---------------------------------------------------------------------------
# The host planner's per-struct cost is anchor resolution: three binary
# searches per ref against the per-client fragment index.  These kernels
# hoist that into sorted-segment array ops over the whole flush batch:
#
# - `plan_anchor_lookup`: ONE searchsorted over the slot-major
#   concatenated fragment index resolves every ref's origin/rightOrigin
#   candidate at once (the composed (slot, clock) key trick — per-slot
#   runs are clock-sorted, so slot*B+clock is globally sorted);
# - `plan_conflict_scan`: adjacent-ref chain detection — a ref whose
#   origin (or rightOrigin) lands inside the PREVIOUS ref's id range
#   chains onto it (typing runs, prepend runs), so its anchor is the
#   previous ref's row with no index lookup at all.  `run_id` numbers the
#   maximal chained runs (cumsum over chain breaks).
#
# Hints are *candidates*, not answers: the planner verifies containment
# against the live columns and falls back to the sequential bisect walk
# on any miss, so a wrong hint can never change placement.  Both kernels
# have NumPy twins (the default host path, YTPU_PLAN_SEGMENT=np) and
# jitted JAX versions (YTPU_PLAN_SEGMENT=jax) whose retraces/compiles the
# kernel profiler attributes like any other device kernel.


def _compose_keys(flat_slot, flat_clock, q_slot, q_clock):
    """(slot, clock) pairs -> one sortable int64 key space; invalid
    queries (slot < 0) map below every real key."""
    base = int(max(flat_clock.max() if flat_clock.size else 0,
                   q_clock.max() if q_clock.size else 0)) + 2
    flat_key = flat_slot * base + flat_clock
    q_key = np.where(q_slot >= 0, q_slot * base + q_clock, -1)
    return flat_key, q_key


@profiled("plan_anchor_lookup")
@jax.jit
def _anchor_lookup_jax(flat_key, q_key):
    return jnp.searchsorted(flat_key, q_key, side="right") - 1


def plan_anchor_lookup(flat_slot, flat_clock, q_slot, q_clock,
                       backend: str = "np"):
    """Candidate fragment-index position for each (q_slot, q_clock): the
    last fragment starting at or before the queried clock, or -1.  The
    caller must verify slot match + containment before trusting it."""
    flat_key, q_key = _compose_keys(flat_slot, flat_clock, q_slot, q_clock)
    if backend == "jax":
        return np.asarray(_anchor_lookup_jax(flat_key, q_key))
    return np.searchsorted(flat_key, q_key, side="right") - 1


@profiled("plan_conflict_scan")
@jax.jit
def _conflict_scan_jax(client, clock, length, o_client, o_clock,
                       r_client, r_clock):
    p_client, p_clock = client[:-1], clock[:-1]
    p_end = p_clock + length[:-1]
    left = (
        (o_client[1:] == p_client)
        & (o_client[1:] >= 0)
        & (o_clock[1:] >= p_clock)
        & (o_clock[1:] < p_end)
    )
    right = (
        (r_client[1:] == p_client)
        & (r_client[1:] >= 0)
        & (r_clock[1:] >= p_clock)
        & (r_clock[1:] < p_end)
    )
    pad = jnp.zeros(1, bool)
    left = jnp.concatenate([pad, left])
    right = jnp.concatenate([pad, right])
    run_id = jnp.cumsum(~(left | right))
    return left, right, run_id


def plan_conflict_scan(client, clock, length, o_client, o_clock,
                       r_client, r_clock, backend: str = "np"):
    """Chain masks over a clock-sorted flush batch: ``left[j]`` /
    ``right[j]`` mean ref j's origin / rightOrigin lies inside ref j-1's
    id range (so its anchor row IS ref j-1's row); ``run_id`` groups the
    maximal chained (conflict-free) runs."""
    if backend == "jax":
        l, r, g = _conflict_scan_jax(
            client, clock, length, o_client, o_clock, r_client, r_clock
        )
        return np.asarray(l), np.asarray(r), np.asarray(g)
    p_client, p_clock = client[:-1], clock[:-1]
    p_end = p_clock + length[:-1]
    left = np.zeros(len(client), bool)
    right = np.zeros(len(client), bool)
    left[1:] = (
        (o_client[1:] == p_client)
        & (o_client[1:] >= 0)
        & (o_clock[1:] >= p_clock)
        & (o_clock[1:] < p_end)
    )
    right[1:] = (
        (r_client[1:] == p_client)
        & (r_client[1:] >= 0)
        & (r_clock[1:] >= p_clock)
        & (r_clock[1:] < p_end)
    )
    run_id = np.cumsum(~(left | right))
    return left, right, run_id


@profiled("plan_chunk_conflict_scan")
@jax.jit
def _chunk_conflict_scan_jax(doc_id, client, clock, length, o_client,
                             o_clock, r_client, r_clock):
    p_client, p_clock = client[:-1], clock[:-1]
    p_end = p_clock + length[:-1]
    same_doc = doc_id[1:] == doc_id[:-1]
    left = (
        same_doc
        & (o_client[1:] == p_client)
        & (o_client[1:] >= 0)
        & (o_clock[1:] >= p_clock)
        & (o_clock[1:] < p_end)
    )
    right = (
        same_doc
        & (r_client[1:] == p_client)
        & (r_client[1:] >= 0)
        & (r_clock[1:] >= p_clock)
        & (r_clock[1:] < p_end)
    )
    pad = jnp.zeros(1, bool)
    left = jnp.concatenate([pad, left])
    right = jnp.concatenate([pad, right])
    run_id = jnp.cumsum(~(left | right))
    return left, right, run_id


def plan_chunk_conflict_scan(doc_id, client, clock, length, o_client,
                             o_clock, r_client, r_clock,
                             backend: str = "np"):
    """Doc-aware twin of :func:`plan_conflict_scan` for whole-chunk
    planning (ISSUE 15): one scan over the doc-major concatenation of
    every cold doc's flush batch.  ``doc_id`` breaks chains at doc
    boundaries so a run can never span two documents — the rest of the
    semantics match the per-doc kernel exactly."""
    if backend == "jax":
        l, r, g = _chunk_conflict_scan_jax(
            doc_id, client, clock, length, o_client, o_clock,
            r_client, r_clock
        )
        return np.asarray(l), np.asarray(r), np.asarray(g)
    p_client, p_clock = client[:-1], clock[:-1]
    p_end = p_clock + length[:-1]
    same_doc = doc_id[1:] == doc_id[:-1]
    left = np.zeros(len(client), bool)
    right = np.zeros(len(client), bool)
    left[1:] = (
        same_doc
        & (o_client[1:] == p_client)
        & (o_client[1:] >= 0)
        & (o_clock[1:] >= p_clock)
        & (o_clock[1:] < p_end)
    )
    right[1:] = (
        same_doc
        & (r_client[1:] == p_client)
        & (r_client[1:] >= 0)
        & (r_clock[1:] >= p_clock)
        & (r_clock[1:] < p_end)
    )
    run_id = np.cumsum(~(left | right))
    return left, right, run_id


# ---------------------------------------------------------------------------
# export / sync kernels
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


@profiled("state_vector_kernel")
@functools.partial(jax.jit, static_argnums=(2,))
def state_vector_kernel(row_slot, row_end, n_slots):
    """Dense per-doc state vectors: sv[b, slot] = max(clock+len) over rows —
    the segment-max recast of getStateVector (StructStore.js:49-56).

    row_slot: [B, N] i32 (NULL for unused rows), row_end: [B, N] i32.
    """
    seg = jnp.where(row_slot >= 0, row_slot, n_slots)
    f = jax.vmap(
        lambda s, e: jax.ops.segment_max(
            e, s, num_segments=n_slots + 1, indices_are_sorted=False
        )
    )
    sv = f(seg, row_end)
    sv = jnp.maximum(sv, 0)
    return sv[:, :n_slots]


@profiled("diff_mask_kernel")
@jax.jit
def diff_mask_kernel(row_slot, row_clock, row_end, sv):
    """Rows (or row suffixes) missing from a remote state vector: the
    columnar filter of writeClientsStructs (encoding.js:94-116).

    Returns (needed[B,N] bool, offset[B,N] i32): offset>0 means the row must
    be written from that element offset (the partial-first-struct rule,
    encoding.js:71-84).
    """
    safe_slot = jnp.where(row_slot >= 0, row_slot, 0)
    remote = jnp.take_along_axis(sv, safe_slot, axis=1)
    needed = (row_slot >= 0) & (row_end > remote)
    offset = jnp.clip(remote - row_clock, 0, None)
    return needed, jnp.where(needed, offset, 0)
