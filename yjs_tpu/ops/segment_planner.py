"""The Python planner's segment pass: its fast set (ISSUE 9, ISSUE 15).

``DocMirror`` (``ops/columns.py``) is the planner of a host without a
compiler and the reference the native core is held to.  Before it walks
a room's flush batch struct by struct it makes one NumPy pass over the
(client, clock)-sorted batch:

- one conflict scan detects chained runs (typing runs, prepend storms):
  the rank of each chained struct IS its placement, no per-struct walk;
- one composed-key searchsorted resolves the remaining anchors against
  the slot-major snapshot of the fragment index;
- the structs the scan cannot chain form the *conflict residue*, the
  only structs handed to the sequential YATA walk.

Hints are *candidates*, not answers: they are verified against the live
columns, and the walk falls back to its bisect on any miss, so a wrong
hint can never change placement.  Every array this module returns is
freshly allocated host memory.

Monotone-run snapshot reuse (ISSUE 15 bugfix): when the conflict scan
chains all but a handful of anchors (pure head-prepend / typing runs),
rebuilding the flat slot-major snapshot of the fragment index — a full
re-sort's worth of concatenation per flush — buys nothing.  The planner
detects that case and leaves those few anchors to the caller's per-slot
bisect against the *prior sorted segments* (the fragment index is
already clock-sorted per slot), skipping the snapshot entirely.
"""

from __future__ import annotations

import numpy as np

from . import plan_cache as _pc

NULL = -1  # must match yjs_tpu.ops.columns.NULL

# at or below this many unresolved anchors the planner reuses the
# per-slot sorted fragment segments directly (caller-side bisect per
# anchor) instead of rebuilding the flat snapshot
SNAPSHOT_SKIP_MAX = 8

# a chained run shorter than this is not worth bulk integration
MIN_RUN = 4


class SegmentQueries:
    """Anchor-query columns for one doc's flush batch, built by
    ``DocMirror._segment_queries`` after the pre-split pass.

    ``o_*`` / ``r_*`` mirror origin / rightOrigin: client -1 means the
    anchor is absent, slot -1 means the anchor's client has no slot
    (resolved by the caller's bisect fallback).  ``gc``, ``cref``,
    ``pid`` and ``pname`` carry the per-ref facts span eligibility
    needs (GC tombstone, content kind, explicit parent id / name).
    """

    __slots__ = (
        "n", "client", "clock", "length",
        "o_cl", "o_ck", "o_slot", "r_cl", "r_ck", "r_slot",
        "gc", "cref", "pid", "pname",
    )


class SegmentPlan:
    """One doc's segment pass.

    ``hint_l`` / ``hint_r`` are verified candidate anchor rows
    (``NULL`` = resolve by bisect) or ``None`` when the snapshot was
    skipped entirely; ``chain_l`` / ``chain_r`` / ``run_id`` are the
    conflict-scan chain masks; ``spans`` lists the maximal
    single-direction chained runs eligible for bulk integration as
    ``(start, end, direction)`` with direction ``'l'`` (left chains to
    the previous ref, typing runs) or ``'r'`` (right chains, prepend
    runs).  All arrays are fresh host memory.
    """

    __slots__ = (
        "hint_l", "hint_r", "chain_l", "chain_r", "run_id", "spans",
        "snapshot_reused",
    )


def plan_anchor_lookup(flat_slot, flat_clock, q_slot, q_clock):
    """Candidate fragment-index position for each (q_slot, q_clock): the
    last fragment starting at or before the queried clock, or -1.  ONE
    searchsorted over the slot-major fragment index: per-slot runs are
    clock-sorted, so the composed key ``slot * base + clock`` is globally
    sorted; an invalid query (slot < 0) maps below every real key.  The
    caller must verify slot match + containment before trusting it."""
    base = int(max(flat_clock.max() if flat_clock.size else 0,
                   q_clock.max() if q_clock.size else 0)) + 2
    flat_key = flat_slot * base + flat_clock
    q_key = np.where(q_slot >= 0, q_slot * base + q_clock, -1)
    return np.searchsorted(flat_key, q_key, side="right") - 1


def plan_conflict_scan(client, clock, length, o_client, o_clock,
                       r_client, r_clock):
    """Chain masks over a clock-sorted flush batch: ``left[j]`` /
    ``right[j]`` mean ref j's origin / rightOrigin lies inside ref j-1's
    id range (so its anchor row IS ref j-1's row); ``run_id`` groups the
    maximal chained (conflict-free) runs."""
    p_client, p_clock = client[:-1], clock[:-1]
    p_end = p_clock + length[:-1]

    def inside_previous(a_client, a_clock):
        out = np.zeros(len(client), bool)
        out[1:] = (
            (a_client[1:] == p_client)
            & (a_client[1:] >= 0)
            & (a_clock[1:] >= p_clock)
            & (a_clock[1:] < p_end)
        )
        return out

    left = inside_previous(o_client, o_clock)
    right = inside_previous(r_client, r_clock)
    run_id = np.cumsum(~(left | right))
    return left, right, run_id


def _chain_spans(q: SegmentQueries, chain_l, chain_r, run_id):
    """Maximal single-direction chained spans eligible for bulk
    integration straight from their ranks.

    A span ``(s, e, d)`` promises: refs ``s+1 .. e-1`` chain purely in
    direction ``d`` onto their predecessor, are non-GC non-delete
    content from one client with strictly ascending clocks, carry no
    explicit parent, and (for ``'l'``) share one rightOrigin id.  The
    caller integrates ref ``s`` through the normal sequential path,
    verifies the live-state preconditions, then splices the interior in
    one pass — any precondition miss simply falls back to the scalar
    loop (the residue), so placement can never differ.
    """
    n = q.n
    if n < MIN_RUN:
        return []
    chained = chain_l | chain_r
    spans = []
    # run starts: positions where the chain breaks
    starts = np.flatnonzero(~chained)
    bounds = np.append(starts, n)
    for si in range(len(starts)):
        s, e = int(bounds[si]), int(bounds[si + 1])
        if e - s < MIN_RUN:
            continue
        il, ir = chain_l[s + 1 : e], chain_r[s + 1 : e]
        if il.all() and not ir.any():
            d = "l"
        elif ir.all() and not il.any():
            d = "r"
        else:
            continue  # mixed-direction run: scalar loop handles it
        sl = slice(s, e)
        if q.gc[sl].any() or q.pid[sl].any():
            continue
        if (q.cref[sl] == 1).any():  # ContentDeleted feeds delete ranges
            continue
        if q.pname[s + 1 : e].any():  # interior must copy the neighbour seg
            continue
        if not (q.client[sl] == q.client[s]).all():
            continue
        if not (np.diff(q.clock[sl]) > 0).all():
            continue  # fragment-index append needs ascending clocks
        if d == "r":
            # prepend run: interior origins must be absent (left = NULL)
            if (q.o_cl[s + 1 : e] != -1).any():
                continue
        else:
            # typing run: one shared rightOrigin id across the interior
            if not (
                (q.r_cl[s + 1 : e] == q.r_cl[s + 1]).all()
                and (q.r_ck[s + 1 : e] == q.r_ck[s + 1]).all()
            ):
                continue
        spans.append((s, e, d))
    return spans


def _verify_hints(cand, q_slot, q_ck, flat_slot, flat_clock, flat_row,
                  row_len):
    """Containment check: a candidate only becomes a hint when the live
    columns confirm the queried clock lies inside the candidate row."""
    total = flat_clock.shape[0]
    if total == 0:
        return np.full(q_slot.shape[0], NULL, np.int64)
    safe = np.clip(cand, 0, total - 1)
    c_row = flat_row[safe]
    ok = (
        (cand >= 0)
        & (q_slot >= 0)
        & (flat_slot[safe] == q_slot)
        & (q_ck >= flat_clock[safe])
        & (q_ck < flat_clock[safe] + row_len[c_row])
    )
    return np.where(ok, c_row, NULL)


def _needed(q: SegmentQueries, chain_l, chain_r) -> int:
    """Anchors the chain masks do NOT cover — the only ones a snapshot
    lookup could resolve."""
    need_l = int(((q.o_slot >= 0) & ~chain_l).sum())
    need_r = int(((q.r_slot >= 0) & ~chain_r).sum())
    return need_l + need_r


def plan_doc(q: SegmentQueries | None, snapshot=None) -> SegmentPlan | None:
    """Plan one doc's flush batch.  ``snapshot`` is a zero-arg callable
    returning ``(flat_slot, flat_clock, flat_row, row_len, n_slots)``
    (the slot-major fragment-index snapshot); it is only invoked when
    the chain masks leave enough anchors unresolved to justify the
    rebuild."""
    if q is None or q.n < MIN_RUN:
        return None
    chain_l, chain_r, run_id = plan_conflict_scan(
        q.client, q.clock, q.length, q.o_cl, q.o_ck, q.r_cl, q.r_ck
    )
    plan = SegmentPlan()
    plan.chain_l, plan.chain_r, plan.run_id = chain_l, chain_r, run_id
    plan.spans = _chain_spans(q, chain_l, chain_r, run_id)
    plan.hint_l = plan.hint_r = None
    plan.snapshot_reused = False
    if snapshot is None or _needed(q, chain_l, chain_r) <= SNAPSHOT_SKIP_MAX:
        # monotone chained run: the prior per-slot sorted segments are
        # reused as-is by the caller's bisect — no snapshot rebuild
        plan.snapshot_reused = True
        _pc.note_snapshot_reuse()
        return plan
    flat_slot, flat_clock, flat_row, row_len, _n_slots = snapshot()
    q_slot = np.concatenate([q.o_slot, q.r_slot])
    q_ck = np.concatenate([q.o_ck, q.r_ck])
    cand = plan_anchor_lookup(flat_slot, flat_clock, q_slot, q_ck)
    hint = _verify_hints(
        cand, q_slot, q_ck, flat_slot, flat_clock, flat_row, row_len
    )
    plan.hint_l, plan.hint_r = hint[: q.n], hint[q.n :]
    return plan
