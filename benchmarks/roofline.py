"""The table of peaks, and the bytes the device kernels must move.

``apply_plan2`` and ``scatter_rows`` (``yjs_tpu/ops/kernels.py``; on a
mesh the same bodies under ``shard_map``) do no arithmetic to speak of:
they are scatters, so HBM bandwidth is the roof and bytes are what is
counted.  Both counts are the least the work needs, from the shapes the
run's counters give, never what an implementation happens to move:

- ``apply_plan2``: each real link write reads its value lane from the
  staged lanes and writes one int32 of ``right_link``.  Row-index lanes
  of sparse writes, segment heads, delete marks and the per-doc counts
  are left out: the engine's counters do not give them, and leaving
  them out keeps the count a lower bound (the share can only read low).
- ``scatter_rows``: each rebuilt room reads its new ``right`` (int32)
  and ``deleted`` (bool) rows of ``cap + 1`` entries and its ``starts``
  row of ``seg_cap + 1`` int32, and writes them into the resident tables.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {PEAKS}: add them, "
            "with their source"
        )
    return table[device_kind]


def lane_bytes(cap: int) -> int:
    """Lanes travel as int16 where every row index fits, else int32."""
    return 2 if cap + 1 <= 32767 else 4


def apply_plan2_bytes(link_writes: int, cap: int) -> int:
    return link_writes * (lane_bytes(cap) + 4)


def scatter_rows_bytes(rows: int, cap: int, seg_cap: int) -> int:
    return 2 * rows * ((cap + 1) * (4 + 1) + (seg_cap + 1) * 4)
