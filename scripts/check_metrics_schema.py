#!/usr/bin/env python
"""Diff the LIVE registered metric names against README's documented list.

Thin shim over :func:`yjs_tpu.analysis.drift.live_comparison` — the
knob/metric drift logic moved into the ytpu-lint static-analysis suite
(``scripts/ytpu_lint.py``, rules ``knob-drift`` / ``metric-drift``),
which additionally checks at the AST level that every ``YTPU_*`` env
read and literal ``ytpu_*`` registration is documented.  This script
keeps the original live half: instantiate a provider + the smallest
fleet, extract the registered family names, and fail when they and the
README Observability table disagree (plus the curated-prefix env-knob
cross-check).  Wired into scripts/ci_check.sh and runnable standalone:

    python scripts/check_metrics_schema.py
"""

from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from yjs_tpu.analysis.drift import (  # noqa: E402
    KNOB_RE,
    documented_metrics,
    live_comparison,
    live_metric_names,
)


# -- original module API, kept for the tier-1 tests that import it ------------

def documented_names(readme_text: str) -> set[str]:
    """Backticked ytpu_* names from the Observability metric table."""
    return documented_metrics(readme_text)


def registered_names() -> set[str]:
    """Live family names, every lazily registered holder touched."""
    return live_metric_names()


def resilience_knobs_in_code() -> set[str]:
    """Curated-prefix env names the package actually mentions."""
    knobs: set[str] = set()
    for path in (ROOT / "yjs_tpu").rglob("*.py"):
        knobs |= set(KNOB_RE.findall(path.read_text()))
    return knobs


def main() -> int:
    problems = live_comparison(ROOT)
    for p in problems:
        print(p)
    if problems:
        return 1
    print("ok: live metric families and env knobs agree with README")
    return 0


if __name__ == "__main__":
    sys.exit(main())
