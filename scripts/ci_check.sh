#!/usr/bin/env bash
# Repo CI gate: the metrics/docs schema check plus the fast test tier.
# Run from anywhere; JAX_PLATFORMS defaults to cpu (override to target
# an accelerator).
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

echo "== static analysis (ytpu-lint) =="
# the pure-ast checker suite (ISSUE 13): donation-aliasing, retrace
# hazards, lock discipline/ordering, seam completeness, knob/metric
# drift — exits nonzero on any unsuppressed finding or stale baseline
python scripts/ytpu_lint.py --ci

echo "== metrics schema =="
python scripts/check_metrics_schema.py

echo "== trace validity (check_trace selftest) =="
# builds a 3-shard replicated fleet with everything sampled and
# validates the merged Perfetto trace: all flow arrows resolve, every
# sampled chain completes origin -> visible (ISSUE 11)
python scripts/check_trace.py --selftest

if [[ "${YTPU_CI_BENCH:-0}" == "1" ]]; then
    echo "== bench-regression gate (YTPU_CI_BENCH=1) =="
    # opt-in: re-runs the headline bench blocks (minutes) and diffs
    # against the committed BENCH_*.json baselines (ISSUE 16)
    python scripts/check_bench.py
fi

echo "== telemetry history smoke (marker: tsdb) =="
# the embedded TSDB (ISSUE 19) is the newest subsystem: codec
# round-trips, downsample-tier oracles, torn-read hammers, and
# crash-truncation reload regressions surface fast and isolated
python -m pytest tests/ -q -m 'tsdb and not slow' -p no:cacheprovider

echo "== cost attribution smoke (marker: cost) =="
# the per-doc/per-tenant cost ledger + capacity model (ISSUE 19):
# attribution proportionality, top-K cardinality bounds, and the
# TSDB-derived sessions-per-device knee
python -m pytest tests/ -q -m 'cost and not slow' -p no:cacheprovider

echo "== geo replication smoke (marker: geo) =="
# the multi-region active-active suite (ISSUE 17) is the newest
# subsystem: doc-space codecs, the budgeted WAN delta scheduler,
# one-way-partition/flap chaos convergence, and journaled-floor
# resume-after-kill regressions surface fast and isolated
python -m pytest tests/ -q -m 'geo and not slow' -p no:cacheprovider

echo "== admin plane smoke (marker: admin) =="
# the per-process introspection plane (ISSUE 16): endpoint unit tests,
# readiness/fencing semantics, scrape-race hardening, and the
# concurrent-scrape hammer
python -m pytest tests/ -q -m 'admin and not slow' -p no:cacheprovider

echo "== cluster smoke (marker: cluster) =="
# the process-native cluster suite (ISSUE 14) is the newest subsystem:
# real OS-process shards behind the y-websocket gateway — kill -9
# recovery, replica failover, wire-compat, launcher, and supervision
# panel regressions surface fast and isolated
python -m pytest tests/ -q -m 'cluster and not slow' -p no:cacheprovider

echo "== analysis smoke (marker: analysis) =="
# the ytpu-lint framework suite (ISSUE 13): fixture corpus, suppression
# and baseline round-trips, and the whole-repo self-run
python -m pytest tests/ -q -m 'analysis and not slow' -p no:cacheprovider

echo "== flush pipeline smoke (marker: flushpipe) =="
# the pipelined-flush + donation + adaptive-tick suite (ISSUE 12) is
# the newest subsystem: pipeline-on/off byte-identity, donation
# aliasing, and tick-controller regressions surface fast and isolated
python -m pytest tests/ -q -m 'flushpipe and not slow' -p no:cacheprovider

echo "== tracing smoke (marker: tracing) =="
# the causal-tracing + flight-recorder + federation suite (ISSUE 11)
# is the newest subsystem: context-propagation, envelope-compat, and
# merge-semantics regressions surface fast and isolated
python -m pytest tests/ -q -m 'tracing and not slow' -p no:cacheprovider

echo "== admission smoke (marker: admission) =="
# the rate-limit + brownout suite (ISSUE 10) is the newest subsystem:
# bucket/fair-queue, hysteresis, and BUSY-backpressure regressions
# surface fast and isolated
python -m pytest tests/ -q -m 'admission and not slow' -p no:cacheprovider

echo "== overload harness smoke (marker: loadgen) =="
# the seeded multi-tenant overload harness (ISSUE 10): acked-loss /
# convergence / SLO-protection invariants under >2x offered load
python -m pytest tests/ -q -m 'loadgen and not slow' -p no:cacheprovider

echo "== planner smoke (marker: planner) =="
# the plan-cache + segment-planning suite (ISSUE 9/15) is the newest
# subsystem: cache-aliasing and fast-path-divergence regressions
# surface fast and isolated
python -m pytest tests/ -q -m 'planner and not slow' -p no:cacheprovider

echo "== failover smoke (marker: failover) =="
# the replication + failure-detection suite (ISSUE 8) is the newest
# subsystem: fan-out, detector, promotion, and fencing regressions
# surface fast and isolated
python -m pytest tests/ -q -m 'failover and not slow' -p no:cacheprovider

echo "== tiering smoke (marker: tiering) =="
# the doc-lifecycle suite (ISSUE 7) is the newest subsystem: demotion /
# promotion / recovery-placement regressions surface fast and isolated
python -m pytest tests/ -q -m 'tiering and not slow' -p no:cacheprovider

echo "== fleet smoke (marker: fleet) =="
# the sharded-fleet suite (ISSUE 6) runs first as a fast standalone
# smoke: routing, migration, and recovery regressions surface before
# the full tier sinks time into everything else
python -m pytest tests/ -q -m 'fleet and not slow' -p no:cacheprovider

echo "== tier-1 tests (not slow) =="
# includes the chaos / durability / network / fleet marker suites (all
# deterministic); deselect one with e.g. -m 'not slow and not network'
python -m pytest tests/ -q -m 'not slow' -p no:cacheprovider
