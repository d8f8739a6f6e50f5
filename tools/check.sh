#!/usr/bin/env bash
# One-command repo gate: kwoklint + tier-1 tests + a chaos smoke + a
# scaled bench smoke + the chip smoke's CPU dry run.  This is the CI
# entrypoint shape — each stage fails fast and loudly.  Everything here
# runs on the CPU (JAX_PLATFORMS=cpu, given explicitly); the chip is
# reached with `python chip_smoke.py` through the chip tool.
#
#   tools/check.sh            # full tier-1 (sequential, ~15 min)
#   FAST=1 tools/check.sh     # -n 4 --dist loadfile (~8 min, may flake timing gates)
#   SKIP_BENCH=1 SKIP_CHAOS=1 SKIP_SMOKE=1 tools/check.sh
set -euo pipefail

cd "$(dirname "$0")/.."

echo "== kwoklint (python -m kwok_tpu.analysis) =="
JAX_PLATFORMS=cpu python -m kwok_tpu.analysis

if [[ "${FAST:-0}" == "1" ]]; then
    # CI-annotation artifact on the fast path: the git-diff-scoped walk
    # is sub-second and the SARIF lands where code-review tooling can
    # pick it up (the full walk above still gates cross-file rules)
    echo "== kwoklint --changed-only (SARIF -> ${KWOKLINT_SARIF:-/tmp/kwoklint.sarif}) =="
    JAX_PLATFORMS=cpu python -m kwok_tpu.analysis --changed-only \
        --format sarif > "${KWOKLINT_SARIF:-/tmp/kwoklint.sarif}"
fi

echo "== tier-1 tests (pytest -m 'not slow') =="
PYTEST_ARGS=(-q -m 'not slow' -p no:cacheprovider)
if [[ "${FAST:-0}" == "1" ]]; then
    PYTEST_ARGS+=(-n 4 --dist loadfile)
fi
JAX_PLATFORMS=cpu python -m pytest tests/ "${PYTEST_ARGS[@]}"

if [[ "${SKIP_CHAOS:-0}" != "1" ]]; then
    echo "== chaos smoke (seeded faults -> WAL recovery, zero lost writes) =="
    JAX_PLATFORMS=cpu python -m kwok_tpu.chaos --smoke --pods "${CHAOS_PODS:-40}"
    echo "== corruption smoke (seeded disk faults -> detected, bounded, honest recovery) =="
    JAX_PLATFORMS=cpu python -m kwok_tpu.chaos --corruption-smoke
    echo "== exhaustion smoke (disk-full/fsync-error windows -> degraded read-only, zero lost acks) =="
    JAX_PLATFORMS=cpu python -m kwok_tpu.chaos --exhaustion-smoke
    echo "== overload smoke (best-effort flood -> 429s, canary unharmed) =="
    JAX_PLATFORMS=cpu python -m kwok_tpu.chaos --overload-smoke \
        --flood-seconds "${OVERLOAD_SECONDS:-2}"
    echo "== failover smoke (leader kill/release -> bounded takeover, fenced writes) =="
    JAX_PLATFORMS=cpu python -m kwok_tpu.chaos --failover-smoke \
        --lease-seconds "${FAILOVER_LEASE_SECONDS:-2.5}"
    echo "== fleet smoke (1k tenants on one apiserver: flood isolation, scale-to-zero, no leaks) =="
    JAX_PLATFORMS=cpu python -m kwok_tpu.chaos --fleet-smoke \
        --fleet-tenants "${FLEET_TENANTS:-1000}"
    echo "== DST smoke (whole-cluster virtual-time seeds + invariant checks; lock + race sentinels armed) =="
    # KWOK_LOCK_SENTINEL=1 arms the runtime deadlock sentinel and
    # KWOK_RACE_SENTINEL=1 the Eraser-style lockset checker
    # (kwok_tpu/utils/locks.py): every seed doubles as a lock-order
    # inversion + data-race detector, and trace digests are
    # sentinel-neutral by construction (tests/test_locks.py pins that)
    KWOK_LOCK_SENTINEL=1 KWOK_RACE_SENTINEL=1 JAX_PLATFORMS=cpu python -m kwok_tpu.chaos --dst --seeds "${DST_SEEDS:-25}"
    echo "== guided fault search smoke (coverage-guided rediscovery of an injected bug, minimized + replay-verified) =="
    # fixed search seed + small budget: the loop must find the
    # fanin-stale-resume regression, delta-debug the schedule to a
    # minimal fault set, and verify a byte-identical replay (exit 0
    # covers all three — kwok_tpu/dst/search.py)
    JAX_PLATFORMS=cpu python -m kwok_tpu.chaos --dst-search \
        --dst-bug fanin-stale-resume \
        --search-budget "${DST_SEARCH_BUDGET:-16}" --search-seed 0
fi

if [[ "${SKIP_BENCH:-0}" != "1" ]]; then
    echo "== bench smoke (BENCH_PODS-scaled) =="
    JAX_PLATFORMS=cpu \
        BENCH_PODS="${BENCH_PODS:-200}" BENCH_NODES="${BENCH_NODES:-20}" \
        BENCH_TICKS="${BENCH_TICKS:-50}" \
        BENCH_E2E_PODS="${BENCH_E2E_PODS:-200}" \
        BENCH_E2E_WINDOWS="${BENCH_E2E_WINDOWS:-1}" \
        BENCH_E2E_WINDOW_S="${BENCH_E2E_WINDOW_S:-5}" \
        BENCH_E2E_BUDGET_S="${BENCH_E2E_BUDGET_S:-60}" \
        python bench.py
fi

if [[ "${SKIP_SMOKE:-0}" != "1" ]]; then
    echo "== chip_smoke dry run (tiny sizes, CPU pinned: real daemons, counts only) =="
    # the command the chip runs with no arguments (python chip_smoke.py),
    # debugged here at a tiny size first; it labels itself cpu
    JAX_PLATFORMS=cpu python chip_smoke.py \
        --nodes "${SMOKE_NODES:-10}" --pods-per-node 20 --delete-pods 20 \
        --soa-pods 4096 --soa-nodes 64 --parity-rows 2048 --macro-ticks 3
fi

echo "== all checks passed =="
