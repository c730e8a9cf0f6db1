#!/usr/bin/env bash
# One-shot pre-push gate: both static engines, then their test suites.
#
#   scripts/check.sh          # analysis gate + jaxlint/contracts suites
#   scripts/check.sh --full   # ...then the full fast tier-1 suite
#
# Mirrors what CI runs (docs/testing.md "One-shot gate"). Exit is the
# first failing stage's; later stages are skipped so the shortest
# feedback loop stays the default.
set -u -o pipefail

repo="$(cd "$(dirname "$0")/.." && pwd)"
cd "$repo"

run() {
    echo "==> $*"
    "$@" || exit $?
}

# 1. Static analysis: jaxlint rules + cross-artifact contracts, gated
#    on the committed baseline and contracts.json. Exit 1 here means a
#    new finding or contract drift — fix it, suppress it with a
#    reasoned `# jaxlint: disable=`, or (for contract changes made on
#    purpose) regenerate the inventory with --write-inventory.
run env JAX_PLATFORMS=cpu python -m relayrl_tpu.analysis

# 2. The engines' own test suites (rule units, fixture passes, the
#    repo-wide gates) — fast, no accelerator.
run env JAX_PLATFORMS=cpu python -m pytest tests/test_jaxlint.py \
    tests/test_contracts.py -q -p no:cacheprovider

# 3. Optional: the whole fast tier-1 wall (~12 min on a 2-core host).
if [ "${1:-}" = "--full" ]; then
    run env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m "not slow" \
        --continue-on-collection-errors -p no:cacheprovider
fi

echo "check.sh: all stages passed"
