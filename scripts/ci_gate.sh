#!/usr/bin/env bash
# One CI entrypoint (ISSUE 16): tier-1 tests, strict lint, the telemetry
# schema contract (every --require-* tier against ONE smoke-run JSONL),
# the fused lane-sharding parity verdict and the router failover smoke —
# with a greppable `CI_GATE <stage> PASS|FAIL` line per stage and a
# nonzero exit when any stage fails. Stages keep running after a failure
# so one invocation reports the full picture.
#
# Usage:
#   bash scripts/ci_gate.sh                 # all stages
#   CI_GATE_SKIP_TESTS=1 bash scripts/ci_gate.sh   # skip the pytest leg
set -u
REPO="$(cd "$(dirname "$0")/.." && pwd)"
cd "$REPO"

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

FAILED=0
declare -a SUMMARY=()

report() {  # report <stage> <rc>
    local stage="$1" rc="$2"
    if [ "$rc" -eq 0 ]; then
        echo "CI_GATE ${stage} PASS"
        SUMMARY+=("${stage}: PASS")
    else
        echo "CI_GATE ${stage} FAIL (rc=${rc})"
        SUMMARY+=("${stage}: FAIL")
        FAILED=1
    fi
}

# -- stage 1: tier-1 pytest ------------------------------------------------
if [ "${CI_GATE_SKIP_TESTS:-0}" = "1" ]; then
    echo "CI_GATE tests SKIP (CI_GATE_SKIP_TESTS=1)"
    SUMMARY+=("tests: SKIP")
else
    python -m pytest tests/ -q -m 'not slow' \
        --continue-on-collection-errors -p no:cacheprovider
    report tests $?
fi

# -- stage 2: strict lint --------------------------------------------------
python -m dotaclient_tpu.lint --strict
report lint $?

# -- stage 3: telemetry schema (all learner tiers, one smoke JSONL) --------
# One smoke run produces the JSONL; every learner-JSONL tier validates
# against it (the eager-creation contract each tier documents). The
# serve tier is a different process class (own JSONL) — exercised by
# tests/test_serve.py, not this stage.
SMOKE_JSONL="$(mktemp /tmp/ci_gate_smoke_XXXXXX.jsonl)"
trap 'rm -f "$SMOKE_JSONL"' EXIT
python -m dotaclient_tpu.train.learner \
    --smoke --steps 2 --metrics-jsonl "$SMOKE_JSONL"
SMOKE_RC=$?
if [ "$SMOKE_RC" -ne 0 ]; then
    report schema_smoke "$SMOKE_RC"
else
    python scripts/check_telemetry_schema.py --path "$SMOKE_JSONL" \
        --require-snapshot --require-health --require-trace \
        --require-fleet --require-outcome --require-advantage \
        --require-multichip --require-utilization
    report schema $?
fi

# -- stage 4: fused lane-sharding parity (PR 18) ---------------------------
# The 1-vs-2 forced-host shape of the fused-parity verdict: the
# lane-sharded one-dispatch program must produce a matching rollout
# digest (1e-7 relative), Adam-tolerance losses, a 1e-5 param checksum,
# AND the compiled lane-sharding proof. The fast always-on pin; the same
# tool takes any N (`--fused-parity 8`).
python scripts/run_multichip.py --fused-parity 2 --steps 2 --parity-steps 2
report fused_parity $?

# -- stage 5: router failover smoke (ISSUE 19) -----------------------------
# In-process serve-fleet failover: three tiny backends, a session-affine
# router, a mid-game backend kill — the re-home must land bit-exact
# (parity digest "bitwise", exit 0 iff so) and the router's JSONL must
# carry the eagerly-created router/* schema tier.
ROUTER_JSONL="$(mktemp /tmp/ci_gate_router_XXXXXX.jsonl)"
trap 'rm -f "$SMOKE_JSONL" "$ROUTER_JSONL"' EXIT
python scripts/serve_loadgen.py --rehome-parity --metrics-jsonl "$ROUTER_JSONL"
ROUTER_RC=$?
if [ "$ROUTER_RC" -ne 0 ]; then
    report router_failover "$ROUTER_RC"
else
    python scripts/check_telemetry_schema.py --path "$ROUTER_JSONL" \
        --require-router
    report router_failover $?
fi

echo "== ci_gate summary =="
for line in "${SUMMARY[@]}"; do
    echo "  $line"
done
exit "$FAILED"
