#!/usr/bin/env bash
# Fast gate for CI and pre-commit: collection must be CLEAN (a single
# collection error silently masks an entire test module, which is how
# the seed shipped with 29 uncollectable modules), then the non-slow
# subset must pass.
#
#   bash tests/smoke.sh            # collection check + non-slow subset
#   bash tests/smoke.sh --collect  # collection check only (seconds)
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
# smoke_*.py scripts run as `python tests/foo.py` — put the repo root on
# the import path so fabric_tpu resolves without an install
export PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}"
echo "== pytest collection (must be error-free) =="
collect_out=$(python -m pytest tests/ -q --collect-only -p no:cacheprovider 2>&1 | tail -5)
echo "$collect_out"
if echo "$collect_out" | grep -qiE "error"; then
    echo "FAIL: test collection has errors" >&2
    exit 1
fi

if [[ "${1:-}" == "--collect" ]]; then
    echo "OK: collection clean"
    exit 0
fi

echo "== live trace endpoints (/traces, /spans/stats) =="
python tests/smoke_traces.py

echo "== cluster trace assembly (3 OS processes, ?cluster=1 merge) =="
python tests/smoke_cluster_trace.py

echo "== seeded chaos probe (fault plane + convergence) =="
python tests/smoke_chaos.py

echo "== telemetry + SLO probe (/metrics, /slo, /gateway, node.top) =="
python tests/smoke_metrics.py

echo "== verify-once probe (speculative coverage, zero cache rejects) =="
python tests/smoke_verify_once.py

echo "== native streamed-window probe (C tail/gate vs Python mirror) =="
python tests/smoke_window.py

echo "== sharded mesh window probe (8 virtual devices, divergence gate) =="
python tests/smoke_mesh.py

echo "== overload probe (open-loop 2x saturation, admission shed + recovery) =="
python tests/smoke_overload.py

echo "== snapshot rejoin drill (wiped peer, faulted transfer, tail-bounded) =="
python tests/smoke_snapshot.py

echo "== byzantine scenario drills (equivocation containment + crash-stop control) =="
python tests/smoke_scenarios.py

echo "== rolling upgrade drill (drain+restart every node under load, no height regression) =="
python tests/smoke_rolling_upgrade.py

echo "== two-faced orderer drill (fraud-proof gossip, network-wide conviction) =="
python tests/smoke_proof_gossip.py

echo "== compressed-soak leak gate (Theil-Sen over resource series, honest + injected fd leak) =="
python tests/smoke_soak.py

echo "== incident capture drill (SLO burn -> verified 3-node flight-recorder bundle) =="
python tests/smoke_incident.py

echo "== ASan/UBSan fuzz corpus vs the native wire parser and the MVCC pass =="
# Build _fastparse with the sanitizers and drive the full adversarial
# corpus (tests/test_fastparse.py --asan-corpus) through it: any heap
# overflow / UB in the span parser aborts here instead of shipping.
# _fastmvcc the same way (tests/test_fastmvcc.py --asan-corpus: the key
# hash, the walk over random and malformed lanes, the version fetch).
# Skipped gracefully when the toolchain lacks the sanitizer runtimes.
san_tmp=$(mktemp -d)
trap 'rm -rf "$san_tmp"' EXIT
if echo 'int main(void){return 0;}' > "$san_tmp/probe.c" \
   && "${CC:-cc}" -fsanitize=address,undefined -O1 \
        "$san_tmp/probe.c" -o "$san_tmp/probe" 2>/dev/null \
   && "$san_tmp/probe"; then
    "${CC:-cc}" -fsanitize=address,undefined -fno-sanitize-recover=all \
        -O1 -g -shared -fPIC -Wall -Wextra -Werror \
        -I"$(python -c 'import sysconfig;print(sysconfig.get_path("include"))')" \
        fabric_tpu/native/fastparse.c -o "$san_tmp/_fastparse.so"
    LD_PRELOAD="$("${CC:-cc}" -print-file-name=libasan.so)" \
    ASAN_OPTIONS=detect_leaks=0 \
    PYTHONPATH="$san_tmp:$PYTHONPATH" \
        python tests/test_fastparse.py --asan-corpus
    "${CC:-cc}" -fsanitize=address,undefined -fno-sanitize-recover=all \
        -O1 -g -shared -fPIC -Wall -Wextra -Werror \
        -I"$(python -c 'import sysconfig;print(sysconfig.get_path("include"))')" \
        fabric_tpu/native/fastmvcc.c -o "$san_tmp/_fastmvcc.so"
    LD_PRELOAD="$("${CC:-cc}" -print-file-name=libasan.so)" \
    ASAN_OPTIONS=detect_leaks=0 \
    PYTHONPATH="$san_tmp:$PYTHONPATH" \
        python tests/test_fastmvcc.py --asan-corpus
else
    echo "skip: sanitizer toolchain unavailable"
fi

echo "== non-slow test subset =="
python -m pytest tests/ -q -m 'not slow' -p no:cacheprovider
echo "OK: smoke passed"
