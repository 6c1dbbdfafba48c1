#!/usr/bin/env bash
# Tier-1 CI gate: reproducible verify command with pinned deps.
#
#   ./ci.sh            run the FULL tier-1 test suite (includes the slow
#                      interpret-mode Pallas sweeps and subprocess tests)
#   ./ci.sh --fast     inner-loop tier: skip tests marked pallas/slow
#                      (see [tool.pytest.ini_options].markers), then run the
#                      docs smokes (docs-check + examples/quickstart.py, the
#                      README front door), the engine smokes (single-device
#                      poisson trace + the sharded engine on a forced
#                      2-device host-platform mesh, per-step and with the
#                      k=8 scanned decode chunk), the chaos smoke (mid-trace
#                      corrupt+kill with drain + hot reprogram; fails on a
#                      lost request or ledger drift), the paged-engine smokes
#                      (prefix-cache exactly-once + chunked prefill, verified
#                      via --paged-verify), and the kernel
#                      perf-smoke (bench_kernels in interpret mode, writes
#                      BENCH_kernels.json, fails on check regression)
#   ./ci.sh --install  pip-install pinned deps first (no-op in the baked image)
set -euo pipefail
cd "$(dirname "$0")"

if [[ "${1:-}" == "--install" ]]; then
    python -m pip install -r requirements.txt
    shift
fi

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# Hygiene gate: compiled artifacts must never be tracked. A stray .pyc in
# the index silently shadows source edits for anyone importing the package.
if git ls-files -- '*.pyc' '*__pycache__*' | grep -q .; then
    echo "CI FAILURE: compiled python artifacts are tracked in git:" >&2
    git ls-files -- '*.pyc' '*__pycache__*' >&2
    exit 1
fi

if [[ "${1:-}" == "--fast" ]]; then
    python -m pytest -x -q -m "not pallas and not slow"
    echo "== docs-smoke: file references + README quickstart =="
    python tools/docs_check.py
    python examples/quickstart.py
    echo "== engine smoke: continuous-batching serve (poisson trace) =="
    python -m repro.launch.serve --arch granite-8b --smoke --requests 4 \
        --prompt-len 8 --gen 4 --slots 2 --trace poisson:300 --exec aimc
    echo "== engine smoke: sharded engine on a 2-device host-platform mesh =="
    XLA_FLAGS="--xla_force_host_platform_device_count=2 ${XLA_FLAGS:-}" \
        python -m repro.launch.serve --arch granite-8b --smoke --requests 4 \
        --prompt-len 8 --gen 4 --slots 2 --trace poisson:300 --exec aimc \
        --cores 2 --mesh data:2,model:1
    echo "== engine smoke: chunked decode (k=8 scan) on the 2-device mesh =="
    XLA_FLAGS="--xla_force_host_platform_device_count=2 ${XLA_FLAGS:-}" \
        python -m repro.launch.serve --arch granite-8b --smoke --requests 4 \
        --prompt-len 8 --gen 4 --slots 2 --trace poisson:300 --exec aimc \
        --cores 2 --mesh data:2,model:1 --decode-chunk 8
    echo "== chaos smoke: mid-trace corrupt+kill, drain + hot reprogram =="
    # exits nonzero if any in-flight request is lost, a scheduled fault
    # never fires, or the CM_* / recal-CM_INITIALIZE ledgers drift
    # (DESIGN.md §14; launch.serve._verify_resilience)
    python -m repro.launch.serve --arch granite-8b --smoke --requests 6 \
        --prompt-len 8 --gen 6 --slots 3 --trace poisson:300 --exec aimc \
        --cores 2 --decode-chunk 2 --chaos "corrupt:0@1:0.5,kill:1@3"
    echo "== paged smoke: prefix cache, shared span prefilled exactly once =="
    # 8 requests share one 8-token system prompt on the paged engine with
    # the content-hashed prefix cache; --paged-verify exits nonzero unless
    # the shared span was prefilled exactly once, the page ledger
    # reconciles, and nothing recompiled after warmup (DESIGN.md §15)
    python -m repro.launch.serve --arch granite-8b --smoke --requests 8 \
        --prompt-len 12 --gen 6 --slots 4 --exec aimc \
        --page-size 4 --prefix-cache --shared-prefix 8 --paged-verify
    echo "== paged smoke: chunked prefill interleaved with decode =="
    python -m repro.launch.serve --arch granite-8b --smoke --requests 6 \
        --prompt-len 12 --gen 4 --slots 3 --trace poisson:300 --exec aimc \
        --page-size 4 --prefix-cache --prefill-chunk 4 --paged-verify
    echo "== server smoke: two models co-programmed, mixed-tenant trace =="
    # exits nonzero if per-tenant ledgers fail to reconcile or any tenant
    # with requests is starved of all tokens (runtime.server front door)
    python -m repro.launch.serve --smoke \
        --models granite-8b:aimc,xlstm-350m:digital \
        --tenants premium:granite-8b:2,standard:granite-8b:1:sjf,batch:xlstm-350m \
        --requests 8 --prompt-len 8 --gen 4 --slots 2 --trace poisson:200
    echo "== placement smoke: auto split, forced overflow rotation =="
    # budget 2 overflows the smoke model: serving time-multiplexes a
    # 2-state rotation plan; --placement-verify exits nonzero unless all
    # requests are served bit-equal to the all-digital oracle, every
    # rotation state packs within budget, the per-swap CM_INITIALIZE
    # books reconcile, and nothing recompiled after warmup (DESIGN.md §16)
    python -m repro.launch.serve --arch granite-8b --smoke --exec aimc \
        --placement auto:2 --tile-rows 64 --adc-alpha 0.5 --requests 4 \
        --prompt-len 8 --gen 6 --seed 1 --placement-verify
    echo "== perf-smoke: bench_kernels (interpret mode) =="
    exec python -m benchmarks.bench_kernels --json BENCH_kernels.json
fi
exec python -m pytest -x -q
