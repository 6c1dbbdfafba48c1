PY ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH),)

.PHONY: tier1 test test-fast test-all bench bench-pipeline bench-json \
        bench-serving bench-server serve-aimc serve-aimc-reprogram \
        serve-aimc-multicore serve-smoke serve-sharded serve-multi \
        serve-chaos serve-drift serve-paged serve-auto docs-check

# Tier-1 verify: the gate every PR must keep green (runs everything).
tier1:
	$(PY) -m pytest -x -q

test:
	$(PY) -m pytest -q

# Tier split (pytest markers, see pyproject.toml): `test-fast` skips the
# slow interpret-mode Pallas sweeps and multi-process system tests for a
# quick inner loop; `test-all` is the full tier (identical scope to tier1,
# without -x so every failure reports).
test-fast:
	$(PY) -m pytest -q -m "not pallas and not slow"

test-all:
	$(PY) -m pytest -q

bench:
	$(PY) -m benchmarks.run

# Multi-core schedule benchmarks alone (measured vs predicted).
bench-pipeline:
	$(PY) -m benchmarks.bench_pipeline

# Machine-readable benchmark artifact: per-case wall-clock, modeled latency
# and check pass/fail (the cross-PR perf-trajectory record). The full suite
# writes BENCH_all.json — including the sharded-engine serving checks on a
# forced 2-device mesh; the kernel perf-smoke alone writes
# BENCH_kernels.json (same artifact ci.sh --fast produces). A partial run
# (crashed sub-bench, --only) refuses to overwrite a complete BENCH_all.json.
bench-json:
	$(PY) -m benchmarks.run --mesh data:2,model:1 --json BENCH_all.json
	$(PY) -m benchmarks.bench_kernels --json BENCH_kernels.json

# Serving-engine benchmark alone (continuous batching vs static batch,
# PLUS the sharded engine vs single-device on a forced 2-device
# host-platform mesh: bit-equality + ledger reconciliation are the bar).
bench-serving:
	$(PY) -m benchmarks.bench_serving --mesh data:2,model:1 \
	    --json BENCH_serving.json

# Multi-tenant server benchmark alone (two models on one crossbar pool:
# per-tenant tok/s + TTFT/TPOT percentiles, quota fairness under a
# saturated contended window, exact per-tenant ledger reconciliation).
bench-server:
	$(PY) -m benchmarks.bench_server --json BENCH_server.json

# Docs link-rot gate: every file path README/DESIGN/EXPERIMENTS/ROADMAP
# mention must exist (tools/docs_check.py; part of ci.sh --fast).
docs-check:
	$(PY) tools/docs_check.py

# Continuous-batching engine smoke: a ragged Poisson trace through the
# programmed AIMC path (the ci.sh --fast engine smoke, runnable alone).
serve-smoke:
	$(PY) -m repro.launch.serve --arch granite-8b --smoke --requests 4 \
	    --prompt-len 8 --gen 4 --slots 2 --trace poisson:300 --exec aimc

# Program-once AIMC serving vs the legacy per-call-reprogram path (A/B for
# the program API speedup; see DESIGN.md §2).
serve-aimc:
	$(PY) -m repro.launch.serve --arch granite-8b --smoke --exec aimc

serve-aimc-reprogram:
	$(PY) -m repro.launch.serve --arch granite-8b --smoke --exec aimc --reprogram

# Multi-core AIMC serving: matrices spread over 4 per-core tile contexts,
# per-core CM_*/comm ledgers + modeled latency reported (core.schedule).
serve-aimc-multicore:
	$(PY) -m repro.launch.serve --arch granite-8b --smoke --exec aimc --cores 4

# Sharded serving smoke: the continuous-batching engine over a forced
# 2-device host-platform mesh (slots over data, crossbar bit lines over
# model; DESIGN.md §11) with per-device ledger reporting.
serve-sharded:
	XLA_FLAGS="--xla_force_host_platform_device_count=2 $(XLA_FLAGS)" \
	$(PY) -m repro.launch.serve --arch granite-8b --smoke --requests 4 \
	    --prompt-len 8 --gen 4 --slots 2 --trace poisson:300 --exec aimc \
	    --cores 2 --mesh data:2,model:1

# Chaos smoke: deterministic mid-trace faults (tile corruption at chunk 1,
# core kill at chunk 3) through the drift/health/chaos tick (DESIGN.md §14).
# The engine must detect via probe, drain the dead core onto its peer,
# hot-reprogram bit-exactly, and close the CM_* + recal-CM_INITIALIZE books
# exactly — exits nonzero on a lost request, an unfired fault, or ledger
# drift. Same invocation as the ci.sh --fast chaos smoke.
serve-chaos:
	$(PY) -m repro.launch.serve --arch granite-8b --smoke --requests 6 \
	    --prompt-len 8 --gen 6 --slots 3 --trace poisson:300 --exec aimc \
	    --cores 2 --decode-chunk 2 --chaos "corrupt:0@1:0.5,kill:1@3"

# Drift-aware serving smoke: power-law conductance decay on the serve clock
# with online probes and threshold-triggered hot recalibration.
serve-drift:
	$(PY) -m repro.launch.serve --arch granite-8b --smoke --requests 6 \
	    --prompt-len 8 --gen 8 --slots 3 --trace poisson:300 --exec aimc \
	    --cores 2 --decode-chunk 2 --drift 0.3 --drift-t0 0.01

# Paged-engine smoke: fixed-size KV pages + content-hashed prefix cache on
# a shared-system-prompt trace (DESIGN.md §15). --paged-verify exits
# nonzero unless the shared span is prefilled exactly once, the page
# ledger reconciles exactly, and nothing recompiles after warmup. Same
# invocation as the ci.sh --fast paged smoke.
serve-paged:
	$(PY) -m repro.launch.serve --arch granite-8b --smoke --requests 8 \
	    --prompt-len 12 --gen 6 --slots 4 --exec aimc \
	    --page-size 4 --prefix-cache --shared-prefix 8 --paged-verify

# Auto-placement smoke: the cost-model placer picks the analog/digital
# split under a 2-tile budget — the smoke model overflows, so serving
# time-multiplexes a 2-state rotation plan, billing CM_INITIALIZE per
# swap (DESIGN.md §16). --placement-verify exits nonzero unless tokens
# are bit-equal to the all-digital oracle, every state packs within
# budget, and the swap books reconcile. Same invocation as the ci.sh
# --fast placement smoke.
serve-auto:
	$(PY) -m repro.launch.serve --arch granite-8b --smoke --exec aimc \
	    --placement auto:2 --tile-rows 64 --adc-alpha 0.5 --requests 4 \
	    --prompt-len 8 --gen 6 --seed 1 --placement-verify

# Multi-tenant serving smoke: two models resident in one process (granite
# co-programmed on the shared TilePool, xlstm digital), interleaved
# Poisson traffic with weighted tenant quotas (DESIGN.md §12); exits
# nonzero on ledger-reconciliation failure or a starved tenant.
serve-multi:
	$(PY) -m repro.launch.serve --smoke \
	    --models granite-8b:aimc,xlstm-350m:digital \
	    --tenants premium:granite-8b:2,standard:granite-8b:1:sjf,batch:xlstm-350m \
	    --requests 8 --prompt-len 8 --gen 4 --slots 2 --trace poisson:200
