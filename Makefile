# Developer/CI gates. `make check` is the PR gate: the JAX-pitfall lint
# must be clean over the package source, then the tier-1 test command
# (ROADMAP.md) must pass.

PY ?= python
# bash, not /bin/sh: TIER1 uses PIPESTATUS, and with a dash /bin/sh the
# old `bash -c "$(TIER1)"` indirection broke — the OUTER shell expanded
# ${PIPESTATUS[0]} inside the double quotes ("Bad substitution")
SHELL := /bin/bash
TIER1 = set -o pipefail; rm -f /tmp/_t1.log; \
	timeout -k 10 870 env JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q \
	-m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
	-p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; \
	rc=$${PIPESTATUS[0]}; \
	echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); \
	exit $$rc

.PHONY: lint conc-check serve-smoke fleet-smoke chaos-smoke \
	ingest-smoke faults-smoke trace-smoke cache-smoke multichip-smoke \
	continual-smoke costmodel-smoke roofline-smoke slo-smoke \
	parse-smoke router-smoke pod-smoke autopilot-smoke fleetobs-smoke \
	chip-rehearsal test check

lint:
	$(PY) -m transmogrifai_tpu.lint transmogrifai_tpu/

# whole-program concurrency audit (C001-C004): lock discipline,
# lock-order cycles, blocking-under-lock, generation-fence re-checks.
# Fails on any finding not in the reviewed baseline; prints the
# lock-order graph so ordering regressions are visible in CI logs.
conc-check:
	$(PY) -m transmogrifai_tpu.analysis.concurrency transmogrifai_tpu/ \
		--baseline conc_baseline.json --graph

# fault-tolerance smoke: kill a ModelSelector sweep mid-grid with an
# injected fault, resume it from the block journal, and assert the best
# config + every fold metric are bit-identical to an uninterrupted run;
# also kills a save_model mid-write and asserts the resident artifact
# survives intact. See transmogrifai_tpu/runtime/smoke.py.
faults-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m transmogrifai_tpu.runtime.smoke

# feature-cache smoke: cold dual build writes the content-addressed
# wire artifact, a rebuild HITS it (zero store reads, bit-identical
# buffers), a corrupted artifact is rejected and falls back to a
# rebuild, and the int8 quantized wire stays within tolerance at 2x
# compression. See transmogrifai_tpu/data/feature_cache.py.
cache-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m transmogrifai_tpu.data.feature_cache

# out-of-core ingest smoke: small synthetic ColumnarStore through the
# pipelined one-pass dual-representation build (data/pipeline.py) —
# asserts serial-parity results and that overlap metrics are emitted.
ingest-smoke:
	env JAX_PLATFORMS=cpu $(PY) -c "from transmogrifai_tpu.data.pipeline \
	import _smoke; raise SystemExit(_smoke())"

# end-to-end serving smoke: train tiny -> save -> boot HTTP server on a
# random port -> POST /score -> scrape /metrics (+ /healthz, /reload
# no-op) -> clean shutdown. See transmogrifai_tpu/serving/smoke.py.
serve-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m transmogrifai_tpu.serving.smoke

# fleet-serving smoke: three models (two same-shaped, one different)
# across two tenants in ONE process — the same-shaped pair shares
# compiled bucket programs (zero new traces, RetraceMonitor-asserted),
# the over-quota tenant collects the only 429s under mixed HTTP load,
# a rolling swap of one model drops zero in-flight requests for the
# others, and cold-start-to-first-score is measured without and with
# the persistent compile cache + warmup manifest. See
# transmogrifai_tpu/serving/fleet_smoke.py.
fleet-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m transmogrifai_tpu.serving.fleet_smoke

# roofline-scoring smoke: a warm service executes exactly ONE device
# dispatch per bucket per score call (whole-pipeline fusion,
# DISPATCHES-asserted), int8 scoring agrees with f32 within the stated
# wire tolerance and never adopts the f32 programs, two same-shaped
# linear tenants share one compiled program set (zero traces on the
# second, bit-identical vs solo), and scoring_bytes_per_sec is present
# and nonzero (scoring_hbm_frac only off the CPU). See
# transmogrifai_tpu/serving/roofline_smoke.py.
roofline-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m transmogrifai_tpu.serving.roofline_smoke

# serving-resilience chaos smoke: a seeded device-error storm trips one
# fleet member's breaker (HEALTHY->QUARANTINED->HEALTHY with measured
# MTTR) while degraded fallback serves from the resident previous
# version and the untouched members' traffic sees zero errors with
# bounded p99; a killed scoring thread and a stalled dispatch are both
# watchdog-recovered with every in-flight request answered (never a
# hang); a corrupt reload is rejected under concurrent traffic. See
# transmogrifai_tpu/serving/chaos.py.
chaos-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m transmogrifai_tpu.serving.chaos

# serving-autopilot smoke: the same seeded overload storm (delayed
# member + low-priority flood, gold deadline tighter than the degraded
# queue drain) is driven at a static-config fleet and an autopilot
# fleet; the static arm's gold availability collapses while the
# controller climbs the actuation ladder (rebucket re-arm -> fidelity
# flip to the resident int8 member -> predictive admission -> warm
# spare), damps gold p99 below the static arm, makes ZERO actuations
# in the healthy phase, releases every actuation after the storm, and
# every actuation event embeds the burn window that justified it. See
# transmogrifai_tpu/serving/chaos.py (run_storm / storm_main).
autopilot-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m transmogrifai_tpu.serving.chaos --storm

# distributed-sweep smoke: on 8 forced host devices, a 2-family grid
# sweep scheduled across the mesh must return the bit-identical winner
# to the single-device sweep; an injected kill of one worker preempts
# the schedule and the resume re-runs ONLY that worker's in-flight
# block (journal-shard-asserted; with blocks <= lanes every other block
# was dispatched and drains to its journal); a worker-level error is
# survived by work stealing. See transmogrifai_tpu/parallel/smoke.py.
multichip-smoke:
	$(PY) -m transmogrifai_tpu.parallel.smoke

# pod-scale sweep smoke: 2 real host scheduler PROCESSES (fresh
# interpreters, forced host meshes) claim-race one sweep's blocks
# through the shared store/ lease table; every host must report the
# bit-identical winner vs a single-host run (rows merged from the
# host-qualified journal shards); a host killed holding a block lease
# is TTL-reclaimed by a survivor process that finishes with exactly
# the dead host's unjournaled blocks re-run (journal-shard- and
# lease-attempt-asserted); measured speedup + the fleet-wide
# mesh-utilization rollup are emitted. The parent never initializes
# JAX (children force their own host meshes).
# See transmogrifai_tpu/parallel/pod_smoke.py.
pod-smoke:
	$(PY) -m transmogrifai_tpu.parallel.pod_smoke

# continuous-training smoke: drifted records appended to a live store
# fire the drift monitor, a warm-start refit runs while serving stays
# live (zero dropped requests, p99 measured during refit), the promoted
# model answers /score with a new version, and an injected holdout
# regression (runtime/faults site continual.holdout_eval) auto-rolls
# the swap back. See transmogrifai_tpu/continual/smoke.py.
continual-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m transmogrifai_tpu.continual.smoke

# observability smoke: tiny train+score through the runner with
# --trace-out; validates the Perfetto JSON (well-formed events,
# monotonic ts, parented spans), the GoodputReport buckets summing to
# ~wall time, and the correlation-id-stamped JSONL event log. See
# transmogrifai_tpu/obs/smoke.py.
trace-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m transmogrifai_tpu.obs.smoke

# observability-plane smoke: scripted traffic + one injected
# device-error storm against a served model — asserts the traceparent
# roundtrip (caller trace id echoed; queue-wait/assemble+parse/pad/
# dispatch spans under the request root), tail sampling keeping every
# error trace while head-sampling successes, the breaker-open flight
# dump validating as a Chrome trace with the failing dispatch spans,
# and the availability SLO burn-rate alert firing during the storm and
# clearing after recovery. See transmogrifai_tpu/obs/slo_smoke.py.
slo-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m transmogrifai_tpu.obs.slo_smoke

# learned-cost-model smoke: a synthetic corpus fits to holdout MAPE
# under the gate per target; then a real multi-block sweep on 8 forced
# host devices schedules count-LPT (cold model, recording its block
# rows) and predicted-LPT (refit from that corpus) — winners and fold
# metrics bit-identical, residuals recorded, packing pair reported.
# See transmogrifai_tpu/perf/smoke.py.
costmodel-smoke:
	$(PY) -m transmogrifai_tpu.perf.smoke

# host-data-plane smoke: the compiled row codec is bit-identical to the
# reference Dataset.from_rows on a hostile NaN/None/missing-key/big-int
# /object schema, a warm service assembles batches by WRITING into the
# resident staging buffers (zero fresh batch allocations across
# sustained traffic, generation-fenced across swaps), and calibrated
# int8 quantization scores the same rows bit-identically inside two
# different batch compositions. See
# transmogrifai_tpu/serving/parse_smoke.py.
parse-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m transmogrifai_tpu.serving.parse_smoke

# fleet-router smoke: two replicas over ONE shared artifact store —
# replica-2's cold start is artifact replay (store-keyed warmup
# manifest + shared compile cache, <= 1.5x a warm restart), the
# over-quota tenant 429s from EITHER replica (CAS-guarded shared
# balance), and concurrent binary-framed requests through the frontend
# score bit-identically to the JSON columnar wire. See
# transmogrifai_tpu/serving/router_smoke.py.
router-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m transmogrifai_tpu.serving.router_smoke

# fleet-observability smoke: two replica PROCESSES + a routing frontend
# over one shared store — a sampled request's W3C traceparent crosses
# the HTTP hop and the fleet merge stitches frontend + replica shards
# into ONE validate-clean Perfetto trace (100% of sampled requests);
# /metrics/fleet folds every replica's published registry snapshot; a
# seeded storm split across both replicas fires the fleet SLO alert
# EXACTLY once (CAS latch) and clears without re-firing; the firing
# replica's flight dump opens a fleet incident that every peer joins
# within the capture window, merged into one cross-host Chrome trace.
# See transmogrifai_tpu/serving/fleetobs_smoke.py.
fleetobs-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m transmogrifai_tpu.serving.fleetobs_smoke

# CPU rehearsal of the chip check: the exact chip_smoke.py command at a
# tiny size (train -> fused score -> score_stream -> cli serve f32 +
# int8-calibrated as separate processes -> 4-device host-mesh train),
# so the command is debugged here and chip time is spent on the real
# size only. Without the flag and without a TPU chip_smoke.py exits
# non-zero (tests/test_bringup.py). See chip_smoke.py.
chip-rehearsal:
	$(PY) chip_smoke.py --cpu-rehearsal

test:
	@$(TIER1)

check: lint conc-check serve-smoke parse-smoke fleet-smoke chaos-smoke \
	autopilot-smoke roofline-smoke ingest-smoke cache-smoke faults-smoke \
	trace-smoke slo-smoke multichip-smoke pod-smoke continual-smoke \
	costmodel-smoke router-smoke fleetobs-smoke chip-rehearsal test
