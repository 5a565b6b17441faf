# Convenience entry points; CI runs `make lint test`.
# JAX_PLATFORMS=cpu keeps both off any attached accelerator.

PY ?= python
TUTORIAL ?= /root/reference/example_data/tutorial.fil
SMOKE_DIR ?= /tmp/peasoup-trace-smoke
SERVE_SMOKE_DIR ?= /tmp/peasoup-serve-smoke
FLEET_SMOKE_DIR ?= /tmp/peasoup-fleet-smoke
BATCH_SMOKE_DIR ?= /tmp/peasoup-batch-smoke
HEALTH_SMOKE_DIR ?= /tmp/peasoup-health-smoke
PIPELINE_SMOKE_DIR ?= /tmp/peasoup-pipeline-smoke
LOADGEN_SMOKE_DIR ?= /tmp/peasoup-loadgen-smoke
JERK_SMOKE_DIR ?= /tmp/peasoup-jerk-smoke
SENSITIVITY_SMOKE_DIR ?= /tmp/peasoup-sensitivity-smoke
CHAOS_SMOKE_DIR ?= /tmp/peasoup-chaos-smoke
OBS_SMOKE_DIR ?= /tmp/peasoup-obs-smoke
ANALYSIS_SMOKE_DIR ?= /tmp/peasoup-analysis-smoke
COLDSTART_SMOKE_DIR ?= /tmp/peasoup-coldstart-smoke
LINEAGE_SMOKE_DIR ?= /tmp/peasoup-lineage-smoke

.PHONY: lint test bench perf-gate peaks-sweep-smoke trace-smoke serve-smoke fleet-smoke batch-smoke health-smoke pipeline-smoke loadgen-smoke jerk-smoke sensitivity-smoke chaos-smoke obs-smoke analysis-smoke coldstart-smoke lineage-smoke

# covers the whole tree incl. ops/peaks_pallas.py against the
# committed (near-empty) baseline — new kernels land lint-clean, no
# grandfathering (tests/test_lint.py pins this per-file too)
lint:
	JAX_PLATFORMS=cpu $(PY) -m peasoup_tpu.analysis

test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow'

bench:
	$(PY) bench.py

# noise-aware perf regression gate over benchmarks/history.jsonl (+ the
# legacy BENCH_r0*.json artifacts): fails when the newest record's gate
# metric exceeds the trailing-window median by the threshold factor.
# Besides wall-clock (e2e_s) the gate also checks the per-stage device
# -time columns (peaks_device_s, search_device_s — ISSUE 6): a sort
# -wall regression must trip even when host jitter hides it from
# wall-clock.  `python bench.py --gate` is the run-then-gate spelling
# for hardware CI.
perf-gate:
	JAX_PLATFORMS=cpu $(PY) -m peasoup_tpu.tools.perf_report --gate

# peak-extraction shape-stability sweep, one subprocess per
# (C, stop, cap) cell so a backend crash is recorded as an unsafe cell
# instead of killing the sweep (full grid writes
# benchmarks/peaks_sweep.json; the smoke runs one safe cell)
peaks-sweep-smoke:
	JAX_PLATFORMS=cpu $(PY) benchmarks/peaks_sweep.py --quick \
	    --out /tmp/peasoup-peaks-sweep.json --iters 4

# span-tracing smoke test: a tutorial run must write a parseable
# Chrome trace whose span names cover the five pipeline stages
trace-smoke:
	rm -rf $(SMOKE_DIR)
	JAX_PLATFORMS=cpu $(PY) -m peasoup_tpu.cli -i $(TUTORIAL) \
	    -o $(SMOKE_DIR) --dm_start 0 --dm_end 60 --acc_start -5 \
	    --acc_end 5 --acc_pulse_width 64000 --npdmp 2 --limit 50 \
	    --single_device --trace_json $(SMOKE_DIR)/trace.json
	JAX_PLATFORMS=cpu $(PY) -m peasoup_tpu.tools.trace_report \
	    $(SMOKE_DIR)/trace.json \
	    --require Dedisperse DM-Loop Accel-Search Distill Folding

# survey-scheduler smoke test: spool 3 synthetic observations (one
# truncated), drain a worker, assert 2 done + 1 quarantined + store
# candidates + a serve throughput record in benchmarks/history.jsonl,
# then crash a job mid-search and assert the retry resumes from its
# per-job checkpoint instead of recomputing
serve-smoke:
	JAX_PLATFORMS=cpu $(PY) -m peasoup_tpu.tools.serve_smoke \
	    --dir $(SERVE_SMOKE_DIR)

# fleet control-plane smoke test: two real fleet-worker processes (fake
# membership) drain one spool — 2 done + 1 quarantined with zero
# double-claims and per-host store shards — then a worker is SIGKILLed
# mid-job and `requeue --expired` recovers its lease-expired job with
# the attempt history intact; merged-shard coincidence must equal a
# single store and `status --fleet` must aggregate every host
fleet-smoke:
	JAX_PLATFORMS=cpu $(PY) -m peasoup_tpu.tools.fleet_smoke \
	    --dir $(FLEET_SMOKE_DIR)

# batched-dispatch smoke test: drain 4 same-geometry + 1 odd-geometry
# observations with `worker --batch 4` and assert ONE batched dispatch
# (+1 singleton for the odd bucket), all 5 done, fewer fused dispatches
# than a sequential drain, per-beam store records bit-identical to the
# batch=1 reference, and a ledger record with batch_fill >= 2
batch-smoke:
	JAX_PLATFORMS=cpu $(PY) -m peasoup_tpu.tools.batch_smoke \
	    --dir $(BATCH_SMOKE_DIR)

# telemetry/health-plane smoke test: two real fleet-worker processes
# drain with fast telemetry — both hosts must leave ts- shards whose
# samples carry queue depths, `health` must exit 0, and the sampler's
# self-measured overhead must stay <1% of the drain wall-clock; then a
# worker is SIGKILLed mid-job and `health` must exit nonzero with a
# crit stale_host finding until `requeue --expired` + a re-drain bring
# the fleet back to ok
health-smoke:
	JAX_PLATFORMS=cpu $(PY) -m peasoup_tpu.tools.health_smoke \
	    --dir $(HEALTH_SMOKE_DIR)

# dispatch-pipeline smoke test: drain 4 chunked-driver observations at
# pipeline_depth=1 then depth=2 and assert both drains measure a sane
# device_duty_cycle gauge, record chunk.pipeline_depth, write a serve
# ledger record carrying the duty gauge, and produce BIT-IDENTICAL
# per-source candidates (the pipeline is pure scheduling)
pipeline-smoke:
	JAX_PLATFORMS=cpu $(PY) -m peasoup_tpu.tools.pipeline_smoke \
	    --dir $(PIPELINE_SMOKE_DIR)

# load-observatory smoke test: an open-loop two-rate saturation sweep
# (15 jobs/point incl. one poison job) against two real fleet-worker
# processes — saturation_report.json must carry >=2 rate points with
# phase-decomposed p50/p95/p99 sojourn, the poison job must be
# quarantined WITHOUT entering the percentile pool, a kind:"loadgen"
# ledger record must carry the detected knee, the `timeline <job_id>`
# verb must render a waterfall whose phase sum equals the sojourn, and
# the timeline plane's own cost must stay <1% of drain wall-clock
loadgen-smoke:
	JAX_PLATFORMS=cpu $(PY) -m peasoup_tpu.tools.loadgen --smoke \
	    --dir $(LOADGEN_SMOKE_DIR)

# jerk-search smoke test (ISSUE 13): zero-jerk runs must be
# bit-identical to the accel-only default; a {-j, 0, +j} jerk grid
# must recover a synthetic jerk-smeared pulse the accel-only grid
# misses; forced u8/bf16 trial lattices must keep the recovery and
# write a parity-gated lattice sidecar that `auto` resolution honors
# (and refuses when a verdict fails); a kind:"jerk_smoke" ledger
# record must round-trip
jerk-smoke:
	JAX_PLATFORMS=cpu $(PY) -m peasoup_tpu.tools.jerk_smoke \
	    --dir $(JERK_SMOKE_DIR)

# sensitivity-observatory smoke test (ISSUE 14): a 3-point injected-SNR
# sweep must recover the bright injections, miss the faintest, attach
# a monotone per-stage SNR budget to every cell and append exactly one
# kind:"sensitivity" ledger record; a recovered canary drain must pass
# `health` while a missed canary must trip canary_recovery to crit
# (nonzero exit) until a clean re-drain clears it; canary candidates
# must stay out of science store reads
sensitivity-smoke:
	JAX_PLATFORMS=cpu $(PY) -m peasoup_tpu.tools.sensitivity --smoke \
	    --dir $(SENSITIVITY_SMOKE_DIR)

# chaos smoke test (ISSUE 15): seeded fault plan (worker SIGKILL
# mid-job, one poison input, one over-quota tenant) against a live
# supervised fleet under two-rate traffic — the supervisor must
# detect/reap/respawn, health must return to exit 0 inside the
# budget with zero jobs lost or double-run, the flooding tenant must
# be deferred with a typed AdmissionError while the fair-share tenant
# completes its whole quota, and a control phase with NO supervisor
# must leave the same fault at health exit 1
chaos-smoke:
	JAX_PLATFORMS=cpu $(PY) -m peasoup_tpu.tools.chaos --smoke \
	    --dir $(CHAOS_SMOKE_DIR)

# flight-recorder smoke test (ISSUE 16): the obs verb family against
# the checked-in fixtures — `obs diff` must regenerate the trace
# summary from the two fixture run reports, `obs baseline` must pass
# (exit 0) over the clean fixture ledger, and ingest/query/top must
# round-trip every fixture stream through a scratch warehouse
obs-smoke:
	rm -rf $(OBS_SMOKE_DIR)
	mkdir -p $(OBS_SMOKE_DIR)
	JAX_PLATFORMS=cpu $(PY) -m peasoup_tpu.cli obs diff \
	    benchmarks/fixtures/run_report_host_loop.json \
	    benchmarks/fixtures/run_report_fused.json \
	    --out $(OBS_SMOKE_DIR)/trace_summary.md
	cmp $(OBS_SMOKE_DIR)/trace_summary.md \
	    benchmarks/fixtures/trace_summary_fixture.md
	JAX_PLATFORMS=cpu $(PY) -m peasoup_tpu.cli obs baseline \
	    --ledger benchmarks/fixtures/history_fixture.jsonl
	JAX_PLATFORMS=cpu $(PY) -m peasoup_tpu.cli obs ingest \
	    --dir $(OBS_SMOKE_DIR)/warehouse \
	    --report benchmarks/fixtures/run_report_host_loop.json \
	    --report benchmarks/fixtures/run_report_fused.json \
	    --ledger benchmarks/fixtures/history_fixture.jsonl
	JAX_PLATFORMS=cpu $(PY) -m peasoup_tpu.cli obs top \
	    --dir $(OBS_SMOKE_DIR)/warehouse -n 5 --metric span.device_s
	JAX_PLATFORMS=cpu $(PY) -m peasoup_tpu.cli obs query \
	    --dir $(OBS_SMOKE_DIR)/warehouse --stage peaks --limit 10

# cold-start observatory smoke test (ISSUE 18): a cold worker drain
# must measure cold_to_first_candidate_s and decompose it into
# read/trace/compile/execute phases that partition the total, the
# spool compile ledger must attribute every backend compile to a
# program + geometry fingerprint, and a warm drain of the same
# geometry in the same process must ledger ZERO new compiles
coldstart-smoke:
	JAX_PLATFORMS=cpu $(PY) -m peasoup_tpu.tools.coldstart_smoke \
	    --dir $(COLDSTART_SMOKE_DIR)

# concurrency & contracts prover smoke test (ISSUE 17): writes a
# deliberately broken fixture tree and asserts each of PSL010-PSL013
# fires on it (nonzero exit naming the rule), `--rules` subsetting
# works, and the real tree stays clean under the same four rules — a
# detector that cannot detect is worse than none
analysis-smoke:
	JAX_PLATFORMS=cpu $(PY) -m peasoup_tpu.tools.analysis_smoke \
	    --dir $(ANALYSIS_SMOKE_DIR)

# candidate-provenance smoke test (ISSUE 19): a real drain must leave
# a lineage ledger whose funnel conserves EXACTLY
# (decoded == absorbed + cut + emitted), the `why` verb must
# reconstruct a stored candidate's full decision chain from only its
# store record, distilled candidates must be bit-identical with
# lineage on vs --no-lineage, provenance's cost (its Lineage spans)
# must stay <1% of drain wall-clock, and a deliberately widened
# harmonic tolerance must trip the distill_collapse health rule
lineage-smoke:
	JAX_PLATFORMS=cpu $(PY) -m peasoup_tpu.tools.lineage_smoke \
	    --dir $(LINEAGE_SMOKE_DIR)
