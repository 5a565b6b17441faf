"""The metrics catalog: every counter/gauge name, in one place.

The warehouse schema, the health rules and any dashboard built on the
telemetry shards all join on metric *names* — a name emitted in code
but absent here is a dangling wire nobody will ever query.  Lint rule
PSL009 therefore requires every literal ``METRICS.inc("...")`` /
``METRICS.gauge("...")`` name in the tree to appear in
:data:`CATALOG` (or match a documented dynamic prefix in
:data:`DYNAMIC_PREFIXES`), so adding a metric *forces* cataloguing
it.

This module is pure data — import it from anywhere, it imports
nothing from the package.
"""

from __future__ import annotations

#: every literal metric name in the tree -> one-line description.
#: Counters unless marked (gauge).
CATALOG: dict[str, str] = {
    # -- canary / checkpoint ------------------------------------------------
    "canary.missed": "injected canary pulsar NOT recovered this run",
    "canary.recovered": "injected canary pulsar recovered this run",
    "checkpoint.resumes": "searches resumed from a checkpoint",
    "checkpoint.rows_resumed": "DM rows skipped thanks to a resume",
    # -- chunk planner (gauges) --------------------------------------------
    "chunk.accel_block": "(gauge) planned acceleration-block size",
    "chunk.compact_k": "(gauge) planned peak-compaction capacity K",
    "chunk.dm_chunk": "(gauge) planned DM-chunk height",
    "chunk.peak_capacity": "(gauge) planned per-trial peak capacity",
    "chunk.pipeline_depth": "(gauge) planned upload pipeline depth",
    # -- cold start / compile cache -----------------------------------------
    "coldstart.cold_to_first_candidate_s": "(gauge) wall seconds "
                                           "from drain start to the "
                                           "first completed job",
    "compile_cache.enabled": "persistent XLA compile-cache "
                             "engagements this process",
    # -- device -------------------------------------------------------------
    "device_duty_cycle": "(gauge) device seconds per wall second "
                         "over the last drain window",
    # -- events plane -------------------------------------------------------
    "events.flood_suppressed": "event-log lines dropped by flood "
                               "control",
    # -- fold ---------------------------------------------------------------
    "fold.cache_evicted": "fold plan-cache evictions",
    # -- HBM accounting (gauges) -------------------------------------------
    "hbm.budget_bytes": "(gauge) planner's HBM budget",
    "hbm.data_bytes": "(gauge) staged observation bytes on device",
    "hbm.est_full_bytes": "(gauge) planner's full-problem estimate",
    "hbm.high_water_bytes": "(gauge) max bytes_in_use seen at any "
                            "span close",
    "hbm.probed_fold_samp_bytes": "(gauge) measured fold bytes per "
                                  "sample (memory_analysis probe)",
    "hbm.probed_row_bytes": "(gauge) measured trial bytes per DM row "
                            "(memory_analysis probe)",
    "hbm.probed_spectrum_bytes": "(gauge) measured bytes per live "
                                 "accel spectrum element "
                                 "(memory_analysis probe)",
    # -- injection / parity (gauges) ---------------------------------------
    "injection.recovered": "(gauge) 1.0 when the parity injection "
                           "was recovered",
    "injection.snr_interbin": "(gauge) parity injection interbin SNR",
    "injection.snr_peak": "(gauge) parity injection peak SNR",
    "injection.snr_whiten": "(gauge) parity injection whitened SNR",
    # -- jit ----------------------------------------------------------------
    "jit.backend_compiles": "XLA backend_compile events this process",
    "jit.compiles_attributed": "backend compiles attributed to a "
                               "(program, geometry) key in the "
                               "compile ledger",
    "jit.recompiles_seen_geometry": "backend compiles on an "
                                    "already-seen (program, "
                                    "geometry, device) key",
    # -- lineage (candidate provenance) -------------------------------------
    "lineage.mark_errors": "lineage decision marks that failed to "
                           "write",
    "lineage.marks": "candidate selection-decision marks written to "
                     "lineage.jsonl",
    "lineage.writes": "file writes of lineage.jsonl: one per Lineage "
                      "block with marks, one per mark outside a block",
    # -- peaks / runs -------------------------------------------------------
    "peaks.compact_pallas": "pallas threshold-compaction dispatches",
    "runs.fused_fold_dispatches": "batched fold program dispatches",
    "runs.host_loop": "searches run on the host-loop path",
    "runs.mesh_chunked": "searches run on the chunked mesh path",
    "runs.mesh_fused": "searches run on the fused mesh path",
    "runs.mesh_fused_batched": "searches run on the batched fused "
                               "path",
    # -- profiler -----------------------------------------------------------
    "profile.captures": "sampled jax.profiler captures written",
    # -- scheduler ----------------------------------------------------------
    "scheduler.admission_deferred": "submits deferred by a token "
                                    "bucket",
    "scheduler.admission_rejected": "submits rejected by admission "
                                    "control",
    "scheduler.batch_fill": "jobs packed into batched dispatches",
    "scheduler.batched_dispatches": "multi-observation batched "
                                    "dispatches",
    "scheduler.claimed": "jobs claimed from pending/",
    "scheduler.exhausted": "jobs failed past max attempts",
    "scheduler.geometry_trimmed": "batch claims trimmed on geometry "
                                  "mismatch",
    "scheduler.heartbeats": "lease heartbeats written",
    "scheduler.jobs_per_hour": "(gauge) live drain throughput",
    "scheduler.lease_reaped": "expired leases reaped back to "
                              "pending/",
    "scheduler.plan_reuse": "search-plan cache hits across jobs",
    "scheduler.prefetch_hits": "claims served from the prefetcher",
    "scheduler.prefetch_misses": "claims that missed the prefetcher",
    "scheduler.quarantined": "jobs quarantined on poison input",
    "scheduler.requeued": "jobs requeued for another attempt",
    "scheduler.retried": "job attempts after the first",
    "scheduler.staged_raw_hits": "device-staged uploads reused on "
                                 "claim",
    "scheduler.staged_raw_uploads": "raw observations staged to "
                                    "device ahead of claim",
    "scheduler.submitted": "jobs accepted into pending/",
    "scheduler.succeeded": "jobs completed into done/",
    "scheduler.timeout_abandoned": "jobs abandoned on wall-clock "
                                   "timeout",
    # -- search geometry (gauges) ------------------------------------------
    "search.batch": "(gauge) observations per batched dispatch",
    "search.fft_size": "(gauge) padded FFT size of the run",
    "search.n_devices": "(gauge) devices the run sharded over",
    "search.n_dm_trials": "(gauge) DM trials of the run",
    # -- survey store (ISSUE 20) --------------------------------------------
    "store.compactions": "shard tails folded into sealed segments",
    "store.compacted_records": "records sealed into segments",
    "store.query_requests": "query-service requests answered",
    # -- supervisor ---------------------------------------------------------
    "supervisor.actions": "supervisor actions executed",
    "supervisor.throttled": "supervisor actions skipped by the "
                            "rate budget",
    # -- timeline / trace ---------------------------------------------------
    "timeline.mark_errors": "timeline marks that failed to write",
    "timeline.marks": "timeline marks written",
    "timeline.marks_dropped": "timeline marks dropped by flood "
                              "control",
    "trace.listener_errors": "span listeners dropped after raising",
    "trace.spans_dropped": "spans dropped past the retention cap",
    # -- upload (chunked path) ----------------------------------------------
    "upload.link_bytes": "bytes the chunked path's static upload handed "
                         "to the host link, summed over devices",
    "upload.parts_gathered": "flat filterbank parts sent as shards and "
                             "all-gathered on the devices",
}

#: metric families whose names are built dynamically (f-strings) —
#: PSL009 cannot check these literally, so the *prefix* is the
#: catalogued contract
DYNAMIC_PREFIXES: tuple = (
    "events.",                    # events.<kind> per warn_event kind
    "peaks.method_",              # peaks.method_<sort|two_stage|...>
    "dedisp.method_",             # dedisp.method_<pallas|scan>
    "scheduler.prefetch_miss.",   # scheduler.prefetch_miss.<class>
    "supervisor.action.",         # supervisor.action.<action name>
)


def is_cataloged(name: str) -> bool:
    """True when ``name`` is in the catalog or matches a documented
    dynamic prefix (what lint rule PSL009 enforces)."""
    return name in CATALOG or any(
        name.startswith(p) for p in DYNAMIC_PREFIXES)
