"""Long-running survey worker: claim -> search -> ingest, repeated.

The driver that turns the single-shot pipeline into a service.  Per
job it

1. claims the best pending job (priority, then FIFO) from the spool;
2. reads the observation — from the prefetch slot when the previous
   iteration already fetched it (see below) — and builds the existing
   :class:`~peasoup_tpu.search.pipeline.PulsarSearch` /
   :class:`~peasoup_tpu.parallel.mesh.MeshPulsarSearch` on it;
3. kicks a background read+unpack of the NEXT pending observation, so
   host I/O overlaps the current job's device search — the
   ``utils/hostfetch``-style double buffering of the chunked driver,
   lifted to observation granularity;
4. runs the search under a ``Job-<id>`` root span, writes the usual
   per-run artefacts (overview.xml, run_report.json) into the job's
   work directory, and ingests the distilled candidates into the
   cross-run store;
5. on failure, classifies (serve/retry.py): quarantine straight to
   ``failed/``, transient back to ``pending/`` after backoff, with
   the captured run report + traceback on the job record either way.

Program reuse across jobs: jitted programs are keyed by array shapes,
so the worker buckets each observation's geometry to the plan shapes
— observations whose sample counts share a power-of-two FFT size are
LOSSLESSLY trimmed to ``size + max_delay + 1`` samples (the search
reads nothing beyond that: trials use the first ``size`` columns and
the fold's power-of-two length is preserved by the ``+ 1``), so every
job in the bucket replays the already-compiled programs instead of
paying a per-observation XLA compile.

Per-job checkpointing: each job gets a checkpoint file in its work
directory, so a worker killed mid-job resumes that job's completed DM
rows on the next claim instead of recomputing from scratch.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
import traceback
from dataclasses import replace

from ..errors import ConfigError
from ..obs import timeline
from ..obs.events import warn_event
from ..obs.metrics import REGISTRY as METRICS
from ..obs.trace import device_seconds, span, span_cursor
from .queue import JobRecord, JobSpool
from .retry import (
    QUARANTINE,
    BackoffPolicy,
    abandoned_count,
    classify_failure,
    pause,
    run_with_timeout,
)
from .store import CandidateStore

#: per-job ``events.jsonl`` byte budget (ISSUE 16): a retry-looping
#: or event-heavy job rotates its log to ``events.jsonl.1`` instead
#: of growing without bound — the fleet's disk footprint stays
#: proportional to job count, not event volume
EVENT_LOG_MAX_BYTES = 512 * 1024


class ObservationPrefetcher:
    """Multi-slot background filterbank reader (double buffering at
    observation granularity; ``slots`` of them for batched dispatch).

    ``start(path)`` spawns a daemon thread reading + unpacking the
    file while the caller's search occupies the devices; ``take(path)``
    joins and hands the :class:`Filterbank` over — or returns None on
    a slot miss (a different job won the claim) or a read error (the
    claimer's own synchronous read then raises the real, classifiable
    exception in job context).  With ``slots > 1`` the batched worker
    fills the NEXT batch's observations while the current batch is on
    device; when full, the oldest slot is evicted (its read result is
    simply dropped — prefetch is only ever a hint).

    ``device_stage`` (ISSUE 11) extends the prefetch one stage toward
    the device: after a successful read the same thread calls
    ``device_stage(fil, job)`` — the worker's pack + ``device_put`` of
    the raw bytes — so the h2d upload ALSO overlaps the previous job's
    search.  The staged value of the most recent successful ``take``
    is parked on ``self.last_staged`` (None on misses or when staging
    failed; staging failures never fail the prefetch — the read result
    alone is still a hit).
    """

    def __init__(self, slots: int = 1, device_stage=None):
        self.slots = max(1, int(slots))
        self.device_stage = device_stage
        self.last_staged = None
        # path -> {"thread", "result", "error", "staged", "job"};
        # insertion-ordered so eviction drops the oldest prefetch first
        self._inflight: dict[str, dict] = {}

    def start(self, path: str, job=None) -> None:
        if path in self._inflight:
            return  # already in flight (or landed) for this path
        while len(self._inflight) >= self.slots:
            oldest = next(iter(self._inflight))
            slot = self._inflight.pop(oldest)
            if slot["thread"].is_alive():
                slot["thread"].join()  # reads are short next to a search
        slot = {"thread": None, "result": None, "error": None,
                "staged": None, "job": job}

        def _read():
            from ..io.sigproc import read_filterbank

            try:
                slot["result"] = read_filterbank(path)
            except BaseException as exc:
                slot["error"] = exc
                return
            if self.device_stage is not None and slot["job"] is not None:
                try:
                    slot["staged"] = self.device_stage(
                        slot["result"], slot["job"])
                except BaseException:
                    pass  # a hint, never a failure: upload on claim

        slot["thread"] = threading.Thread(
            target=_read, daemon=True, name="serve-prefetch")
        self._inflight[path] = slot
        slot["thread"].start()

    def take(self, path: str):
        self.last_staged = None
        slot = self._inflight.pop(path, None)
        if slot is None:
            # plain slot miss (a different job won the claim): routine
            # at the drain tail, so a counter is enough
            METRICS.inc("scheduler.prefetch_misses")
            return None
        slot["thread"].join()
        if slot["error"] is not None or slot["result"] is None:
            # classified miss (ISSUE 11 satellite): the claimer's
            # synchronous re-read will raise the real exception in job
            # context, but the EVENT log should already say what the
            # background read hit and how retry.py would class it
            err = slot["error"]
            kind = classify_failure(err) if err is not None else "unknown"
            METRICS.inc("scheduler.prefetch_misses")
            METRICS.inc(f"scheduler.prefetch_miss.{kind}")
            warn_event(
                "prefetch_miss",
                f"background prefetch of {path} failed "
                f"({type(err).__name__ if err is not None else 'no result'}"
                f"; classified {kind}); falling back to a synchronous "
                f"read",
                path=path, classification=kind,
                error=(f"{type(err).__name__}: {err}"
                       if err is not None else ""),
            )
            return None
        METRICS.inc("scheduler.prefetch_hits")
        self.last_staged = slot.get("staged")
        return slot["result"]


class SurveyWorker:
    """Claims and runs spool jobs until the queue drains (or a job
    budget is reached).

    ``run_job_fn`` is injectable for tests: it replaces the real
    search (:meth:`_run_job`) but keeps the whole claim / classify /
    retry / quarantine machinery live.  ``sleeper`` routes backoff
    waits (serve/retry.py) to a fake in tests.
    """

    def __init__(self, spool: JobSpool, store: CandidateStore | None = None,
                 *, base_config=None, backoff: BackoffPolicy | None = None,
                 timeout_s: float = 0.0, single_device: bool = False,
                 max_devices: int | None = None, worker_id: str = "",
                 prefetch: bool = True, run_job_fn=None,
                 history_path: str | None = None, sleeper=None,
                 batch: int = 1, telemetry_interval_s: float = 5.0,
                 profile_every: int = 0, profile_dir: str | None = None,
                 lineage: bool = True):
        self.spool = spool
        self.store = store if store is not None else CandidateStore(
            os.path.join(spool.root, "candidates.jsonl"))
        self.base_config = base_config
        self.backoff = backoff if backoff is not None else BackoffPolicy()
        self.timeout_s = float(timeout_s)
        self.single_device = single_device
        self.max_devices = max_devices
        self.worker_id = worker_id or f"worker-{os.getpid()}"
        #: fleet host label stamped on claims ("" single-host; set by
        #: serve/fleet.py FleetWorker)
        self.host_label = ""
        self.prefetch = prefetch
        self.run_job_fn = run_job_fn
        self.history_path = history_path
        self.sleeper = sleeper
        #: batched dispatch (ISSUE 9): stack up to ``batch``
        #: same-geometry pending jobs into ONE fused device program per
        #: round trip; 1 = historical per-job dispatch
        self.batch = max(1, int(batch))
        #: live telemetry cadence (obs/telemetry.py); 0 disables the
        #: sampler.  The shard lands in the spool's ``fleet/`` dir so
        #: ``health`` / ``status --watch`` see single-host workers too
        self.telemetry_interval_s = float(telemetry_interval_s)
        #: sampled device profiling (ISSUE 18): capture a jax.profiler
        #: trace for every Nth job (0 disables).  Tolerant no-op where
        #: the profiler is unavailable; each capture lands under
        #: ``profile_dir`` and is registered in the compile ledger
        #: (kind ``profile``) so the warehouse knows the artifact path
        self.profile_every = max(0, int(profile_every))
        self.profile_dir = profile_dir or os.path.join(
            spool.root, "profiles")
        #: candidate provenance (ISSUE 19): record every selection
        #: decision into the spool's ``lineage.jsonl``; False is the
        #: ``--no-lineage`` escape hatch (candidate output is
        #: bit-identical either way)
        self.lineage = bool(lineage)
        self._jobs_started = 0
        #: observation-granularity pipeline depth (ISSUE 11): how many
        #: jobs ahead the prefetcher reads (and device-stages).  Jobs
        #: are still CLAIMED one at a time — lookahead uses peeks, so a
        #: crashed worker never holds leases on unstarted jobs
        self.pipeline_depth = max(1, int(getattr(
            base_config, "pipeline_depth", 2) or 1))
        self._prefetcher = ObservationPrefetcher(
            slots=max(self.batch, self.pipeline_depth),
            device_stage=(None if single_device
                          else self._stage_observation),
        )
        #: geometry bucket -> jobs served (program-reuse accounting)
        self.geometries: dict[tuple, int] = {}
        #: per-drain latency samples for the serve ledger record:
        #: submit->done sojourns (timeline-derived) and submit->claim
        #: waits of every job this worker finished
        self._sojourns: list[float] = []
        self._queue_waits: list[float] = []
        #: run ids (job ids) finished this drain — the funnel scope of
        #: the drain summary's lineage block
        self._drained_runs: list[str] = []

    # -- config / geometry -------------------------------------------------

    def _job_config(self, job: JobRecord):
        """Base config + the job's overrides + per-job spool paths."""
        from ..search.plan import SearchConfig

        cfg = (replace(self.base_config) if self.base_config is not None
               else SearchConfig())
        for key, val in (job.overrides or {}).items():
            if not hasattr(cfg, key):
                raise ConfigError(
                    f"job {job.job_id}: unknown SearchConfig override "
                    f"{key!r}")
            setattr(cfg, key, val)
        cfg.infilename = job.input
        work = self.spool.work_dir(job.job_id)
        cfg.outdir = os.path.join(work, "out")
        # lineage run id (ISSUE 19): every decision mark this job's
        # search emits is attributed to the job, so per-job funnels
        # and the `why` verb scope to one observation exactly
        cfg.lineage_run = job.job_id
        if not cfg.checkpoint_file:
            # crash-resume: a re-claimed job resumes its completed DM
            # rows instead of recomputing (search/checkpoint.py keys
            # on header content, so the spool can even be relocated)
            cfg.checkpoint_file = os.path.join(work, "search.ckpt")
        return cfg

    def _build_search(self, fil, cfg):
        """Construct the search, bucketing geometry for program reuse
        (lossless trim — see module docstring)."""
        if self.single_device:
            from ..search.pipeline import PulsarSearch

            make = lambda f: PulsarSearch(f, cfg)
        else:
            from ..parallel.mesh import MeshPulsarSearch

            make = lambda f: MeshPulsarSearch(
                f, cfg, max_devices=self.max_devices)
        search = make(fil)
        keep = search.size + search.max_delay + 1
        if fil.nsamps > keep:
            from ..io.sigproc import Filterbank

            cfg.size = search.size  # pin: the trim must not shrink it
            hdr = replace(fil.header, nsamples=keep)
            fil = Filterbank(header=hdr, data=fil.data[:keep])
            search = make(fil)
            METRICS.inc("scheduler.geometry_trimmed")
        gkey = (fil.nchans, fil.header.nbits, search.size,
                int(search.out_nsamps), len(search.dm_list))
        if gkey in self.geometries:
            METRICS.inc("scheduler.plan_reuse")
        self.geometries[gkey] = self.geometries.get(gkey, 0) + 1
        # compile attribution (ISSUE 18): every backend compile fired
        # while this search runs is ledgered against the reuse-bucket
        # geometry — a cold bucket shows its compiles, a warm one shows
        # recompiles (the compile_storm health rule watches the latter)
        from ..obs.compilation import set_compile_context

        set_compile_context(
            program="serve.search",
            geometry={"nchans": gkey[0], "nbits": gkey[1],
                      "size": gkey[2], "out_nsamps": gkey[3],
                      "n_dm": gkey[4]})
        return fil, search

    def _stage_observation(self, fil, job: JobRecord):
        """Prefetch device stage (ISSUE 11): pack + upload the raw
        filterbank bytes from the prefetch thread, so the h2d transfer
        overlaps the PREVIOUS job's device time instead of sitting on
        the claim's critical path.  Runs the same lossless trim as
        ``_build_search`` so the staged vector matches the geometry
        the search will ask for (``_staged_raw_device`` re-validates
        shape/dtype before trusting it).  Single-process only: the
        multi-host ``put_global`` assembly is not thread-safe against
        a concurrently dispatching main thread."""
        import jax

        if self.single_device or jax.process_count() != 1:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..io.sigproc import Filterbank
        from ..parallel.mesh import MeshPulsarSearch
        from ..utils.hostfetch import put_global

        cfg = self._job_config(job)
        search = MeshPulsarSearch(fil, cfg, max_devices=self.max_devices)
        keep = search.size + search.max_delay + 1
        if fil.nsamps > keep:
            hdr = replace(fil.header, nsamples=keep)
            fil = Filterbank(header=hdr, data=fil.data[:keep])
        raw = search._pack_raw(fil)
        staged = put_global(raw, NamedSharding(search.mesh, P()))
        METRICS.inc("scheduler.staged_raw_uploads")
        return staged

    # -- batched dispatch (ISSUE 9) ----------------------------------------

    def _batch_key(self, job: JobRecord):
        """Geometry fingerprint computable from the HEADER alone.

        Two jobs may share one batched dispatch iff they resolve to
        the identical plan: same overrides and same (nchans, nbits,
        tsamp, fch1, foff) — which fix the delay table and accel grid
        — plus the same fft ``size`` and the same effective (post
        lossless-trim) sample count.  Deliberately STRICTER than
        ``_build_search``'s reuse bucket, which omits the frequency
        axis.  Only the SIGPROC header is read (cheap), never the
        data.  None = don't batch this job (unreadable header, odd
        config); it then runs through the normal solo path.
        """
        try:
            cfg = self._job_config(job)
            from ..io.sigproc import read_sigproc_header

            with open(job.input, "rb") as f:
                hdr = read_sigproc_header(f)
            from ..ops import delay_table, generate_dm_list, max_delay
            from ..search.plan import prev_power_of_two

            if cfg.dm_list is not None:
                import numpy as np

                dm_list = np.asarray(cfg.dm_list, dtype=np.float32)
            elif cfg.dm_file:
                from ..search.pipeline import load_dm_file

                dm_list = load_dm_file(cfg.dm_file)
            else:
                dm_list = generate_dm_list(
                    cfg.dm_start, cfg.dm_end, hdr.tsamp,
                    cfg.dm_pulse_width, hdr.fch1, hdr.foff, hdr.nchans,
                    cfg.dm_tol,
                )
            md = max_delay(dm_list, delay_table(
                hdr.nchans, hdr.tsamp, hdr.fch1, hdr.foff))
            size = cfg.size or prev_power_of_two(hdr.nsamples)
            eff = min(int(hdr.nsamples), int(size) + int(md) + 1)
            ovr = tuple(sorted(
                (k, repr(v)) for k, v in (job.overrides or {}).items()))
            return (ovr, int(hdr.nchans), int(hdr.nbits),
                    float(hdr.tsamp), float(hdr.fch1), float(hdr.foff),
                    int(size), eff,
                    # jerk axis + trial lattice change the padded grid
                    # and the traced program — never batch across them
                    float(cfg.jerk_start), float(cfg.jerk_end),
                    float(cfg.jerk_step), str(cfg.trial_lattice))
        except Exception:
            return None

    def _claim_batch_mates(self, leader: JobRecord,
                           room: int) -> list[JobRecord]:
        """Claim up to ``room`` pending jobs sharing the leader's
        batch key (bucket-fill: mates jump the priority queue — a full
        batch beats strict queue order because the marginal cost of a
        same-bucket beam is near zero)."""
        key = self._batch_key(leader)
        if key is None:
            return []
        mates: list[JobRecord] = []
        for rec in self.spool.pending_jobs():
            if len(mates) >= room:
                break
            if self._batch_key(rec) != key:
                continue
            got = self.spool.claim_job(
                rec.job_id, self.worker_id, host=self.host_label)
            if got is not None:  # lost races just shrink the batch
                self._mark_job(got, "batch-claim",
                               leader=leader.job_id)
                mates.append(got)
        return mates

    # -- lifecycle timeline (obs/timeline.py) ------------------------------

    def _mark_job(self, job: JobRecord, phase: str, **attrs) -> None:
        """One worker-side mark in the job's lifecycle timeline."""
        timeline.mark(self.spool.work_dir(job.job_id), phase,
                      host=self.host_label, attempt=job.attempts,
                      **attrs)

    def _recorder(self, jobs) -> timeline.TimelineRecorder:
        """Span-close listener mapping this worker's pipeline spans
        (read/dedisperse/dispatch/fetch/.../store-ingest, plus
        interpolated compile marks) into the given jobs' timelines;
        batched dispatch passes every batch-mate so the shared device
        phases land in each beam's waterfall."""
        recs = jobs if isinstance(jobs, list) else [jobs]
        return timeline.TimelineRecorder(
            [self.spool.work_dir(j.job_id) for j in recs],
            host=self.host_label,
            attempt=max((j.attempts for j in recs), default=0),
        )

    def _note_done(self, job: JobRecord) -> None:
        """Latency accounting for a finished job: the submit->done
        sojourn from its timeline marks (clock-step-proof), falling
        back to wall stamps for pre-timeline records, into the
        ``scheduler.sojourn`` timer + this drain's percentile pools."""
        soj = timeline.sojourn_for(self.spool.work_dir(job.job_id))
        if soj is None:
            soj = max(0.0, job.finished_utc - job.submitted_utc)
        METRICS.observe("scheduler.sojourn", soj)
        self._sojourns.append(float(soj))
        self._queue_waits.append(float(job.queue_wait_s or 0.0))
        self._drained_runs.append(str(job.job_id))

    def _mark_store(self, job: JobRecord, result) -> None:
        """Lineage annotations for the store ingest (ISSUE 19):
        science candidates are ``stored``; a canary job's candidates
        are ``quarantined`` — tagged out of every science read — so
        the funnel shows known-answer probes leaving the population."""
        from ..obs import lineage

        if not lineage.enabled() or not result.candidates:
            return
        run = str(job.job_id)
        with lineage.marking():
            lineage.mark(
                "quarantined" if job.canary else "stored", run=run,
                ids=[lineage.candidate_uid(run, c)
                     for c in result.candidates],
                n=len(result.candidates))

    def _run_batch_jobs(self, jobs: list[JobRecord]) -> int:
        """Run claimed same-bucket jobs through ONE batched dispatch;
        returns the success count.  Failures stay per-job: a beam that
        fails to read, search or ingest goes through the usual
        classify/retry/quarantine path without touching its
        batch-mates (their checkpoints are per-job files)."""
        from ..cli import write_search_output
        from ..io.sigproc import read_filterbank
        from ..obs.events import configure_event_log

        # phase A: per-job config + observation read; a beam failing
        # HERE (e.g. truncated file -> typed InputFileError) peels off
        # through _handle_failure before the dispatch
        ready: list[tuple] = []
        for job in jobs:
            try:
                cfg = self._job_config(job)
                configure_event_log(
                    os.path.join(self.spool.work_dir(job.job_id),
                                 "events.jsonl"),
                    max_log_bytes=EVENT_LOG_MAX_BYTES)
                fil = (self._prefetcher.take(job.input)
                       if self.prefetch else None)
                if fil is not None:
                    self._mark_job(job, "prefetch-hit")
                else:
                    with self._recorder(job), \
                            span("Observation-Read", metric="obs_read",
                                 input=job.input):
                        fil = read_filterbank(job.input)
                ready.append((job, cfg, fil))
            except Exception as exc:
                self._handle_failure(job, exc)
        # phase B: build per-job searches (lossless trim + geometry
        # accounting per job); the first survivor's search leads
        js, cfgs, fils, searches = [], [], [], []
        for job, cfg, fil in ready:
            try:
                fil2, search = self._build_search(fil, cfg)
            except Exception as exc:
                self._handle_failure(job, exc)
                continue
            js.append(job)
            cfgs.append(cfg)
            fils.append(fil2)
            searches.append(search)
        if not js:
            return 0
        leader = searches[0]
        ok = 0
        if len(js) > 1:
            # defensive: the batch key should guarantee this; anything
            # incompatible is peeled back out to the solo path
            want = leader._batch_fields(fils[0])
            solo = [i for i in range(1, len(js))
                    if leader._batch_fields(fils[i]) != want]
            for i in reversed(solo):
                job_i = js.pop(i)
                cfgs.pop(i)
                fils.pop(i)
                searches.pop(i)
                if self.run_one(job_i):
                    ok += 1
        if len(js) == 1:
            return ok + (1 if self.run_one(js[0]) else 0)
        # overlap the NEXT wave's reads with this batch's device time
        if self.prefetch:
            for rec in self.spool.pending_jobs()[: self.batch]:
                self._prefetcher.start(rec.input)
        B = len(js)
        try:
            # the shared device phases (dedisperse/dispatch/fetch/...)
            # land in EVERY batch-mate's timeline
            with self._recorder(js):
                results = run_with_timeout(
                    lambda: leader.run_batch(fils, cfgs),
                    self.timeout_s,
                    label=f"batch {js[0].job_id}+{B - 1}")
        except Exception as exc:
            # whole-dispatch failure (timeout, compile error): every
            # beam classifies/retries individually
            for job in js:
                self._handle_failure(job, exc)
            return ok
        if getattr(leader, "last_dispatch_batched", False):
            METRICS.inc("scheduler.batched_dispatches")
            METRICS.inc("scheduler.batch_fill", B)
        for job, cfg, result in zip(js, cfgs, results):
            with self._recorder(job), \
                    span(f"Job-{job.job_id}", metric="job",
                         job_id=job.job_id, input=job.input,
                         attempt=job.attempts, priority=job.priority,
                         batch=B):
                if isinstance(result, BaseException):
                    self._handle_failure(job, result)
                    continue
                try:
                    write_search_output(result, cfg.outdir)
                    with span("Store-Ingest", metric="store_ingest",
                              job_id=job.job_id):
                        ingested = self.store.ingest(
                            job.job_id, job.input, result.candidates,
                            canary=bool(job.canary),
                            provenance=result.provenance)
                        self._mark_store(job, result)
                    best = max((float(c.snr)
                                for c in result.candidates), default=0.0)
                    summary = {
                        "candidates": len(result.candidates),
                        "ingested": ingested,
                        "best_snr": round(best, 4),
                        "outdir": cfg.outdir,
                        "batch": B,
                        "timers": {k: round(float(v), 3)
                                   for k, v in result.timers.items()},
                    }
                    if job.canary:
                        summary["canary"] = self._check_canary(job,
                                                               result)
                except Exception as exc:
                    self._handle_failure(job, exc)
                    continue
            self.spool.mark_done(job, summary)
            self._note_done(job)
            METRICS.inc("scheduler.succeeded")
            ok += 1
        return ok

    # -- one job -----------------------------------------------------------

    def _run_job(self, job: JobRecord) -> dict:
        from ..cli import write_search_output
        from ..io.sigproc import read_filterbank
        from ..obs.events import configure_event_log

        cfg = self._job_config(job)
        configure_event_log(
            os.path.join(self.spool.work_dir(job.job_id),
                         "events.jsonl"),
            max_log_bytes=EVENT_LOG_MAX_BYTES)
        fil = self._prefetcher.take(job.input) if self.prefetch else None
        staged = self._prefetcher.last_staged if self.prefetch else None
        if fil is None:
            with span("Observation-Read", metric="obs_read",
                      input=job.input):
                fil = read_filterbank(job.input)
        else:
            self._mark_job(job, "prefetch-hit")
        if staged is not None:
            self._mark_job(job, "stage")
        fil, search = self._build_search(fil, cfg)
        if staged is not None:
            # prefetch-thread upload (ISSUE 11): _device_inputs /
            # dedisperse_sharded consume it if the geometry matches
            search._staged_raw = staged
        # overlap the next pipeline_depth-1 observations' read+unpack
        # (and their pack+upload, via the prefetcher's device stage)
        # with this search; depth=1 is the unpipelined A/B reference
        if self.prefetch:
            for rec in self.spool.pending_jobs()[
                    : self.pipeline_depth - 1]:
                self._prefetcher.start(rec.input, job=rec)
        result = search.run()
        write_search_output(result, cfg.outdir)
        with span("Store-Ingest", metric="store_ingest",
                  job_id=job.job_id):
            ingested = self.store.ingest(
                job.job_id, job.input, result.candidates,
                canary=bool(job.canary),
                provenance=result.provenance)
            self._mark_store(job, result)
        best = max((float(c.snr) for c in result.candidates),
                   default=0.0)
        summary = {
            "candidates": len(result.candidates),
            "ingested": ingested,
            "best_snr": round(best, 4),
            "outdir": cfg.outdir,
            "timers": {k: round(float(v), 3)
                       for k, v in result.timers.items()},
        }
        if job.canary:
            summary["canary"] = self._check_canary(job, result)
        return summary

    def _check_canary(self, job: JobRecord, result) -> dict:
        """Match a completed canary job against its injection manifest
        (obs/injection.py, ISSUE 14).

        The serving stack's known-answer probe: counters + a
        ``canary_missed`` event feed the telemetry stream and the
        ``canary_recovery`` health rule, and the verdict rides the job
        summary into the ``done/`` record and the serve ledger.
        Matching failures count as misses — a canary that cannot be
        checked is a canary that did not come back.
        """
        from ..obs.injection import match_candidates

        man = job.canary
        try:
            verdict = match_candidates(man, result.candidates)
            out = {
                "recovered": bool(verdict["recovered"]),
                "best_snr": round(float(verdict["best_snr"]), 4),
                "n_matches": int(verdict["n_matches"]),
                "freq": man.get("freq"),
                "target_snr": man.get("target_snr"),
            }
        except Exception as exc:
            out = {"recovered": False, "best_snr": 0.0, "n_matches": 0,
                   "error": str(exc)}
        if out["recovered"]:
            METRICS.inc("canary.recovered")
        else:
            METRICS.inc("canary.missed")
            warn_event(
                "canary_missed",
                f"canary job {job.job_id} did not recover its "
                f"injected pulsar (freq {man.get('freq')}, target SNR "
                f"{man.get('target_snr')})",
                job_id=job.job_id, freq=man.get("freq"),
                target_snr=man.get("target_snr"),
            )
        return out

    def _capture_failure_report(self, job: JobRecord) -> str:
        """Snapshot the run's telemetry (stage timers, counters,
        events up to the crash) next to the job; best effort."""
        path = os.path.join(
            self.spool.work_dir(job.job_id),
            f"run_report.attempt{job.attempts}.json")
        try:
            from ..obs.report import write_run_report

            write_run_report(path)
        except Exception:
            return ""
        return path

    def _handle_failure(self, job: JobRecord, exc: BaseException) -> None:
        kind = classify_failure(exc)
        job.failures.append({
            "utc": round(time.time(), 3),
            "t_mono": round(time.perf_counter(), 6),
            "attempt": job.attempts,
            "classification": kind,
            "error": f"{type(exc).__name__}: {exc}",
            "traceback": traceback.format_exc(),
            "run_report": self._capture_failure_report(job),
        })
        if kind == QUARANTINE:
            warn_event(
                "job_quarantined",
                f"job {job.job_id} quarantined (attempt "
                f"{job.attempts}): {exc}",
                job_id=job.job_id, input=job.input,
                error=str(exc),
            )
            METRICS.inc("scheduler.quarantined")
            self.spool.mark_failed(job)
        elif self.backoff.exhausted(job.attempts):
            warn_event(
                "job_retries_exhausted",
                f"job {job.job_id} failed {job.attempts} attempts; "
                f"giving up: {exc}",
                job_id=job.job_id, input=job.input,
                attempts=job.attempts, error=str(exc),
            )
            METRICS.inc("scheduler.exhausted")
            self.spool.mark_failed(job)
        else:
            delay = self.backoff.delay_for(job.attempts)
            warn_event(
                "job_retry",
                f"job {job.job_id} attempt {job.attempts} failed "
                f"({type(exc).__name__}); re-queueing with "
                f"{delay:.1f}s backoff",
                job_id=job.job_id, attempt=job.attempts,
                delay_s=delay, error=str(exc),
            )
            METRICS.inc("scheduler.retried")
            self.spool.release(job)
            pause(delay, self.sleeper)

    def _maybe_profile(self, job: JobRecord):
        """Sampled device profiling (ISSUE 18): a ``jax.profiler``
        trace context for every ``profile_every``-th job started, a
        no-op context otherwise.  Start/stop failures (no profiler in
        this jax build, no TensorFlow trace backend, double-start) are
        swallowed — profiling must never fail a job — and a successful
        capture is registered in the compile ledger (kind ``profile``)
        + the ``profile.captures`` counter so the warehouse ingests
        the artifact path."""
        self._jobs_started += 1
        if (self.profile_every <= 0
                or self._jobs_started % self.profile_every != 0):
            return contextlib.nullcontext()
        return self._profile_capture(job)

    @contextlib.contextmanager
    def _profile_capture(self, job: JobRecord):
        path = os.path.join(self.profile_dir, f"job-{job.job_id}")
        try:
            import jax

            os.makedirs(path, exist_ok=True)
            jax.profiler.start_trace(path)
        except Exception:
            yield  # tolerant no-op where the profiler is unavailable
            return
        try:
            yield
        finally:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            try:
                from ..obs.compilation import record_profile

                record_profile(path)
            except Exception:
                pass

    def run_one(self, job: JobRecord) -> bool:
        """Run one claimed job through the retry machinery; True on
        success."""
        runner = self.run_job_fn or self._run_job
        resumes0 = int(METRICS.snapshot().get("counters", {}).get(
            "checkpoint.resumes", 0))
        with self._recorder(job), self._maybe_profile(job), \
                span(f"Job-{job.job_id}", metric="job",
                     job_id=job.job_id, input=job.input,
                     attempt=job.attempts, priority=job.priority,
                     batch=1):
            try:
                summary = run_with_timeout(
                    lambda: runner(job), self.timeout_s,
                    label=f"job {job.job_id}")
            except Exception as exc:
                self._handle_failure(job, exc)
                return False
            resumed = int(METRICS.snapshot().get("counters", {}).get(
                "checkpoint.resumes", 0)) - resumes0
            if resumed > 0:
                self._mark_job(job, "checkpoint-resume",
                               resumes=resumed)
        self.spool.mark_done(job, summary if isinstance(summary, dict)
                             else {})
        self._note_done(job)
        METRICS.inc("scheduler.succeeded")
        return True

    # -- the drain loop ----------------------------------------------------

    def drain(self, max_jobs: int | None = None, wait: bool = False,
              poll_s: float = 5.0) -> dict:
        """Claim and run jobs until the queue is empty (or ``wait``
        to poll for more), appending one throughput record to the
        bench history ledger (obs/history.py, kind ``serve``)."""
        from ..obs.compilation import (
            configure_compile_ledger,
            install_compile_ledger,
        )
        from ..obs.metrics import install_compile_hook

        install_compile_hook()
        # geometry-keyed compile ledger (ISSUE 18): one spool-level
        # compiles.jsonl attributing every backend compile this drain
        # pays to the search geometry that triggered it
        configure_compile_ledger(
            os.path.join(self.spool.root, "compiles.jsonl"))
        install_compile_ledger()
        from ..obs import lineage

        # candidate provenance ledger (ISSUE 19): one spool-level
        # lineage.jsonl recording every selection decision of every
        # job this drain runs; empty path = the --no-lineage hatch
        lineage_path = os.path.join(self.spool.root, "lineage.jsonl")
        lineage.configure_lineage(lineage_path if self.lineage else "")
        lov0 = lineage.overhead()  # lineage mark-count origin
        sampler = self._start_telemetry()
        ov0 = timeline.overhead()  # mark-cost ledger origin
        t0 = time.time()
        timers0 = {
            name: float(rec.get("host_s", 0.0))
            for name, rec in
            METRICS.snapshot().get("timers", {}).items()
        }  # cold-start phase-decomposition origin
        span_c0 = span_cursor()  # drain-level duty-cycle ledger origin
        claimed = succeeded = 0
        coldstart: dict | None = None
        try:
            while max_jobs is None or claimed < max_jobs:
                job = self.spool.claim(self.worker_id,
                                       host=self.host_label)
                if job is None:
                    if not wait:
                        break
                    self._idle_poll()
                    pause(poll_s, self.sleeper)
                    continue
                mates: list = []
                if self.batch > 1 and self.run_job_fn is None:
                    room = self.batch - 1
                    if max_jobs is not None:
                        room = min(room, max_jobs - claimed - 1)
                    if room > 0:
                        mates = self._claim_batch_mates(job, room)
                claimed += 1 + len(mates)
                if mates:
                    succeeded += self._run_batch_jobs([job] + mates)
                elif self.run_one(job):
                    succeeded += 1
                if coldstart is None and succeeded > 0:
                    coldstart = self._coldstart(t0, timers0, span_c0)
            elapsed = time.time() - t0
            jobs_per_hour = (succeeded / (elapsed / 3600.0)
                             if elapsed > 0 else 0.0)
            METRICS.gauge("scheduler.jobs_per_hour", jobs_per_hour)
            # drain-level device_duty_cycle (ISSUE 11): device/link
            # seconds across EVERY job's spans over drain wall-clock —
            # 1.0 means the devices never idled between jobs.
            # Overwrites the per-run figure _finalise left, so the
            # serve ledger and the final telemetry sample carry the
            # drain-level number (the health rule reads this gauge)
            duty = (device_seconds(span_c0) / elapsed
                    if elapsed > 0 else 0.0)
            METRICS.gauge("device_duty_cycle", round(duty, 4))
        finally:
            # stop AFTER the jobs_per_hour gauge so the final sample
            # carries the drain's headline figure
            if sampler is not None:
                sampler.stop()
        summary = {
            "claimed": claimed,
            "succeeded": succeeded,
            "failed": claimed - succeeded,
            "elapsed_s": round(elapsed, 3),
            "jobs_per_hour": round(jobs_per_hour, 3),
            "geometry_buckets": len(self.geometries),
            "batch": self.batch,
            # timed-out attempt threads still alive in this process
            # (run_with_timeout abandons them; serve/retry.py)
            "timeout_abandoned": abandoned_count(),
        }
        if coldstart is not None:
            summary["coldstart"] = coldstart
        if sampler is not None:
            summary["telemetry"] = {
                "samples": sampler.samples_written,
                "overhead_s": round(sampler.overhead_s, 6),
                "shard": sampler.path,
            }
        ov1 = timeline.overhead()
        summary["timeline"] = {
            "marks": ov1["marks"] - ov0["marks"],
            "overhead_s": round(ov1["seconds"] - ov0["seconds"], 6),
            "errors": ov1["errors"] - ov0["errors"],
        }
        lov1 = lineage.overhead()
        lg = {
            "marks": lov1["marks"] - lov0["marks"],
            # the Lineage spans: marks plus the ids and margins
            # computed for them
            "overhead_s": round(lineage.span_seconds(span_c0), 6),
            "errors": lov1["errors"] - lov0["errors"],
        }
        if self.lineage and self._drained_runs:
            # the drain's selection funnel, scoped to the jobs THIS
            # worker finished (fleet mates write their own records)
            fn = lineage.funnel(lineage.read_lineage(lineage_path),
                                runs=self._drained_runs)
            lg.update({
                "decoded": fn["decoded"],
                "absorbed": fn["absorbed"],
                "cut": fn["cut"],
                "emitted": fn["emitted"],
                "pass_frac": round(fn["pass_frac"], 6),
                "absorbed_frac": round(fn["absorbed_frac"], 6),
            })
        summary["lineage"] = lg
        self._append_throughput(summary)
        return summary

    def _coldstart(self, t0: float, timers0: dict,
                   span_c0: int) -> dict:
        """Cold-start decomposition (ISSUE 18): wall time from drain
        start to the FIRST finished job, split into where it went —
        observation ``read`` (obs_read host seconds), XLA ``compile``
        (jit_compile host seconds), device ``execute`` (span-attributed
        device seconds) and ``trace`` (the remainder: jax tracing +
        host dispatch + claim bookkeeping).  The headline total lands
        in the ``coldstart.cold_to_first_candidate_s`` gauge (so it
        rides the telemetry stream) and in the drain summary; bench
        ``--coldstart`` ledgers it for the perf gate."""
        snap = METRICS.snapshot()
        timers = snap.get("timers", {})

        def delta(name: str) -> float:
            now = float(timers.get(name, {}).get("host_s", 0.0))
            return max(0.0, now - float(timers0.get(name, 0.0)))

        total = max(0.0, time.time() - t0)
        read_s = delta("obs_read")
        compile_s = delta("jit_compile")
        execute_s = max(0.0, device_seconds(span_c0))
        trace_s = max(0.0, total - read_s - compile_s - execute_s)
        METRICS.gauge("coldstart.cold_to_first_candidate_s",
                      round(total, 6))
        return {
            "cold_to_first_candidate_s": round(total, 6),
            "read_s": round(read_s, 6),
            "trace_s": round(trace_s, 6),
            "compile_s": round(compile_s, 6),
            "execute_s": round(execute_s, 6),
        }

    def _start_telemetry(self):
        """Spin up the per-host telemetry sampler for this drain (None
        when disabled).  The worker owns the obs->serve seam: it hands
        the sampler a shard path and a queue-depth callable, so
        obs/telemetry.py never imports serve/."""
        if self.telemetry_interval_s <= 0:
            return None
        from ..obs.telemetry import TelemetrySampler, shard_path

        label = self.host_label or self.worker_id
        sampler = TelemetrySampler(
            shard_path(os.path.join(self.spool.root, "fleet"), label),
            label,
            self.telemetry_interval_s,
            extras=lambda: {"queue": self.spool.counts()},
        )
        return sampler.start()

    def _idle_poll(self) -> None:
        """Hook run on every empty poll of a waiting drain (before
        the pause).  The fleet worker reaps expired leases here —
        idle hosts are the ones with time to adopt a dead host's
        jobs."""

    def _append_throughput(self, summary: dict) -> None:
        """One ledger record per drain (the survey-level counterpart
        of bench.py's per-run records; jobs_per_hour is the headline
        metric the README schema table documents)."""
        if summary["claimed"] == 0:
            return  # an empty poll is not a throughput sample
        from ..obs.history import (
            append_history,
            make_history_record,
            stage_device_seconds,
        )
        from .health import percentile

        snap = METRICS.snapshot()
        counters = snap.get("counters", {})
        tl = summary.get("timeline", {})
        lg = summary.get("lineage", {})
        rec = make_history_record(
            "serve",
            {
                "jobs_claimed": summary["claimed"],
                "jobs_succeeded": summary["succeeded"],
                "jobs_failed": summary["failed"],
                "elapsed_s": summary["elapsed_s"],
                "jobs_per_hour": summary["jobs_per_hour"],
                # batched dispatch (ISSUE 9): configured stack depth
                # plus how well the dispatches actually filled — the
                # perf gate watches the jobs_per_hour multiplier
                # between batch=1 and batch=B records
                "batch": self.batch,
                "batched_dispatches": int(
                    counters.get("scheduler.batched_dispatches", 0)),
                "batch_fill": int(
                    counters.get("scheduler.batch_fill", 0)),
                # pipelined dispatch (ISSUE 11): the drain's device
                # seconds per wall second; perf_report's serve table
                # shows it next to jobs_per_hour
                "device_duty_cycle": float(
                    snap.get("gauges", {}).get("device_duty_cycle",
                                               0.0)),
                # load observatory (ISSUE 12): end-to-end latency of
                # the jobs this drain finished (sojourn = submit->done
                # from timeline marks) and the cost of writing the
                # timeline itself — perf_report's serve table shows
                # the p95s next to jobs_per_hour
                "sojourn_p50": round(
                    percentile(self._sojourns, 0.50), 6),
                "sojourn_p95": round(
                    percentile(self._sojourns, 0.95), 6),
                "queue_wait_p50": round(
                    percentile(self._queue_waits, 0.50), 6),
                "queue_wait_p95": round(
                    percentile(self._queue_waits, 0.95), 6),
                "timeline_marks": int(tl.get("marks", 0)),
                "timeline_overhead_s": float(
                    tl.get("overhead_s", 0.0)),
                # sensitivity observatory (ISSUE 14): known-answer
                # canary jobs this drain checked; the canary_recovery
                # health rule goes crit on a missed one
                "canary_recovered": int(
                    counters.get("canary.recovered", 0)),
                "canary_missed": int(
                    counters.get("canary.missed", 0)),
                # candidate provenance (ISSUE 19): the drain's exact
                # selection funnel + the ledger's cost (its spans);
                # baselines band the fracs and the distill_collapse
                # health rule fires on departures
                "lineage_marks": int(lg.get("marks", 0)),
                "lineage_overhead_s": float(lg.get("overhead_s", 0.0)),
                "lineage_decoded": int(lg.get("decoded", 0)),
                "lineage_emitted": int(lg.get("emitted", 0)),
                "lineage_pass_frac": float(lg.get("pass_frac", 0.0)),
                "lineage_absorbed_frac": float(
                    lg.get("absorbed_frac", 0.0)),
            },
            stage_device_s=stage_device_seconds(snap),
            config={
                "spool": self.spool.root,
                "worker": self.worker_id,
                "single_device": self.single_device,
                "geometry_buckets": summary["geometry_buckets"],
                # fleet mode: which host this throughput sample is
                # from (obs/history.py documents the serve schema)
                **({"host": self.host_label}
                   if self.host_label else {}),
            },
        )
        append_history(rec, self.history_path)
