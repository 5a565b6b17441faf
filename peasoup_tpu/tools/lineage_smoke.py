"""Candidate-provenance smoke test (``make lineage-smoke``).

Phase 1 — conservation: drain one synthetic pulsar observation with
the lineage ledger on and prove the selection funnel conserves
EXACTLY — every decoded candidate id reaches exactly one terminal
state (``decoded == absorbed + cut + emitted``, the
:func:`peasoup_tpu.obs.lineage.check_conservation` proof), the drain
summary exports the same funnel, and provenance's cost (the drain's
``Lineage`` spans: ids, margins and writes) stays below 1% of the
drain wall-clock.

Phase 2 — the ``why`` verb: starting from ONLY the strongest store
record (the golden injected-pulse candidate), ``why <candidate-id>``
must reconstruct the full decision chain — decoded, annotations,
``emitted`` terminal, the ``stored`` mark — and report the absorbed
children with their rules and margins.

Phase 3 — bit-identical output: draining the same observation with
``--no-lineage`` must produce candidates whose physics fields match
the lineage-on drain byte for byte (provenance is observation, never
behaviour), and must leave no ``lineage.jsonl`` behind.

Phase 4 — distill collapse: three baseline drains build funnel-rate
history in a scratch serve ledger; a fourth drain with a
deliberately widened harmonic/frequency tolerance (``freq_tol``)
must shift the funnel enough that the ``distill_collapse`` health
rule leaves ``ok`` and :func:`peasoup_tpu.obs.baseline.funnel_anomalies`
emits a typed anomaly record.

Exit status 0 only if every assertion holds — CI-gateable like the
other smokes.
"""

from __future__ import annotations

import argparse
import io
import json
import os
import shutil
import sys
import time
import warnings
from contextlib import redirect_stdout


def _check(ok: bool, what: str, failures: list[str]) -> None:
    print(("PASS " if ok else "FAIL ") + what)
    if not ok:
        failures.append(what)


def _drain(spool_dir: str, fil: str, overrides: dict, history: str,
           lineage: bool = True) -> tuple:
    """Submit ``fil`` into a spool and drain it with one worker;
    returns (spool, drain summary, wall seconds)."""
    from peasoup_tpu.obs.metrics import REGISTRY
    from peasoup_tpu.serve import BackoffPolicy, JobSpool, SurveyWorker

    REGISTRY.reset()
    spool = JobSpool(spool_dir)
    spool.submit(fil, overrides)
    worker = SurveyWorker(
        spool, single_device=True,
        backoff=BackoffPolicy(max_attempts=2, base_s=0.0),
        history_path=history, sleeper=lambda s: None,
        lineage=lineage,
    )
    t0 = time.perf_counter()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        summary = worker.drain()
    return spool, summary, time.perf_counter() - t0


def _physics(rec: dict) -> tuple:
    """A store record's candidate physics — everything that must be
    invariant under the lineage flag (ids/provenance excluded: they
    embed the per-drain job id by design)."""
    return (rec["dm"], rec["acc"], rec["jerk"], rec["freq"],
            rec["snr"], rec["folded_snr"], rec["nh"])


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="peasoup-tpu-lineage-smoke",
        description="Peasoup-TPU - candidate provenance smoke test",
    )
    p.add_argument("--dir", default="/tmp/peasoup-lineage-smoke",
                   help="scratch directory (wiped)")
    args = p.parse_args(argv)

    shutil.rmtree(args.dir, ignore_errors=True)
    os.makedirs(args.dir)
    # scratch ledger: the widened-tolerance drain writes distorted
    # funnel records that must never pollute the repo baseline
    history = os.path.join(args.dir, "history.jsonl")

    from peasoup_tpu.obs import lineage
    from peasoup_tpu.obs.injection import smoke_observation
    from peasoup_tpu.serve.store import CandidateStore

    fil = os.path.join(args.dir, "obs.fil")
    smoke_observation(fil, nsamps=4096, nchans=16, seed=0)
    overrides = {"dm_end": 20.0, "min_snr": 6.0, "npdmp": 2,
                 "limit": 10}

    failures: list[str] = []

    # ---- phase 1: exact conservation on a real drain -----------------
    spool_dir = os.path.join(args.dir, "jobs")
    spool, summary, wall = _drain(spool_dir, fil, overrides, history)
    done = spool.jobs("done")
    _check(len(done) == 1, "drain finished the job", failures)
    runs = [j.job_id for j in done]

    ledger_path = os.path.join(spool_dir, "lineage.jsonl")
    marks = lineage.read_lineage(ledger_path)
    _check(os.path.exists(ledger_path) and len(marks) > 0,
           f"lineage ledger written ({len(marks)} marks)", failures)

    problems = lineage.check_conservation(marks, runs=runs)
    _check(problems == [],
           "conservation: every decoded id reaches exactly one "
           "terminal state" + (f" ({problems[:3]})" if problems else ""),
           failures)
    fn = lineage.funnel(marks, runs=runs)
    _check(fn["decoded"] > 0 and fn["decoded"]
           == fn["absorbed"] + fn["cut"] + fn["emitted"],
           f"funnel conserves exactly: {fn['decoded']} decoded == "
           f"{fn['absorbed']} absorbed + {fn['cut']} cut + "
           f"{fn['emitted']} emitted", failures)

    lg = summary.get("lineage", {})
    _check(lg.get("decoded") == fn["decoded"]
           and lg.get("emitted") == fn["emitted"],
           "drain summary exports the same funnel", failures)
    overhead_s = float(lg.get("overhead_s", float("inf")))
    _check(overhead_s < 0.01 * wall,
           f"lineage overhead {overhead_s:.4f}s < 1% of "
           f"{wall:.2f}s drain", failures)

    # ---- phase 2: `why` reconstructs the chain from the store --------
    store = CandidateStore(os.path.join(spool_dir, "candidates.jsonl"))
    recs = store.records()
    _check(bool(recs) and all(r.get("cand_id") for r in recs),
           f"store records carry candidate ids ({len(recs)})",
           failures)
    _check(bool(recs) and all(
        (r.get("prov") or {}).get("run") for r in recs),
        "store records carry a provenance block", failures)

    why_ok = chain = None
    if recs:
        golden = max(recs, key=lambda r: r.get("snr", 0.0))
        from peasoup_tpu.serve import cli as serve_cli

        why_json = os.path.join(args.dir, "why.json")
        buf = io.StringIO()
        with redirect_stdout(buf):
            rc = serve_cli.main(["--spool", spool_dir, "why",
                                 golden["cand_id"], "--json", why_json])
        chain = json.load(open(why_json))["chain"] if rc == 0 else None
        why_ok = (rc == 0 and chain is not None and chain["decoded"]
                  and (chain["terminal"] or {}).get("kind") == "emitted"
                  and any(m.get("kind") == "stored"
                          for m in chain["annotations"]))
        print(buf.getvalue(), end="")
    _check(bool(why_ok),
           "`why` reconstructs decoded -> emitted -> stored from the "
           "store record alone", failures)
    if chain is not None and chain["children"]:
        kid = chain["children"][0]
        _check((kid["terminal"] or {}).get("kind") == "absorbed"
               and (kid["terminal"] or {}).get("rule") is not None,
               f"absorbed child carries its rule "
               f"({(kid['terminal'] or {}).get('rule')})", failures)

    # ---- phase 3: bit-identical candidates with lineage off ----------
    spool_off_dir = os.path.join(args.dir, "jobs-off")
    _, summary_off, _ = _drain(spool_off_dir, fil, overrides, history,
                               lineage=False)
    off_recs = CandidateStore(
        os.path.join(spool_off_dir, "candidates.jsonl")).records()
    same = (sorted(map(_physics, recs))
            == sorted(map(_physics, off_recs)))
    _check(same and len(off_recs) == len(recs),
           f"--no-lineage candidates bit-identical "
           f"({len(off_recs)} == {len(recs)})", failures)
    _check(not os.path.exists(
        os.path.join(spool_off_dir, "lineage.jsonl")),
        "--no-lineage leaves no ledger behind", failures)
    _check("lineage" in summary and "decoded" not in
           summary_off.get("lineage", {"decoded": None}),
           "drain summaries reflect the lineage flag", failures)

    # ---- phase 4: widened tolerance trips distill_collapse -----------
    # a noise-only observation (no injected train) keeps the BASELINE
    # absorption moderate — the pulse train's harmonic comb would sit
    # near-fully absorbed already, leaving no headroom for the widened
    # tolerance to depart from
    noise_fil = os.path.join(args.dir, "noise.fil")
    from peasoup_tpu.obs.injection import synthesize

    synthesize(noise_fil, period=16.0 * 0.000256, duty=0.05, amp=0.0,
               noise_max=32, nsamps=4096, nchans=16, tsamp=0.000256,
               seed=7)
    noise_ov = {"dm_end": 5.0, "min_snr": 3.5, "npdmp": 0,
                "limit": 10}
    # scratch ledger for this phase only: phases 1/3 appended records
    # with a different observation's funnel shape
    collapse_history = os.path.join(args.dir, "history-collapse.jsonl")
    for i in range(3):  # three identical baseline drains
        _drain(os.path.join(args.dir, f"jobs-base{i}"), noise_fil,
               noise_ov, collapse_history)
    wide = dict(noise_ov)
    wide["freq_tol"] = 0.5  # absurd harmonic/frequency tolerance:
    # every candidate within a factor-~2 frequency band matches, so
    # the distillers absorb nearly the whole decoded population
    _drain(os.path.join(args.dir, "jobs-wide"), noise_fil, wide,
           collapse_history)

    from peasoup_tpu.obs.baseline import funnel_anomalies
    from peasoup_tpu.obs.history import load_history
    from peasoup_tpu.serve.health import (
        HealthContext,
        rule_distill_collapse,
    )

    serve_recs = load_history(collapse_history, kinds=("serve",))
    _check(len(serve_recs) == 4 and all(
        r.get("metrics", {}).get("lineage_decoded", 0) > 0
        for r in serve_recs),
        f"{len(serve_recs)} serve records carry funnel metrics",
        failures)
    ctx = HealthContext(now=time.time(), samples=[], recent=[],
                        latest={}, queue={}, running=[],
                        ledger=serve_recs)
    findings = rule_distill_collapse(ctx)
    verdict = findings[0].severity if findings else "?"
    base_abs = serve_recs[0]["metrics"].get("lineage_absorbed_frac")
    head_abs = serve_recs[-1]["metrics"].get("lineage_absorbed_frac")
    _check(verdict in ("warn", "crit"),
           f"distill_collapse trips on the widened tolerance "
           f"(severity={verdict}, absorbed {base_abs} -> {head_abs})",
           failures)
    anoms = funnel_anomalies(serve_recs)
    _check(bool(anoms),
           f"funnel baseline emits {len(anoms)} anomaly record(s) "
           f"({[a['metric'] for a in anoms]})", failures)

    if failures:
        print(f"\nlineage-smoke: {len(failures)} check(s) FAILED",
              file=sys.stderr)
        return 1
    print("\nlineage-smoke: all checks passed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
