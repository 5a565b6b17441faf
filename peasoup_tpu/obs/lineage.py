"""Candidate provenance: the lineage ledger + selection-funnel audit.

Every raw peak the pipeline decodes gets a stable content-derived
candidate id (:func:`candidate_uid` — a hash of the run id and the
candidate's trial coordinates), and every selection decision between
decode and the survey store appends one typed *mark* to a rotating,
torn-tolerant ``lineage.jsonl`` stream:

====================  =====================================================
kind                  meaning
====================  =====================================================
``decoded``           a DM row's merged peaks entered the funnel (``ids``)
``clipped``           peaks lost to a capacity clip (aggregate ``n``)
``dropped``           decode under-delivery sentinels dropped (``n``)
``merged``            duplicate spectrum bins merged pre-candidate (``n``)
``superseded``        a whole decode pass discarded in favour of an
                      escalated re-search (aggregate ``n``)
``absorbed``          a distiller folded the candidate into ``absorber``
                      under ``rule`` with tolerance ``margin`` (terminal)
``cut``               dropped at the output ``limit`` cut (terminal)
``emitted``           survived to the SearchResult (terminal)
``scored``            scorer verdict flags (annotation)
``fold_cut``          in the fold period window but beyond top-npdmp
``folded``            selected for folding (annotation)
``stored``            ingested into the survey store (annotation)
``quarantined``       canary candidate kept out of science reads
====================  =====================================================

**Conservation invariant** (the timeline-waterfall pattern applied to
candidates): every ``decoded`` id reaches *exactly one* of the three
terminal states — ``absorbed``, ``cut`` or ``emitted`` — so

    ``n(decoded) == n(absorbed) + n(cut) + n(emitted)``

holds exactly.  :func:`check_conservation` proves it mechanically and
is asserted in tests and ``make lineage-smoke``.  ``clipped`` /
``dropped`` / ``merged`` / ``superseded`` account for peaks that never
entered the id'd population (lost before or instead of decode) and are
aggregate counts by design.

Provenance is timed by its call sites, not by the writer: every
contiguous block of provenance work (ids, margins, marks) runs under
:func:`marking`, one ``Lineage`` span carrying ``marks``, the marks the
block wrote, and ``writes``, the file writes it took.  :func:`span_seconds`
sums those spans, so the serve ledger's ``lineage_overhead_s`` (gated
below 1% of drain wall-clock by the smoke) covers the id hashing and
margin grids computed in the callers' arguments too.  :func:`overhead`
counts marks and write errors.  Marking is best-effort and never raises
— provenance must not kill a multi-hour search.  The stream schema is
declared in :mod:`.streams` so lint rule PSL013 proves writer/reader
agreement.

Marks are written per block: inside :func:`marking` a thread's marks
are buffered and reach the file as one write, after one size check,
when its outermost block closes (on an exception too).  A mark made
outside any block is written at once.  A crash loses at most the marks
of the blocks still open, and a torn last line is skipped by the
reader.  Rotation is checked per block, so the live file can pass
``max_bytes`` by one block before it seals.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from contextlib import contextmanager

from .metrics import REGISTRY as METRICS
from .trace import get_tracer, span

LINEAGE_VERSION = 1

#: rotate the live ledger past this size to ``<path>.1`` (one retained
#: generation, the events.jsonl / telemetry-shard scheme)
DEFAULT_MAX_BYTES = 16 * 1024 * 1024

#: the three states a decoded candidate may terminate in — exactly one
#: each (see CONTRIBUTING.md "Adding a decision kind")
TERMINAL_KINDS = ("absorbed", "cut", "emitted")

#: aggregate pre-decode loss accounting (counts, never ids)
AGGREGATE_KINDS = ("clipped", "dropped", "merged", "superseded")

#: non-terminal per-candidate annotations
ANNOTATION_KINDS = ("scored", "fold_cut", "folded", "stored",
                    "quarantined")

#: the span around each contiguous block of provenance work
LINEAGE_SPAN = "Lineage"

_OV_LOCK = threading.Lock()
_OVERHEAD = {"marks": 0, "errors": 0}


def overhead() -> dict:
    """Total marks attempted and write errors, process-wide.  Their
    cost is read from the ``Lineage`` spans (:func:`span_seconds`)."""
    with _OV_LOCK:
        return dict(_OVERHEAD)


@contextmanager
def marking():
    """One ``Lineage`` span around a block of provenance work, with
    attributes ``marks`` and ``writes``: the change of the
    ``lineage.marks`` and ``lineage.writes`` counters across the block.
    The block's marks are buffered and written when it closes, inside
    the span (:meth:`LineageRecorder.buffered`).  Opens nothing when no
    ledger is configured.  Wrap a whole block, never one mark: a span
    polls device memory when it closes."""
    rec = _RECORDER
    if rec is None:
        yield
        return
    m0 = METRICS.counter("lineage.marks")
    w0 = METRICS.counter("lineage.writes")
    with span(LINEAGE_SPAN) as sp:
        try:
            with rec.buffered():
                yield
        finally:
            sp.set(marks=METRICS.counter("lineage.marks") - m0,
                   writes=METRICS.counter("lineage.writes") - w0)


def span_seconds(since: int = 0) -> float:
    """Seconds of the ``Lineage`` spans closed since a
    :func:`.trace.span_cursor` checkpoint: the whole cost of
    provenance, ids and margins included."""
    return sum(r.t_end - r.t_start for r in get_tracer().records()[since:]
               if r.name == LINEAGE_SPAN)


def candidate_uid(run: str, cand) -> str:
    """Stable content-derived candidate id.

    Hash of the run id plus the candidate's trial coordinates
    (dm trial index, accel, jerk, harmonic level, frequency) — the
    fields fixed at decode time and never mutated afterwards (folding
    touches only ``folded_snr`` / ``opt_period``), so the id computed
    at decode, at store-ingest and from a parsed store record is
    identical.  Tolerates pre-jerk candidates (parsed overview.xml,
    legacy checkpoints) the way the binary writer does: missing
    ``dm_idx``/``jerk`` hash as zero."""
    return uid_from_fields(run, getattr(cand, "dm_idx", 0), cand.acc,
                           getattr(cand, "jerk", 0.0), cand.nh,
                           cand.freq)


def uid_from_fields(run: str, dm_idx, acc, jerk, nh, freq) -> str:
    """:func:`candidate_uid` from bare fields (store-record backfill,
    mesh decode arrays).  ``repr(float(...))`` is the shortest exact
    float round-trip, so json-serialised fields reproduce the id."""
    key = "|".join((
        str(run), str(int(dm_idx)), repr(float(acc)),
        repr(float(jerk)), str(int(nh)), repr(float(freq)),
    ))
    return hashlib.sha1(key.encode()).hexdigest()[:16]


class LineageRecorder:
    """Append-only JSONL mark sink with ``.1`` rotation.

    The handle opens lazily and is line-buffered.  Inside
    :meth:`buffered` a thread's marks are held and written together;
    elsewhere each mark is written at once.  An I/O failure disables
    persistence for the rest of the run (each mark it loses counted in
    ``lineage.mark_errors``, never an exception)."""

    def __init__(self, path: str, run: str = "", *,
                 max_bytes: int = DEFAULT_MAX_BYTES):
        self.path = str(path)
        self.run = str(run)
        self.max_bytes = int(max_bytes)
        self._lock = threading.Lock()
        self._file = None
        self._io_failed = False
        self._local = threading.local()  # .lines: this thread's block

    def _maybe_rotate(self) -> None:
        # caller holds the lock; errors are swallowed (a stat race
        # must not kill the emitting run)
        if self.max_bytes <= 0:
            return
        try:
            if os.path.getsize(self.path) < self.max_bytes:
                return
        except OSError:
            return  # no file yet
        if self._file is not None:
            try:
                self._file.close()
            except OSError:
                pass
            self._file = None
        try:
            os.replace(self.path, self.path + ".1")
        except OSError:
            pass

    def _lost(self, n: int) -> None:
        with self._lock:
            self._io_failed = True
        METRICS.inc("lineage.mark_errors", n)
        with _OV_LOCK:
            _OVERHEAD["errors"] += n

    def _write(self, text: str, n: int) -> None:
        """Write ``n`` marks' lines as one write after one size check;
        a failure latches and counts the ``n`` marks lost.  A latch set
        before the call drops them uncounted, as it does a mark made
        after it."""
        try:
            with self._lock:
                if self._io_failed:
                    return
                self._maybe_rotate()
                if self._file is None:
                    d = os.path.dirname(self.path)
                    if d:
                        os.makedirs(d, exist_ok=True)
                    self._file = open(self.path, "a", buffering=1)
                self._file.write(text)
            METRICS.inc("lineage.writes")
            METRICS.inc("lineage.marks", n)
        except OSError:
            self._lost(n)

    @contextmanager
    def buffered(self):
        """Hold this thread's marks until the outermost block closes,
        then write them as one (an exception in the block still writes
        them).  Other threads' marks are never held here."""
        loc = self._local
        if getattr(loc, "lines", None) is not None:
            yield  # nested: the outermost block writes
            return
        loc.lines = []
        try:
            yield
        finally:
            lines, loc.lines = loc.lines, None
            if lines:
                self._write("".join(lines), len(lines))

    def mark(self, kind: str, *, run: str | None = None,
             **fields) -> None:
        """Append one typed decision mark; best-effort, never raises."""
        try:
            rec = {
                "v": LINEAGE_VERSION,
                "ts": round(time.time(), 6),
                "run": self.run if run is None else str(run),
                "kind": str(kind),
            }
            for k, v in fields.items():
                if v is not None:
                    rec[k] = v
            line = json.dumps(rec) + "\n"
        except (TypeError, ValueError):
            self._lost(1)
        else:
            lines = getattr(self._local, "lines", None)
            if lines is None:
                self._write(line, 1)
            elif not self._io_failed:
                lines.append(line)
        finally:
            with _OV_LOCK:
                _OVERHEAD["marks"] += 1

    def close(self) -> None:
        with self._lock:
            if self._file is not None:
                try:
                    self._file.close()
                except OSError:
                    pass
                finally:
                    self._file = None


_global_lock = threading.Lock()
_RECORDER: LineageRecorder | None = None


def configure_lineage(path: str, *, run: str = "",
                      max_bytes: int = DEFAULT_MAX_BYTES
                      ) -> LineageRecorder | None:
    """Point the process-wide lineage ledger at ``path`` (empty path
    disables it — the ``--no-lineage`` escape hatch)."""
    global _RECORDER
    with _global_lock:
        if _RECORDER is not None:
            _RECORDER.close()
        _RECORDER = (LineageRecorder(path, run, max_bytes=max_bytes)
                     if path else None)
        return _RECORDER


def get_lineage() -> LineageRecorder | None:
    return _RECORDER


def enabled() -> bool:
    """Cheap guard for instrumentation call sites: id hashing and mark
    assembly are skipped entirely when no ledger is configured."""
    return _RECORDER is not None


def mark(kind: str, *, run: str | None = None, **fields) -> None:
    """Module-level convenience: mark on the configured recorder, or
    no-op when lineage is off."""
    rec = _RECORDER
    if rec is not None:
        rec.mark(kind, run=run, **fields)


# -------------------------------------------------------------------------
# readers: torn-tolerant parse, funnel accounting, conservation proof
# -------------------------------------------------------------------------

def read_lineage(path: str, run: str | None = None) -> list[dict]:
    """Parse marks from ``path`` (rotated ``.1`` generation first, so
    order is append order).  Torn/garbage lines — a crashed writer's
    partial tail — are skipped, not fatal.  ``run`` filters to one
    run's marks."""
    marks: list[dict] = []
    for p in (path + ".1", path):
        try:
            fh = open(p, encoding="utf-8")
        except OSError:
            continue
        with fh:
            for line in fh:
                try:
                    m = json.loads(line)
                except (json.JSONDecodeError, ValueError):
                    continue  # torn line: tolerate, keep reading
                if not isinstance(m, dict):
                    continue
                if m.get("v") != LINEAGE_VERSION:
                    continue
                if run is not None and m.get("run") != run:
                    continue
                marks.append(m)
    return marks


def _mark_ids(m: dict) -> list[str]:
    if m.get("id") is not None:
        return [m["id"]]
    ids = m.get("ids")
    return list(ids) if ids else []


def funnel(marks, runs=None) -> dict:
    """Exact per-stage selection-funnel counts over ``marks``.

    Terminal/``decoded`` kinds count candidate *ids*; aggregate kinds
    sum their ``n`` fields.  ``pass_frac`` / ``absorbed_frac`` are the
    distillation-behaviour signals the baselines and the
    ``distill_collapse`` health rule watch."""
    if runs is not None:
        runs = set(runs)
        marks = [m for m in marks if m.get("run") in runs]
    counts = {k: 0 for k in
              ("decoded",) + TERMINAL_KINDS + AGGREGATE_KINDS}
    for m in marks:
        kind = m.get("kind")
        if kind == "decoded" or kind in TERMINAL_KINDS:
            counts[kind] += len(_mark_ids(m)) or int(m.get("n") or 0)
        elif kind in AGGREGATE_KINDS:
            counts[kind] += int(m.get("n") or 0)
    dec = counts["decoded"]
    counts["pass_frac"] = (counts["emitted"] / dec) if dec else 0.0
    counts["absorbed_frac"] = (counts["absorbed"] / dec) if dec else 0.0
    return counts


def check_conservation(marks, runs=None) -> list[str]:
    """Prove the conservation invariant; returns problem strings
    (empty list == the invariant holds).

    Every decoded id must appear in exactly one terminal state, every
    terminal id must have been decoded, and the stage counts must sum
    to the decoded count *exactly*."""
    if runs is not None:
        runs = set(runs)
        marks = [m for m in marks if m.get("run") in runs]
    decoded: set[str] = set()
    terminal: dict[str, list[str]] = {}
    n_terminal = 0
    for m in marks:
        kind = m.get("kind")
        if kind == "decoded":
            decoded.update(_mark_ids(m))
        elif kind in TERMINAL_KINDS:
            n_terminal += 1
            for cid in _mark_ids(m):
                terminal.setdefault(cid, []).append(kind)
    problems = []
    for cid, kinds in terminal.items():
        if len(kinds) > 1:
            problems.append(
                f"{cid}: {len(kinds)} terminal states {kinds}")
        if cid not in decoded:
            problems.append(f"{cid}: terminal {kinds[0]} but never "
                            f"decoded")
    for cid in decoded - set(terminal):
        problems.append(f"{cid}: decoded but reached no terminal state")
    if len(decoded) != n_terminal and not problems:
        problems.append(
            f"count mismatch: {len(decoded)} decoded != "
            f"{n_terminal} terminal marks")
    return problems


def why_chain(marks, cid: str, max_depth: int = 8) -> dict:
    """Reconstruct candidate ``cid``'s full decision chain from marks.

    Returns ``{"id", "run", "decoded", "terminal", "annotations",
    "absorbed_into", "children"}`` where ``children`` recurses into the
    candidates this one absorbed (an absorbed candidate may itself
    have absorbed others in an earlier stage)."""
    terminal = None
    absorbed_into = None
    annotations = []
    decoded = False
    run = None
    children_marks = []
    for m in marks:
        ids = _mark_ids(m)
        kind = m.get("kind")
        if kind == "decoded" and cid in ids:
            decoded = True
            run = m.get("run")
        elif cid in ids:
            if kind in TERMINAL_KINDS:
                terminal = m
                if kind == "absorbed":
                    absorbed_into = m.get("absorber")
            elif kind in ANNOTATION_KINDS:
                annotations.append(m)
            if run is None:
                run = m.get("run")
        if kind == "absorbed" and m.get("absorber") == cid:
            children_marks.append(m)
    children = []
    if max_depth > 0:
        for m in children_marks:
            children.append(why_chain(marks, m["id"],
                                      max_depth=max_depth - 1))
    return {
        "id": cid,
        "run": run,
        "decoded": decoded,
        "terminal": terminal,
        "annotations": annotations,
        "absorbed_into": absorbed_into,
        "children": children,
    }
