"""Candidate provenance (ISSUE 19): stable ids, the lineage ledger,
exact funnel conservation, the `why` chain, and the distillation
baselines — plus the assoc-count round-trip pinning ``<nassoc>`` to
the binary writer's pre-order flatten."""

import json
import os
import threading
import time
import xml.etree.ElementTree as ET

import pytest

from peasoup_tpu.data import Candidate
from peasoup_tpu.obs import lineage
from peasoup_tpu.obs.baseline import funnel_anomalies
from peasoup_tpu.obs.warehouse import Warehouse, lineage_rows
from peasoup_tpu.output import (
    CandidateFileParser,
    OutputFileWriter,
    write_candidate_binary,
)
from peasoup_tpu.search import distill
from peasoup_tpu.serve.health import HealthContext, rule_distill_collapse


# -------------------------------------------------------------------------
# stable candidate ids
# -------------------------------------------------------------------------

def test_uid_stable_and_json_roundtrip():
    c = Candidate(dm=12.5, dm_idx=7, acc=-3.25, jerk=0.5, nh=2,
                  snr=9.0, freq=123.456789)
    uid = lineage.candidate_uid("run-a", c)
    assert len(uid) == 16 and int(uid, 16) >= 0
    # same fields -> same id, however they arrive
    assert uid == lineage.uid_from_fields(
        "run-a", c.dm_idx, c.acc, c.jerk, c.nh, c.freq)
    # json round-trip (store record / overview.xml) reproduces the id:
    # repr(float) is the shortest exact round-trip
    fields = json.loads(json.dumps(
        {"dm_idx": c.dm_idx, "acc": c.acc, "jerk": c.jerk,
         "nh": c.nh, "freq": c.freq}))
    assert uid == lineage.uid_from_fields("run-a", **fields)
    # mutating what folding mutates must NOT move the id
    c.folded_snr, c.opt_period = 42.0, 0.1
    assert uid == lineage.candidate_uid("run-a", c)
    # but run and any trial coordinate must
    assert uid != lineage.candidate_uid("run-b", c)
    c2 = Candidate(dm=12.5, dm_idx=8, acc=-3.25, jerk=0.5, nh=2,
                   snr=9.0, freq=123.456789)
    assert uid != lineage.candidate_uid("run-a", c2)


# -------------------------------------------------------------------------
# the recorder: rotation, torn lines, failure latch
# -------------------------------------------------------------------------

def test_recorder_writes_and_reader_filters(tmp_path):
    path = str(tmp_path / "lineage.jsonl")
    rec = lineage.LineageRecorder(path, run="r1")
    rec.mark("decoded", ids=["a", "b"])
    rec.mark("emitted", id="a", rank=0)
    rec.mark("absorbed", run="r2", id="x", absorber="y",
             rule="harmonic", margin=1e-4)
    rec.close()
    with open(path, "a") as f:
        f.write('{"v": 1, "run": "r1", "kind": "torn')  # crashed tail
        f.write("\n")
        f.write(json.dumps({"v": 99, "run": "r1",
                            "kind": "future"}) + "\n")
    marks = lineage.read_lineage(path)
    assert [m["kind"] for m in marks] == ["decoded", "emitted",
                                          "absorbed"]
    assert all(m["v"] == lineage.LINEAGE_VERSION for m in marks)
    only_r1 = lineage.read_lineage(path, run="r1")
    assert [m["kind"] for m in only_r1] == ["decoded", "emitted"]
    # None-valued fields are elided, not serialised
    assert "rank" in marks[1] and "margin" in marks[2]


def test_recorder_rotates_and_reader_spans_generations(tmp_path):
    path = str(tmp_path / "lineage.jsonl")
    rec = lineage.LineageRecorder(path, run="r", max_bytes=256)
    for i in range(40):
        rec.mark("decoded", ids=[f"c{i}"])
    rec.close()
    assert (tmp_path / "lineage.jsonl.1").exists()
    marks = lineage.read_lineage(path)
    # one sealed generation is retained: the reader sees a contiguous
    # TAIL of the append order ending at the newest mark
    got = [m["ids"][0] for m in marks]
    want = [f"c{i}" for i in range(40)]
    assert 0 < len(got) < 40
    assert got == want[-len(got):]


def _block_lines(path):
    with open(path) as f:
        return [json.loads(line)["ids"][0] for line in f]


def test_recorder_rotates_whole_blocks(tmp_path, monkeypatch):
    """Rotation is checked once per block: a block never splits across
    generations, and the live file passes ``max_bytes`` by at most the
    one block that crossed it."""
    calls = _count_io(monkeypatch)
    path = str(tmp_path / "lineage.jsonl")
    rec = lineage.LineageRecorder(path, run="r", max_bytes=256)
    for b in range(12):
        with rec.buffered():
            for i in range(5):
                rec.mark("decoded", ids=[f"c{b}.{i}"])
        assert calls == {"getsize": b + 1, "writes": b + 1}
        live = os.stat(path).st_size
        assert len(_block_lines(path)) % 5 == 0
        assert live < 256 + live // len(_block_lines(path)) * 5
    rec.close()
    sealed = _block_lines(path + ".1")
    assert sealed and len(sealed) % 5 == 0
    got = [m["ids"][0] for m in lineage.read_lineage(path)]
    want = [f"c{b}.{i}" for b in range(12) for i in range(5)]
    assert 0 < len(got) < len(want) and got == want[-len(got):]


def test_recorder_io_failure_latches_never_raises(tmp_path):
    blocker = tmp_path / "dir"
    blocker.write_text("a file where the ledger dir should be")
    rec = lineage.LineageRecorder(str(blocker / "lineage.jsonl"))
    before = lineage.overhead()
    rec.mark("decoded", ids=["a"])  # must not raise
    rec.mark("emitted", id="a")
    after = lineage.overhead()
    assert after["errors"] >= before["errors"] + 1
    assert after["marks"] >= before["marks"] + 2


def test_module_level_configure_and_noop_when_off(tmp_path):
    path = str(tmp_path / "lineage.jsonl")
    try:
        lineage.configure_lineage(path, run="r9")
        assert lineage.enabled()
        lineage.mark("decoded", ids=["z"])
        lineage.configure_lineage("")  # the --no-lineage escape hatch
        assert not lineage.enabled()
        lineage.mark("decoded", ids=["never-written"])
    finally:
        lineage.configure_lineage("")
    marks = lineage.read_lineage(path)
    assert len(marks) == 1 and marks[0]["run"] == "r9"


# -------------------------------------------------------------------------
# funnel accounting + the conservation proof
# -------------------------------------------------------------------------

def _marks_ok(run="r"):
    # ids embed the run like real candidate_uid ids do, so a
    # multi-run ledger never collides
    a, b, c, d = (f"{run}-{x}" for x in "abcd")
    return [
        {"v": 1, "run": run, "kind": "decoded",
         "ids": [a, b, c, d]},
        {"v": 1, "run": run, "kind": "clipped", "n": 3},
        {"v": 1, "run": run, "kind": "absorbed", "id": a,
         "absorber": b, "rule": "harmonic", "margin": 1e-4},
        {"v": 1, "run": run, "kind": "cut", "id": c, "stage": "limit"},
        {"v": 1, "run": run, "kind": "scored", "id": b},
        {"v": 1, "run": run, "kind": "emitted", "id": b, "rank": 0},
        {"v": 1, "run": run, "kind": "emitted", "id": d, "rank": 1},
    ]


def test_funnel_conserves_exactly():
    fn = lineage.funnel(_marks_ok())
    assert fn["decoded"] == 4
    assert fn["decoded"] == fn["absorbed"] + fn["cut"] + fn["emitted"]
    assert (fn["absorbed"], fn["cut"], fn["emitted"]) == (1, 1, 2)
    assert fn["clipped"] == 3  # aggregate: counted, outside invariant
    assert fn["pass_frac"] == pytest.approx(0.5)
    assert fn["absorbed_frac"] == pytest.approx(0.25)
    assert lineage.check_conservation(_marks_ok()) == []


def test_funnel_filters_by_run():
    marks = _marks_ok("r1") + _marks_ok("r2")
    assert lineage.funnel(marks, runs=["r1"])["decoded"] == 4
    assert lineage.funnel(marks)["decoded"] == 8
    assert lineage.check_conservation(marks) == []


def test_conservation_detects_each_violation():
    leaked = _marks_ok()[:-1]  # d decoded, no terminal
    assert any("no terminal" in p
               for p in lineage.check_conservation(leaked))
    double = _marks_ok() + [
        {"v": 1, "run": "r", "kind": "cut", "id": "r-d"}]
    assert any("2 terminal states" in p
               for p in lineage.check_conservation(double))
    orphan = _marks_ok() + [
        {"v": 1, "run": "r", "kind": "emitted", "id": "ghost"}]
    assert any("never decoded" in p
               for p in lineage.check_conservation(orphan))


def test_why_chain_recurses_into_absorbed_children():
    marks = _marks_ok() + [
        # an earlier stage: "r-a" had itself absorbed "r-z"
        {"v": 1, "run": "r", "kind": "decoded", "ids": ["r-z"]},
        {"v": 1, "run": "r", "kind": "absorbed", "id": "r-z",
         "absorber": "r-a", "rule": "dm", "margin": 0.5},
    ]
    chain = lineage.why_chain(marks, "r-b")
    assert chain["decoded"] and chain["run"] == "r"
    assert chain["terminal"]["kind"] == "emitted"
    assert [m["kind"] for m in chain["annotations"]] == ["scored"]
    kid = chain["children"][0]
    assert kid["id"] == "r-a" and kid["absorbed_into"] == "r-b"
    assert kid["terminal"]["rule"] == "harmonic"
    grandkid = kid["children"][0]
    assert (grandkid["id"] == "r-z"
            and grandkid["terminal"]["rule"] == "dm")
    # depth limit stops the recursion, never errors
    shallow = lineage.why_chain(marks, "r-b", max_depth=1)
    assert shallow["children"][0]["children"] == []


# -------------------------------------------------------------------------
# warehouse ingest + funnel baselines + the health rule
# -------------------------------------------------------------------------

def test_lineage_rows_and_ingest(tmp_path):
    path = str(tmp_path / "lineage.jsonl")
    rec = lineage.LineageRecorder(path, run="r1")
    for m in _marks_ok("r1"):
        rec.mark(m["kind"], **{k: v for k, v in m.items()
                               if k not in ("v", "run", "kind")})
    rec.close()
    rows = lineage_rows(lineage.read_lineage(path))
    by_metric = {}
    for r in rows:
        by_metric.setdefault(r["metric"], 0.0)
        by_metric[r["metric"]] += r["value"]
    assert by_metric["lineage.decoded"] == 4.0
    assert by_metric["lineage.clipped"] == 3.0  # aggregate uses n
    assert by_metric["lineage.emitted"] == 2.0

    wh = Warehouse(str(tmp_path / "wh"))
    n = wh.ingest_lineage(path)
    assert n == len(rows) + 3  # + pass_frac / absorbed_frac / decoded
    funnel_rows = wh.rows(stage="funnel")
    got = {r["metric"]: r["value"] for r in funnel_rows}
    assert got["lineage.pass_frac"] == pytest.approx(0.5)
    assert got["lineage.absorbed_frac"] == pytest.approx(0.25)
    assert got["lineage.decoded"] == 4.0


def _serve_rec(i, pass_frac, absorbed_frac, decoded=100):
    return {"kind": "serve", "utc": 1000.0 + 60.0 * i,
            "config": {"worker": "w0"},
            "metrics": {"lineage_decoded": decoded,
                        "lineage_pass_frac": pass_frac,
                        "lineage_absorbed_frac": absorbed_frac}}


def _collapse_ctx(ledger):
    return HealthContext(now=time.time(), samples=[], recent=[],
                         latest={}, queue={}, running=[],
                         ledger=list(ledger))


def test_distill_collapse_needs_baseline():
    recs = [_serve_rec(i, 0.3, 0.6) for i in range(2)]
    (f,) = rule_distill_collapse(_collapse_ctx(recs))
    assert f.severity == "ok" and "baseline" in f.message
    # funnel-free serve records don't count toward the baseline
    recs += [_serve_rec(9, 0.0, 0.0, decoded=0)] * 5
    (f,) = rule_distill_collapse(_collapse_ctx(recs))
    assert f.severity == "ok" and f.data["records"] == 2


def test_distill_collapse_bands():
    steady = [_serve_rec(i, 0.30, 0.60) for i in range(4)]
    (f,) = rule_distill_collapse(_collapse_ctx(steady))
    assert f.severity == "ok"
    shifted = steady[:-1] + [_serve_rec(9, 0.10, 0.90)]
    (f,) = rule_distill_collapse(_collapse_ctx(shifted))
    assert f.severity == "warn"
    collapsed = steady[:-1] + [_serve_rec(9, 0.005, 0.62)]
    (f,) = rule_distill_collapse(_collapse_ctx(collapsed))
    assert f.severity == "crit" and "why" in f.message


def test_funnel_anomalies_attribute_the_shift():
    steady = [_serve_rec(i, 0.30, 0.60) for i in range(5)]
    assert funnel_anomalies(steady) == []
    shifted = steady + [_serve_rec(9, 0.05, 0.95)]
    anoms = funnel_anomalies(shifted)
    metrics = {a["metric"] for a in anoms}
    assert metrics == {"lineage_pass_frac", "lineage_absorbed_frac"}
    for a in anoms:
        assert a["kind"] == "anomaly"
        assert a["key"]["stage"] == "distill"
        assert a["key"]["host"] == "w0"
    # funnel-free records alone -> nothing to judge
    assert funnel_anomalies(
        [_serve_rec(i, 0.0, 0.0, decoded=0) for i in range(6)]) == []


# -------------------------------------------------------------------------
# satellite: count_assoc == binary pre-order flatten == <nassoc>
# -------------------------------------------------------------------------

def _assoc_tree():
    """root absorbs two candidates, one of which absorbed another —
    the nested shape the distillers actually produce."""
    leaf = Candidate(dm=1.0, dm_idx=1, acc=0.5, jerk=0.25, nh=1,
                     snr=5.0, freq=200.0)
    mid = Candidate(dm=2.0, dm_idx=2, acc=1.0, jerk=-0.5, nh=2,
                    snr=7.0, freq=100.0, assoc=[leaf])
    sib = Candidate(dm=3.0, dm_idx=3, acc=-1.0, jerk=0.0, nh=1,
                    snr=6.0, freq=50.0)
    root = Candidate(dm=4.0, dm_idx=4, acc=2.0, jerk=1.5, nh=4,
                     snr=9.0, freq=25.0, assoc=[mid, sib])
    lone = Candidate(dm=5.0, dm_idx=5, acc=0.0, jerk=0.0, nh=1,
                     snr=4.0, freq=10.0)
    return [root, lone]


def test_nassoc_pins_preorder_flatten_and_xml(tmp_path):
    cands = _assoc_tree()
    root = cands[0]
    assert root.count_assoc() == 3  # mid + leaf + sib
    # count_assoc is exactly the flattened tree minus the candidate
    for c in cands:
        assert c.count_assoc() == len(c.collect()) - 1

    # binary layout: ndets per candidate == 1 + count_assoc, rows in
    # the same pre-order collect() walks, jerk column intact
    path = str(tmp_path / "candidates.peasoup")
    mapping = write_candidate_binary(cands, path)
    with CandidateFileParser(path) as parser:
        for ii, c in enumerate(cands):
            _, hits = parser.cand_from_offset(mapping[ii])
            flat = c.collect()
            assert len(hits) == 1 + c.count_assoc() == len(flat)
            for row, d in zip(hits, flat):
                assert row["dm_idx"] == d.dm_idx
                assert row["freq"] == pytest.approx(d.freq)
                assert row["jerk"] == pytest.approx(d.jerk)

    # XML: <nassoc> must agree with the binary block it points into
    w = OutputFileWriter()
    w.add_candidates(cands, mapping,
                     cand_ids=[lineage.candidate_uid("r", c)
                               for c in cands])
    tree = ET.fromstring(w.to_string())
    els = tree.findall(".//candidate")
    assert len(els) == len(cands)
    for el, c in zip(els, cands):
        assert int(float(el.findtext("nassoc"))) == c.count_assoc()
        assert el.findtext("candidate_id") == lineage.candidate_uid(
            "r", c)


# -------------------------------------------------------------------------
# the Lineage span: one per block of provenance work, timing it whole
# -------------------------------------------------------------------------

def _lineage_spans(since):
    from peasoup_tpu.obs.trace import get_tracer

    return [r for r in get_tracer().records()[since:]
            if r.name == lineage.LINEAGE_SPAN]


def test_marking_spans_a_block_and_counts_its_marks(tmp_path):
    from peasoup_tpu.obs.trace import span_cursor

    c0 = span_cursor()
    with lineage.marking():
        lineage.mark("decoded", ids=["off"])  # lineage off: no span
    assert _lineage_spans(c0) == []
    try:
        lineage.configure_lineage(str(tmp_path / "lineage.jsonl"))
        with lineage.marking():
            for i in range(3):
                lineage.mark("decoded", ids=[f"c{i}"])
    finally:
        lineage.configure_lineage("")
    (sp,) = _lineage_spans(c0)
    assert sp.attrs["marks"] == 3
    assert lineage.span_seconds(c0) == pytest.approx(sp.t_end - sp.t_start)


def _distill_cands(seed=0, n=60):
    import numpy as np

    rng = np.random.default_rng(seed)
    f0 = rng.uniform(1.0, 50.0, n // 3)
    # harmonics and near-duplicates of a few fundamentals, and strays
    freqs = np.concatenate([f0, f0 * rng.choice([0.5, 2.0, 3.0], n // 3),
                            f0 * (1 + rng.uniform(-5e-5, 5e-5, n // 3))])
    return [Candidate(dm=float(i % 7), dm_idx=i % 7,
                      acc=float(rng.choice([-5.0, 0.0, 5.0])),
                      nh=int(rng.integers(0, 5)), snr=float(s),
                      freq=float(f))
            for i, (f, s) in enumerate(zip(freqs,
                                           rng.uniform(6, 60, len(freqs))))]


def _old_walk(still, cands):
    """The numpy walk as it emitted decisions before: inside the walk,
    for each fundamental's still-unique hits."""
    import numpy as np

    cands = sorted(cands, key=lambda c: -c.snr)
    still.setup(cands)
    unique = np.ones(len(cands), dtype=bool)
    out = []
    for idx in range(len(cands)):
        if not unique[idx]:
            continue
        hit = np.nonzero(still.match_counts(idx))[0] + idx + 1
        out += [(cands[idx], cands[ii], still.pair_margin(idx, int(ii)))
                for ii in hit if unique[ii]]
        unique[hit] = False
    return out, [c for c, u in zip(cands, unique) if u]


@pytest.mark.parametrize("make", [
    lambda: distill.HarmonicDistiller(1e-4, 16, False),
    lambda: distill.AccelerationDistiller(42.0, 1e-4, False),
    lambda: distill.DMDistiller(1e-4, False),
], ids=["harmonic", "accel", "dm"])
def test_fallback_distiller_decides_as_before_and_as_native(
        monkeypatch, make):
    """The numpy walk now emits its decisions after the walk, under one
    Lineage span: the same decisions in the same order as when it
    emitted them inside the walk, the same multiset as the native
    distiller's, and the same survivors."""
    import collections

    from peasoup_tpu.obs.trace import span_cursor

    def key(d):
        f, a, m = d
        return (f.freq, a.freq, round(m, 9))

    def run(native_on):
        got = []
        with monkeypatch.context() as mp:
            if not native_on:
                mp.setattr(distill, "_native_lib", lambda: None)
            out = make().distill(_distill_cands(),
                                 on_decision=lambda *d: got.append(d))
        return got, out

    want, want_out = _old_walk(make(), _distill_cands())
    assert want  # the candidates do absorb one another
    c0 = span_cursor()
    got, out = run(False)
    assert _lineage_spans(c0) == []  # lineage off: no span
    assert [key(d) for d in got] == [key(d) for d in want]
    assert [c.freq for c in out] == [c.freq for c in want_out]
    if distill._native_lib() is not None:
        nat, nat_out = run(True)
        assert (collections.Counter(map(key, nat))
                == collections.Counter(map(key, want)))
        assert [c.freq for c in nat_out] == [c.freq for c in want_out]


def test_drain_lineage_overhead_is_its_lineage_spans(tmp_path):
    """The drain summary's ``lineage.overhead_s`` is read from the
    Lineage spans the drain closed: ids and margins included."""
    import warnings

    from peasoup_tpu.obs.injection import smoke_observation
    from peasoup_tpu.obs.trace import span_cursor
    from peasoup_tpu.serve import BackoffPolicy, JobSpool, SurveyWorker

    fil = str(tmp_path / "obs.fil")
    smoke_observation(fil, nsamps=4096, nchans=16, seed=0)
    spool = JobSpool(str(tmp_path / "jobs"))
    spool.submit(fil, {"dm_end": 20.0, "min_snr": 6.0, "npdmp": 2,
                       "limit": 10})
    worker = SurveyWorker(
        spool, single_device=True,
        backoff=BackoffPolicy(max_attempts=2, base_s=0.0),
        history_path=str(tmp_path / "history.jsonl"),
        sleeper=lambda s: None, lineage=True)
    c0 = span_cursor()
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            summary = worker.drain()
    finally:
        lineage.configure_lineage("")
    spans = _lineage_spans(c0)
    assert summary["succeeded"] == 1 and spans
    assert summary["lineage"]["overhead_s"] == round(
        sum(r.t_end - r.t_start for r in spans), 6)
    marks = lineage.read_lineage(str(tmp_path / "jobs" / "lineage.jsonl"))
    assert sum(r.attrs["marks"] for r in spans) == len(marks)


# -------------------------------------------------------------------------
# the writer: one write and one size check per Lineage block
# -------------------------------------------------------------------------

class _CountingFile:
    """A file handle that counts its ``write`` calls (and can fail
    them)."""

    def __init__(self, f, calls, fail):
        self._f, self._calls, self._fail = f, calls, fail

    def write(self, text):
        self._calls["writes"] += 1
        if self._fail:
            raise OSError("disk full")
        return self._f.write(text)

    def __getattr__(self, name):
        return getattr(self._f, name)


def _count_io(monkeypatch, fail_write=False):
    """Count the recorder's ``os.path.getsize`` calls and its writes to
    the files it opens for appending."""
    calls = {"getsize": 0, "writes": 0}
    getsize = os.path.getsize

    def counting_getsize(p):
        calls["getsize"] += 1
        return getsize(p)

    def counting_open(path, mode="r", *a, **k):
        f = open(path, mode, *a, **k)
        return _CountingFile(f, calls, fail_write) if "a" in mode else f

    monkeypatch.setattr(os.path, "getsize", counting_getsize)
    monkeypatch.setattr(lineage, "open", counting_open, raising=False)
    return calls


def _some_marks(n):
    """``n`` marks of mixed kinds, with None fields and a run override."""
    kinds = [("decoded", {"ids": ["a", "b"], "n": 2, "stage": None}),
             ("absorbed", {"id": "a", "absorber": "b", "rule": "harmonic",
                           "margin": 1.5e-5, "run": "other"}),
             ("emitted", {"id": "b", "rank": 0, "snr": None})]
    return [(kinds[i % 3][0], dict(kinds[i % 3][1], seq=i))
            for i in range(n)]


@pytest.mark.parametrize("n", [1, 5, 300])
@pytest.mark.parametrize("buffered", [True, False],
                         ids=["block", "direct"])
def test_block_makes_one_write_and_one_size_check(tmp_path, monkeypatch,
                                                  n, buffered):
    calls = _count_io(monkeypatch)
    path = str(tmp_path / "lineage.jsonl")
    rec = lineage.LineageRecorder(path, run="r")
    if buffered:
        with rec.buffered():
            for kind, fields in _some_marks(n):
                rec.mark(kind, **fields)
            assert calls == {"getsize": 0, "writes": 0}
            assert not os.path.exists(path)  # nothing before the exit
    else:
        for kind, fields in _some_marks(n):
            rec.mark(kind, **fields)
    rec.close()
    want = 1 if buffered else n
    assert calls == {"getsize": want, "writes": want}
    assert len(lineage.read_lineage(path)) == n


def _masked(path):
    """The file's marks, ``ts`` masked, fields in written order."""
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    assert all(isinstance(r.pop("ts"), float) for r in recs)
    return [list(r.items()) for r in recs]


def test_block_writes_the_file_the_marks_write_one_by_one(tmp_path):
    marks = _some_marks(30)
    paths = {}
    for how in ("block", "direct"):
        paths[how] = str(tmp_path / how / "lineage.jsonl")
        rec = lineage.LineageRecorder(paths[how], run="r")
        if how == "block":
            with rec.buffered():
                for kind, fields in marks:
                    rec.mark(kind, **fields)
        else:
            for kind, fields in marks:
                rec.mark(kind, **fields)
        rec.close()
    assert _masked(paths["block"]) == _masked(paths["direct"])
    assert len(_masked(paths["block"])) == 30


def test_nested_blocks_write_once_at_the_outermost_exit(tmp_path,
                                                       monkeypatch):
    from peasoup_tpu.obs.trace import span_cursor

    calls = _count_io(monkeypatch)
    path = str(tmp_path / "lineage.jsonl")
    c0 = span_cursor()
    try:
        lineage.configure_lineage(path, run="r")
        with lineage.marking():
            lineage.mark("decoded", ids=["a"])
            with lineage.marking():
                lineage.mark("decoded", ids=["b"])
            assert calls["writes"] == 0
            lineage.mark("decoded", ids=["c"])
    finally:
        lineage.configure_lineage("")
    assert calls == {"getsize": 1, "writes": 1}
    assert [m["ids"][0] for m in lineage.read_lineage(path)] == ["a", "b",
                                                                "c"]
    inner, outer = _lineage_spans(c0)  # records close inner first
    assert (inner.attrs["marks"], inner.attrs["writes"]) == (0, 0)
    assert (outer.attrs["marks"], outer.attrs["writes"]) == (3, 1)


def test_exception_in_a_block_still_writes_its_marks(tmp_path):
    path = str(tmp_path / "lineage.jsonl")
    rec = lineage.LineageRecorder(path, run="r")
    with pytest.raises(RuntimeError, match="boom"):
        with rec.buffered():
            rec.mark("decoded", ids=["a"])
            rec.mark("cut", id="a", stage="limit")
            raise RuntimeError("boom")
    rec.close()
    assert [m["kind"] for m in lineage.read_lineage(path)] == ["decoded",
                                                               "cut"]


def test_threads_blocks_land_whole_and_in_order(tmp_path):
    """More threads than cores, each in its own block, all blocks open
    at once and switching often: every mark lands, each thread's marks
    in order and contiguous, one write a block."""
    import sys

    from peasoup_tpu.obs.metrics import REGISTRY

    path = str(tmp_path / "lineage.jsonl")
    rec = lineage.LineageRecorder(path, run="r")
    names = [f"t{k:02d}." for k in range((os.cpu_count() or 1) + 4)]
    all_open = threading.Barrier(len(names))
    marks0 = REGISTRY.counter("lineage.marks")
    writes0 = REGISTRY.counter("lineage.writes")

    def work(t):
        with rec.buffered():
            rec.mark("decoded", ids=[f"{t}0"])
            all_open.wait(timeout=30)  # every block overlaps every other
            for i in range(1, 200):
                rec.mark("decoded", ids=[f"{t}{i}"])

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ts = [threading.Thread(target=work, args=(t,)) for t in names]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in ts)
    rec.close()
    got = [m["ids"][0] for m in lineage.read_lineage(path)]
    assert len(got) == 200 * len(names)
    for t in names:
        at = [k for k, g in enumerate(got) if g.startswith(t)]
        assert at == list(range(at[0], at[0] + 200))  # contiguous
        assert [got[k] for k in at] == [f"{t}{i}" for i in range(200)]
    assert REGISTRY.counter("lineage.marks") - marks0 == len(got)
    assert REGISTRY.counter("lineage.writes") - writes0 == len(names)


@pytest.mark.parametrize("fail", ["open", "write"])
def test_failed_block_write_latches_counts_and_never_raises(
        tmp_path, monkeypatch, fail):
    from peasoup_tpu.obs.metrics import REGISTRY

    if fail == "open":
        blocker = tmp_path / "dir"
        blocker.write_text("a file where the ledger dir should be")
        path = str(blocker / "lineage.jsonl")
    else:
        _count_io(monkeypatch, fail_write=True)
        path = str(tmp_path / "lineage.jsonl")
    rec = lineage.LineageRecorder(path, run="r")
    before = lineage.overhead()
    marks0 = REGISTRY.counter("lineage.marks")
    errors0 = REGISTRY.counter("lineage.mark_errors")
    with rec.buffered():  # must not raise
        for i in range(4):
            rec.mark("decoded", ids=[f"c{i}"])
    after = lineage.overhead()
    assert after["errors"] - before["errors"] == 4
    assert after["marks"] - before["marks"] == 4
    assert REGISTRY.counter("lineage.mark_errors") - errors0 == 4
    assert REGISTRY.counter("lineage.marks") == marks0
    with rec.buffered():  # latched: dropped, not retried
        rec.mark("decoded", ids=["late"])
    assert lineage.overhead()["errors"] == after["errors"]
    rec.close()


@pytest.mark.parametrize("n,writes", [(0, 0), (1, 1), (7, 1)])
def test_marking_span_counts_the_blocks_marks_and_writes(tmp_path, n,
                                                         writes):
    from peasoup_tpu.obs.trace import span_cursor

    c0 = span_cursor()
    path = str(tmp_path / "lineage.jsonl")
    try:
        lineage.configure_lineage(path, run="r")
        with lineage.marking():
            for kind, fields in _some_marks(n):
                lineage.mark(kind, **fields)
    finally:
        lineage.configure_lineage("")
    (sp,) = _lineage_spans(c0)
    assert (sp.attrs["marks"], sp.attrs["writes"]) == (n, writes)
    assert len(lineage.read_lineage(path)) == n
