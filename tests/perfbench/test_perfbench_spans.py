"""The beam's host tail in the CLI's ``trace.json`` at test size: a
``Lineage`` span inside each ``Distill`` and no CLI phase outside a
span; and the four readers of those spans on traces the test writes."""

from __future__ import annotations

import collections
import json
import os

import pytest

from perfbench import run, spans
from perfbench.check import read_candidates

SEED = 3400000001


def _cli(root: str, out: str, *extra: str) -> str:
    """One CLI call on the tutorial cell's first beam at test size."""
    from peasoup_tpu import cli

    spec = run.load_cell("tutorial.accel", root)
    prep = run.prepare(spec, SEED, os.path.join(root, "beams"))
    outdir = os.path.join(root, out)
    assert cli.main(["-i", prep["beams"][0]["fil"], "-o", outdir,
                     *prep["args"], "-t", "1", *extra]) == 0
    return outdir


def _by_id(ss):
    return {s["args"]["span_id"]: s for s in ss}


def _marks(outdir: str) -> list[dict]:
    with open(os.path.join(outdir, "lineage.jsonl")) as f:
        return [json.loads(line) for line in f]


def test_cli_run_traces_the_host_tail(tiny_root):
    out = _cli(tiny_root, "on")
    ss = spans.closed(out)
    by_id = _by_id(ss)
    distill = {s["args"]["stage"]: s for s in ss if s["name"] == "Distill"}
    assert set(distill) == {"dm_rows", "cross_dm"}
    assert isinstance(distill["dm_rows"]["args"]["native"], bool)
    lin = [s for s in ss if s["name"] == "Lineage"]
    parents = {by_id[s["args"]["parent_id"]]["args"].get("stage")
               for s in lin if "parent_id" in s["args"]}
    assert {"dm_rows", "cross_dm"} <= parents
    # every mark the run wrote is counted by exactly one Lineage span
    assert sum(s["args"]["marks"] for s in lin) == len(_marks(out))
    assert all(s["args"]["marks"] > 0 for s in lin)
    roots = {s["name"] for s in ss if "parent_id" not in s["args"]}
    assert {"Read", "Search-Setup", "Score", "Output"} <= roots
    score = next(s for s in ss if s["name"] == "Score")
    assert any(by_id.get(s["args"].get("parent_id")) is score for s in lin)
    if distill["dm_rows"]["args"]["native"]:
        # one span per block, never one per mark
        assert len(lin) <= 12


def test_no_lineage_opens_no_lineage_span(tiny_root):
    on, off = _cli(tiny_root, "on"), _cli(tiny_root, "off", "--no_lineage")
    assert not any(s["name"] == "Lineage" for s in spans.closed(off))
    assert not os.path.exists(os.path.join(off, "lineage.jsonl"))
    assert (read_candidates(os.path.join(on, "overview.xml"))
            == read_candidates(os.path.join(off, "overview.xml")))


def _absorbed(outdir: str) -> collections.Counter:
    return collections.Counter(
        (m["id"], m["absorber"], m["rule"], m["margin"])
        for m in _marks(outdir) if m["kind"] == "absorbed")


def test_fallback_distiller_writes_the_native_absorptions(tiny_root,
                                                          monkeypatch):
    """The numpy distillers (native helper off) absorb the same
    candidates into the same absorbers, with the same margins, and
    keep the same survivors as the native ones."""
    import peasoup_tpu.native

    if peasoup_tpu.native.lib is None:
        pytest.skip("the native helper is not built here")
    native = _cli(tiny_root, "native")
    monkeypatch.setattr(peasoup_tpu.native, "lib", None)
    fallback = _cli(tiny_root, "fallback")
    assert _absorbed(native) and _absorbed(native) == _absorbed(fallback)
    assert (read_candidates(os.path.join(native, "overview.xml"))
            == read_candidates(os.path.join(fallback, "overview.xml")))
    dm_rows = [s for s in spans.closed(fallback) if s["name"] == "Distill"
               and s["args"]["stage"] == "dm_rows"]
    assert [s["args"]["native"] for s in dm_rows] == [False]


# -- the readers, on traces written here ---------------------------------

def _span(name, t0, t1, span_id, parent=None, **attrs):
    args = {"span_id": span_id, **attrs}
    if parent is not None:
        args["parent_id"] = parent
    return name, t0, t1, args


def _trace(outdir: str, ss: list[tuple]) -> None:
    """Write ``trace.json`` holding the nested spans ``ss`` (name,
    start and end in microseconds, args) as the CLI does: B/E pairs,
    children's inside their parent's."""
    os.makedirs(outdir)
    events, stack = [], []

    def end(name, ts):
        events.append({"name": name, "ph": "E", "ts": ts, "pid": 0,
                       "tid": 0})

    for name, t0, t1, args in sorted(ss, key=lambda x: x[1]):
        while stack and stack[-1][1] <= t0:
            end(*stack.pop())
        events.append({"name": name, "ph": "B", "ts": t0, "pid": 0,
                       "tid": 0, "args": args})
        stack.append((name, t1))
    while stack:
        end(*stack.pop())
    with open(os.path.join(outdir, "trace.json"), "w") as f:
        json.dump({"traceEvents": events}, f)


def _beam(tmp_path, k, lineage_us, marks, output_us, wall_s):
    """A beam whose root spans are Read (0.1 s), Distill (0.5 s, holding
    Lineage spans of ``lineage_us`` microseconds with ``marks``) and
    Output (``output_us``)."""
    ss = [_span("Read", 0, 100_000, 1),
          _span("Distill", 100_000, 600_000, 2, stage="dm_rows")]
    t = 100_000
    for i, (us, m) in enumerate(zip(lineage_us, marks)):
        ss.append(_span("Lineage", t, t + us, 3 + i, parent=2, marks=m))
        t += us
    ss.append(_span("Output", 600_000, 600_000 + output_us, 9))
    out = str(tmp_path / f"obs{k}")
    _trace(out, ss)
    return {"outdir": out, "wall_s": wall_s}


@pytest.mark.parametrize("metric,want", [
    ("driver.lineage_s", (0.3 + 0.1) / 2),
    ("driver.lineage_marks", (30 + 10) / 2),
    ("cli.output_s", (0.02 + 0.04) / 2),
    ("cli.unspanned_s", ((1.0 - 0.62) + (0.8 - 0.64)) / 2),
])
def test_span_readers_on_written_traces(tmp_path, metric, want):
    obs = [_beam(tmp_path, 0, [100_000, 200_000], [10, 20], 20_000, 1.0),
           _beam(tmp_path, 1, [100_000], [10], 40_000, 0.8)]
    got = run.read_metric(metric, run.Run(obs=obs))
    assert got == pytest.approx(want)


@pytest.mark.parametrize("metric", ["driver.lineage_s",
                                    "driver.lineage_marks", "cli.output_s"])
def test_span_readers_read_nothing_without_the_spans(tmp_path, metric):
    """A program without the new spans (the parent's) gives nothing,
    and a beam without a trace is left out."""
    out = str(tmp_path / "obs0")
    _trace(out, [_span("Distill", 0, 10, 1, stage="dm_rows")])
    obs = [{"outdir": out, "wall_s": 1.0},
           {"outdir": str(tmp_path / "none"), "wall_s": 1.0}]
    assert run.read_metric(metric, run.Run(obs=obs)) is None
    unspanned = run.read_metric("cli.unspanned_s", run.Run(obs=obs))
    assert unspanned == pytest.approx(1.0 - 10e-6)
