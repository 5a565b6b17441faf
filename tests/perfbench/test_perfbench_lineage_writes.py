"""``driver.lineage_writes``: the writes of ``lineage.jsonl`` per
observation, read from the ``writes`` attribute of the ``Lineage``
spans; one write per block of marks at test size."""

from __future__ import annotations

import pytest
from test_perfbench_spans import _cli, _marks, _span, _trace

from perfbench import run, spans


def test_cli_run_writes_each_lineage_block_once(tiny_root):
    out = _cli(tiny_root, "on")
    lin = [s for s in spans.closed(out) if s["name"] == "Lineage"]
    assert lin and all(s["args"]["writes"] == 1 for s in lin)
    got = run.read_metric("driver.lineage_writes", run.Run(obs=[
        {"outdir": out, "wall_s": 1.0}]), root=tiny_root)
    assert got == len(lin) < len(_marks(out))


def _obs(tmp_path, k, writes):
    ss = [_span("Distill", 0, 500_000, 1, stage="dm_rows")]
    for i, w in enumerate(writes):
        attrs = {"marks": 10} if w is None else {"marks": 10, "writes": w}
        ss.append(_span("Lineage", 1000 * i, 1000 * i + 500, 2 + i,
                        parent=1, **attrs))
    out = str(tmp_path / f"obs{k}")
    _trace(out, ss)
    return {"outdir": out, "wall_s": 1.0}


@pytest.mark.parametrize("beams,want", [
    ([[1, 1, 1], [1, 0]], (3 + 1) / 2),
    ([[1, 1], []], 2.0),  # a beam with no block is left out
    ([[None, None], [None]], None),  # the parent's spans: no attribute
    ([[], []], None),
])
def test_writes_reader_sums_the_attribute_per_observation(tmp_path, beams,
                                                          want):
    obs = [_obs(tmp_path, k, w) for k, w in enumerate(beams)]
    got = run.read_metric("driver.lineage_writes", run.Run(obs=obs))
    assert got == (None if want is None else pytest.approx(want))
