"""``driver.upload_wait_s``: the seconds per observation of the chunked
driver's ``Upload-Wait`` spans, on traces the test writes."""

from __future__ import annotations

import pytest
from test_perfbench_spans import _span, _trace

from perfbench import run


def _obs(tmp_path, k, waits_us):
    ss = [_span("Chunked-Search-0", 0, 10_000_000, 1)]
    t = 100
    for i, w in enumerate(waits_us):
        ss.append(_span("Upload-Wait", t, t + w, 2 + i, parent=1))
        t += w + 100
    out = str(tmp_path / f"obs{k}")
    _trace(out, ss)
    return {"outdir": out, "wall_s": 10.0}


@pytest.mark.parametrize("beams,want", [
    ([[4_000_000], [2_000_000]], 3.0),
    ([[1_000_000, 500_000], [500_000]], (1.5 + 0.5) / 2),
    ([[3_000_000], []], 3.0),  # a beam with no such span is left out
    ([[], []], None),  # the parent's trace: no span
])
def test_upload_wait_reader_sums_the_spans_per_observation(tmp_path, beams,
                                                           want):
    obs = [_obs(tmp_path, k, w) for k, w in enumerate(beams)]
    got = run.read_metric("driver.upload_wait_s", run.Run(obs=obs))
    assert got == (None if want is None else pytest.approx(want))


def test_upload_wait_reader_reads_nothing_without_a_trace(tmp_path):
    assert run.read_metric("driver.upload_wait_s", run.Run(
        obs=[{"outdir": str(tmp_path / "none"), "wall_s": 1.0}])) is None
