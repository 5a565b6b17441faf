"""The HTRU-South hilat cell, ``htru_hilat.accel.4chip``, at its test
size on four virtual devices through the chunked driver: ``correct``
with its per-layer metrics, not correct with a planted fault, and the
chip-0 dedispersion roofline reader on a synthetic four-chip run."""

from __future__ import annotations

import json
import math

import pytest

from perfbench import run, work

CELL = "htru_hilat.accel.4chip"
SEED = 2700000123
METRICS = {"driver.prep_transpose_s", "driver.prep_upload_s",
           "driver.chunk_fetch_s", "driver.chunked_compiles_per_obs"}


@pytest.fixture
def chunked(monkeypatch):
    """The chunked driver at test size.  At full size the cell's beam
    is planned onto it by the HBM budget; a CPU-sized beam fits the
    fused path, so the CLI's search is given DM chunks of two rows."""
    from peasoup_tpu import cli

    real = cli.args_to_config

    def with_chunks(args):
        cfg = real(args)
        cfg.dm_chunk = 2
        return cfg

    monkeypatch.setattr(cli, "args_to_config", with_chunks)


def _result(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _main(root, *extra):
    return run.main(["--workload", CELL, "--seed", str(SEED),
                     "--seconds", "1", *extra],
                    require_tpu=False, root=root)


def test_htru_cell_is_correct_with_its_metrics(tiny_root, capsys, chunked):
    assert _main(tiny_root, "--trace", "1") == 0
    res = _result(capsys)
    assert res["correct"] is True, res["check"]
    assert res["device"]["count"] == 4 and res["failed"] == 0
    assert res["check"]["table_gap"]["value"] == 0.0
    # the roofline reads the chip's trace: nothing off the chip
    assert set(res["metrics"]) == METRICS
    assert res["metrics"]["driver.chunked_compiles_per_obs"]["value"] == 0.0
    assert res["metrics"]["driver.prep_transpose_s"]["value"] > 0


def _drop_chip1(monkeypatch):
    """The gather left out for the second chip: the decoder drops the
    peaks of its DM rows, which hold the pulsar."""
    from peasoup_tpu.parallel import mesh

    real = mesh.MeshPulsarSearch._decode_packed

    def broken(self, packed, ndm_local, *a, **kw):
        groups, *rest = real(self, packed, ndm_local, *a, **kw)
        return ({d: g for d, g in groups.items() if d // ndm_local != 1},
                *rest)

    monkeypatch.setattr(mesh.MeshPulsarSearch, "_decode_packed", broken)


def _scale_snr(monkeypatch):
    from peasoup_tpu.parallel import mesh

    real = mesh.MeshPulsarSearch.run

    def broken(self):
        result = real(self)
        result.candidates[0].snr *= 1.001
        return result

    monkeypatch.setattr(mesh.MeshPulsarSearch, "run", broken)


@pytest.mark.parametrize("fault,number", [(_drop_chip1, "missed"),
                                          (_scale_snr, "snr_gap")],
                         ids=["chip1_dropped", "snr_scaled"])
def test_htru_cell_with_a_fault_is_not_correct(tiny_root, capsys, chunked,
                                               monkeypatch, fault, number):
    fault(monkeypatch)
    assert _main(tiny_root) == 0
    res = _result(capsys)
    assert res["correct"] is False
    c = res["check"][number]
    assert c["value"] > c["limit"]


def _read(name: str, r):
    return run.read_metric(name, r, run.ROOT)


def test_survey_roofline_reads_chip_zero_quarter(tmp_path):
    """Four device planes, each with its quarter of the DM rows' kernel
    time: the reader holds chip 0's time against chip 0's 32 of the 128
    rows, and a four-times-whole-grid reading would be impossible."""
    obs = dict(nsamps=(1 << 22) + (1 << 14), nchans=1024, nbits=2)
    ndm, n = 128, work.fft_size(obs["nsamps"])
    out = tmp_path / "obs0"
    out.mkdir()
    (out / "run_report.json").write_text(json.dumps(
        {"n_dm_trials": ndm, "gauges": {"search.fft_size": n}}))
    peak = {"flops": 197e12, "hbm_bytes_per_s": 819e9}
    rows = math.ceil(ndm / 4)
    fb = obs["nsamps"] * obs["nchans"] * obs["nbits"] / 8
    # chip 0 at 60% of its memory roofline
    secs = (fb + 4.0 * rows * n) / peak["hbm_bytes_per_s"] / 0.6
    devices = {f"/device:TPU:{k}": [
        ("dedisperse_pallas_flat.3", 0, int(secs * 1e9)),
        ("fusion.1", int(secs * 1e9), int(2 * secs * 1e9))]
        for k in range(4)}
    r = run.Run(obs=[{"outdir": str(out)}], trace={"devices": devices,
                                                     "host": []},
                t0_ns=0, t1_ns=10**12, peak=peak,
                work=work.counts(obs, ndm, 0))
    want, bound = work.roofline_share(
        float(rows) * obs["nchans"] * n, fb + 4.0 * rows * n, secs, peak)
    got = _read("kernel.survey_dedisp_roofline", r)
    assert bound == "memory" and got == pytest.approx(want)
    assert got == pytest.approx(60.0)
    whole, _ = work.roofline_share(r.work["dedisp_ops"],
                                   r.work["dedisp_bytes"], secs, peak)
    assert 0 < got < 100 < whole
    # off the chip, or without the program's report, it reads nothing
    assert _read("kernel.survey_dedisp_roofline",
                 run.Run(**dict(r.__dict__, peak=None))) is None
    (out / "run_report.json").write_text("{}")
    assert _read("kernel.survey_dedisp_roofline", r) is None


@pytest.mark.parametrize("compiles", [[], [0, 0], [2, 1, 0]],
                         ids=["no_obs", "none", "some"])
def test_chunked_compiles_reads_as_compiles_per_obs(compiles):
    """The chunked cell's compile count is ``driver.compiles_per_obs``'s
    reading, by that reader."""
    r = run.Run(obs=[{"compiles": c} for c in compiles])
    want = (sum(compiles) / len(compiles)) if compiles else None
    assert _read("driver.compiles_per_obs", r) == want
    assert _read("driver.chunked_compiles_per_obs", r) == want
