"""The reference's peak stage on the device, at test size on the CPU:
harmonic levels bit for bit against plain numpy, every hit through any
capacity, the same table with its DM rows on one device and on four,
and a four-chip 2-bit cell through the harness, sound and with the
exchange between chips left out."""

from __future__ import annotations

import json
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import reference, run


def numpy_levels(spec: np.ndarray, nh: int, dtype) -> list:
    """The oracle: levels ``0..nh`` of the incremental harmonic sum.
    Level ``k`` adds the spectrum at ``(i*m + 2^(k-1)) >> k`` for the
    odd ``m < 2^k`` to level ``k - 1``'s unscaled sum, and is scaled by
    ``2^(-k/2)``."""
    size = spec.shape[0]
    spec = spec.astype(dtype)
    i = np.arange(size, dtype=np.int64)
    val = spec.copy()
    out = [spec]
    for k in range(1, nh + 1):
        for m in range(1, 1 << k, 2):
            val = (val + spec[np.minimum((i * m + (1 << (k - 1))) >> k,
                                         size - 1)]).astype(dtype)
        out.append((val * dtype(2.0 ** (-k / 2))).astype(dtype))
    return out


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("nh", range(5))
def test_device_levels_are_the_numpy_levels_bit_for_bit(nh, dtype):
    npdt = np.dtype(dtype).type
    levels = jax.jit(partial(reference.harmonic_levels, nh=nh, dtype=dtype))
    rng = np.random.default_rng(nh)
    # a search's spectrum length (2^k + 1), one that no row count
    # divides, and one shorter than a row of 128
    for size in (4097, 5000, 33):
        spec = (3.0 * rng.standard_normal(size)).astype(np.float32)
        got = np.asarray(levels(spec)).astype(np.float32)
        want = numpy_levels(spec, nh, npdt)
        assert got.shape == (nh + 1, size)
        for k in range(nh + 1):
            w = want[k].astype(np.float32)
            assert (got[k].view(np.uint32) == w.view(np.uint32)).all(), (size, k)


@pytest.fixture(scope="module")
def beam(tmp_path_factory):
    """The first cell of a test-size root and one of its beams."""
    from conftest import make_root

    root = make_root(tmp_path_factory.mktemp("dev") / "root")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    spec = run.load_cell(cell, root)
    prep = run.prepare(spec, 2147483999, os.path.join(root, "w"))
    return spec, prep, prep["beams"][0]["payload"]


def _reference(beam, **kw):
    spec, prep, payload = beam
    return reference.Reference(prep["obs"], payload, float(prep["dms"].max()),
                               birdies=spec["config"].get("birdies", ()), **kw)


def test_capacity_of_one_returns_every_hit(beam):
    _, prep, _ = beam
    want = _reference(beam).search(prep["dms"], prep["search"])
    ref = _reference(beam, capacity=1)
    got = ref.search(prep["dms"], prep["search"])
    assert len(want) >= 2
    assert got == want


def test_table_is_the_same_on_one_and_on_four_devices(beam):
    _, prep, _ = beam
    devices = jax.devices()[:4]
    want = _reference(beam).search(prep["dms"], prep["search"])
    ref = _reference(beam, devices=devices)
    got = ref.search(prep["dms"], prep["search"])
    # every device searched DM rows: row i on device i mod 4
    assert {key[3] for key in ref._white} == set(devices)
    assert got == want


def _drop_other_chips(monkeypatch):
    """The exchange between chips left out: the decoder keeps the
    first chip's DM rows and drops the peaks of the others."""
    from peasoup_tpu.parallel import mesh

    real = mesh.MeshPulsarSearch._decode_packed

    def broken(self, packed, ndm_local, *a, **kw):
        groups, *rest = real(self, packed, ndm_local, *a, **kw)
        return ({d: g for d, g in groups.items() if d < ndm_local}, *rest)

    monkeypatch.setattr(mesh.MeshPulsarSearch, "_decode_packed", broken)


@pytest.mark.parametrize("fault", [None, _drop_other_chips],
                         ids=["sound", "no_exchange"])
def test_four_chip_two_bit_cell(tiny_root, capsys, monkeypatch, fault):
    """A 2-bit cell on four chips, added as files and entries: its
    tables agree with the reference's, whose rows are spread over the
    same four devices; without the exchange between chips they do
    not."""
    from conftest import TWO_BIT, add_cell

    add_cell(tiny_root, "twobit", TWO_BIT, {
        "accel": True, "pulsar": {"period_s": [0.05, 0.06], "dm": [40.0, 90.0],
                                  "accel_ms2": [-20.0, 20.0], "duty": 0.05,
                                  "snr": 50.0}}, chips=4)
    if fault:
        fault(monkeypatch)
    rc = run.main(["--workload", "twobit.cell", "--seed", "3000000019",
                   "--seconds", "1"], require_tpu=False, root=tiny_root)
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["device"]["count"] == 4 and res["failed"] == 0
    if fault is None:
        assert res["correct"] is True, res["check"]
        assert res["check"]["table_gap"]["value"] == 0.0
    else:
        assert res["correct"] is False
        assert res["check"]["table_gap"]["value"] > res["check"]["table_gap"]["limit"]


def test_config_without_test_size_is_named(tmp_path):
    from conftest import TWO_BIT, at_test_size

    path = tmp_path / "nosize.json"
    path.write_text(json.dumps(TWO_BIT))
    with pytest.raises(ValueError, match="nosize.json"):
        at_test_size(str(path))
