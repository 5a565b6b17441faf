"""The chunked driver at a 2-bit survey-like test size on four virtual
devices: the host-prep spans in the CLI's ``trace.json``, the flat
filterbank parts sent as shards and gathered on the devices, no compile
in a second search of one geometry, and what a search keeps loaded for
the next: the chunk program only where the HBM reckoning fits the fold
beside its arena (which the fold's budget then leaves out), released
before any other chunk program or the fused path loads."""

from __future__ import annotations

import os

import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from peasoup_tpu.io import read_filterbank
from peasoup_tpu.obs.injection import synthesize
from peasoup_tpu.obs.metrics import REGISTRY
from peasoup_tpu.obs.trace import get_tracer
from peasoup_tpu.parallel import mesh
from peasoup_tpu.search import pipeline
from peasoup_tpu.search.plan import SearchConfig

#: HTRU-South hilat's band, 64 us and 2-bit samples, cut to 64 channels
#: of 16x the width and 2^16 + 2^10 samples
OBS = dict(nsamps=(1 << 16) + (1 << 10), nchans=64, tsamp=6.4e-05,
           fch1=1552.0 - 0.390625 * 8, foff=-0.390625 * 16, nbits=2,
           noise_max=4)
DMS = np.linspace(0.0, 293.96, 8).astype(np.float32)
#: the pulsar on DM row 3: the second device's rows
PULSAR = dict(period=0.0437, dm=float(DMS[3]), accel=20.0, duty=0.05,
              snr=60.0)
SEARCH = dict(acc_start=-50.0, acc_end=50.0, acc_step=25.0, nharmonics=4,
              npdmp=10, limit=1000)
#: two seeds whose beams give different candidate counts
SEEDS = (1, 3)


@pytest.fixture(autouse=True)
def _nothing_kept():
    """Each test starts and ends with no program kept loaded."""
    mesh.release_kept_programs()
    yield
    mesh.release_kept_programs()


def _beam(tmp_path, seed: int, nsamps: int = OBS["nsamps"]) -> str:
    path = str(tmp_path / f"beam{seed}_{nsamps}.fil")
    if not os.path.exists(path):
        synthesize(path, seed=seed, **dict(OBS, nsamps=nsamps), **PULSAR)
    return path


def _compiles() -> int:
    return int(REGISTRY.snapshot()["counters"].get("jit.backend_compiles", 0))


def _search(path: str, ndev: int = 4, **over) -> mesh.MeshPulsarSearch:
    """A chunked search on ``ndev`` devices: a CPU-sized beam fits the
    fused path, so the DM chunks are given (two rows a device)."""
    cfg = SearchConfig(dm_list=list(DMS), dm_chunk=2,
                       **dict(SEARCH, **over))
    return mesh.MeshPulsarSearch(read_filterbank(path), cfg,
                                 mesh=mesh.make_mesh(ndev))


@pytest.fixture
def cli_chunks(monkeypatch):
    """The CLI's search given DM chunks of two rows."""
    from peasoup_tpu import cli

    real = cli.args_to_config

    def with_chunks(args):
        cfg = real(args)
        cfg.dm_chunk = 2
        return cfg

    monkeypatch.setattr(cli, "args_to_config", with_chunks)


def _cli(tmp_path, fil: str, out: str) -> tuple[str, int]:
    """One CLI search on four devices: its output directory and how
    many programs it compiled."""
    import chip_smoke

    dm_file = tmp_path / "dms.txt"
    np.savetxt(dm_file, DMS, fmt="%.6f")
    outdir = str(tmp_path / out)
    args = ["--dm_file", str(dm_file), "--acc_start", "-50", "--acc_end",
            "50", "--acc_step", "25", "-n", "4", "--npdmp", "10",
            "--limit", "1000", "-t", "4"]
    run = chip_smoke.run_cli(dict(fil=fil, args=args), outdir)
    return outdir, run["compiles"]


def test_chunked_prep_spans_nest_with_bytes(tmp_path, cli_chunks):
    """``Chunk-Prep`` is a root span holding ``Prep-Transpose`` and
    ``Prep-Upload``, each with the bytes it laid out or sent; the
    upload hands the parts to the host link once, not once a device,
    and the first chunk's wait for it is an ``Upload-Wait`` span."""
    from perfbench import spans

    outdir, _ = _cli(tmp_path, _beam(tmp_path, SEEDS[0]), "obs")
    ss = spans.closed(outdir)
    by_id = {s["args"]["span_id"]: s for s in ss}
    (prep,) = [s for s in ss if s["name"] == "Chunk-Prep"]
    assert "parent_id" not in prep["args"]
    # the channel-major filterbank, at least, is laid out and uploaded
    least = OBS["nchans"] * OBS["nsamps"]
    for name in ("Prep-Transpose", "Prep-Upload"):
        (child,) = [s for s in ss if s["name"] == name]
        assert by_id[child["args"]["parent_id"]] is prep
        assert child["args"]["bytes"] >= least
    (up,) = [s for s in ss if s["name"] == "Prep-Upload"]
    assert up["args"]["gathered"] >= 1
    assert up["args"]["bytes"] <= up["args"]["link_bytes"] < 4 * least
    (wait,) = [s for s in ss if s["name"] == "Upload-Wait"]
    assert wait["s"] >= 0


@pytest.mark.parametrize("ndev", [4, 1])
def test_flat_parts_land_replicated_byte_for_byte(tmp_path, monkeypatch,
                                                   ndev):
    """After the chunked prep every flat part is replicated over the
    mesh and holds the host part byte for byte.  On four devices each
    part crosses the host link once, as shards gathered on the devices:
    ``Prep-Upload``'s ``link_bytes`` counts the parts once and the
    tables once a device.  One device gathers nothing and takes no
    gather program."""
    host = []
    real = mesh.split_flat_channels

    def spy(*a, **kw):
        out = real(*a, **kw)
        host.extend(np.array(p) for p in out)
        return out

    monkeypatch.setattr(mesh, "split_flat_channels", spy)
    gathers = len(mesh._PART_GATHERS)
    get_tracer().reset()
    s = _search(_beam(tmp_path, SEEDS[0]), ndev=ndev)
    s.run()
    (up,) = [r for r in get_tracer().records() if r.name == "Prep-Upload"]
    parts = s._dev_chunk_static[0]
    assert len(parts) == len(host) >= 1
    for d, h in zip(parts, host):
        assert d.sharding == NamedSharding(s.mesh, P())
        assert d.sharding.is_fully_replicated
        assert np.asarray(d).tobytes() == h.tobytes()
    part_bytes = sum(h.nbytes for h in host)
    table_bytes = up.attrs["bytes"] - part_bytes
    assert table_bytes > 0
    assert up.attrs["link_bytes"] == part_bytes + ndev * table_bytes
    assert up.attrs["gathered"] == (len(host) if ndev > 1 else 0)
    if ndev == 1:
        assert len(mesh._PART_GATHERS) == gathers


@pytest.mark.parametrize("ndev,lengths,gathered", [
    (4, [4096, 8192], 2),
    (4, [4097], 0),  # an uneven part is put whole to each device
    (4, [4096, 4098], 1),
    (1, [4096], 0),
], ids=["even", "uneven", "mixed", "one_device"])
def test_parts_cross_the_link_by_mesh_and_length(ndev, lengths, gathered):
    """A part is sent as shards and gathered only where the mesh has
    more than one device and its length divides by their count; the
    rest are put replicated, and the link carries them once a device."""
    m = mesh.make_mesh(ndev)
    host = [(np.arange(n) % 251).astype(np.uint8) for n in lengths]
    n0 = len(mesh._PART_GATHERS)
    parts, got, link = mesh.put_flat_parts(m, host)
    assert got == gathered
    for d, h in zip(parts, host):
        assert d.sharding == NamedSharding(m, P())
        assert np.asarray(d).tobytes() == h.tobytes()
    even = [n for n in lengths if ndev > 1 and n % ndev == 0]
    assert link == sum(even) + ndev * (sum(lengths) - sum(even))
    if not gathered:
        assert len(mesh._PART_GATHERS) == n0


@pytest.mark.parametrize("second", ["same_beam", "other_beam"])
def test_second_search_of_one_geometry_compiles_nothing(tmp_path, cli_chunks,
                                                        second):
    """A second CLI search object on the same shapes and mesh compiles
    nothing, on the same beam and on one whose candidate count differs
    (the folds' shapes follow the geometry, not the candidates)."""
    from peasoup_tpu.output.parsers import OverviewFile

    first, _ = _cli(tmp_path, _beam(tmp_path, SEEDS[0]), "first")
    seed = SEEDS[0] if second == "same_beam" else SEEDS[1]
    again, n = _cli(tmp_path, _beam(tmp_path, seed), "again")
    assert n == 0
    a, b = (OverviewFile(os.path.join(d, "overview.xml")).as_array()
            for d in (first, again))
    if second == "same_beam":
        assert a.tobytes() == b.tobytes()
    else:
        assert len(a) != len(b)


def test_kept_chunk_arena_is_left_out_of_the_fold_budget(tmp_path,
                                                         monkeypatch):
    """Where the full fold fits beside its arena, the chunk program
    stays loaded, and the fold's free HBM is the budget less the
    filterbank, the fold rows, the 2 GB reserve and the kept arena."""
    seen = {}
    real = pipeline.fold_candidates

    def spy(*a, **kw):
        seen["free"] = kw["hbm_free_bytes"]
        seen["rows"] = a[1].size
        return real(*a, **kw)

    monkeypatch.setattr(pipeline, "fold_candidates", spy)
    s = _search(_beam(tmp_path, SEEDS[0]))
    s.run()
    kept = mesh.MeshPulsarSearch.kept_chunk_program
    assert kept is not None and kept._cache_size() == 1
    # the arena is the loaded executable's own temp and code bytes
    arena = s.retained_arena_bytes
    assert arena > 0 and s._chunk_program_fits(arena)
    budget = int(s.config.hbm_budget_gb * 1e9)
    assert seen["free"] == (budget - s._data_bytes() - 4 * seen["rows"]
                            - (2 << 30) - arena)


def test_kept_arena_is_the_executables_own(tmp_path, monkeypatch):
    """The arena that decides keeping is the loaded chunk executable's
    memory_analysis (temp and code bytes), read without compiling."""
    from peasoup_tpu.obs.memprof import compiled_footprint

    seen = []
    real = mesh._loaded_arena_bytes

    def spy(program, specs):
        c0 = _compiles()
        seen.append((real(program, specs), _compiles() - c0,
                     compiled_footprint(program.lower(*specs).compile())))
        return seen[-1][0]

    monkeypatch.setattr(mesh, "_loaded_arena_bytes", spy)
    s = _search(_beam(tmp_path, SEEDS[0]))
    s.run()
    ((arena, compiles, fp),) = seen
    assert compiles == 0 and arena == s.retained_arena_bytes
    assert arena == fp["temp_bytes"] + fp["generated_code_bytes"] > 0


def test_tight_budget_releases_the_chunk_program(tmp_path):
    """Where the fold would not fit beside the arena, the chunk program
    is unloaded before the fold, and the fold's budget counts no arena;
    the next search unloads the rows sweep before its chunk program
    loads, so it loads both again."""
    path = _beam(tmp_path, SEEDS[0])
    probe = _search(path)
    probe.run()
    arena = probe.retained_arena_bytes
    mesh.release_kept_programs()
    # room for the filterbank, the arena and the reserve, not the fold
    tight = (probe._data_bytes() + arena + (2 << 30)) / 1e9
    s = _search(path, hbm_budget_gb=tight)
    s.run()
    assert not s._chunk_program_fits(arena)
    assert mesh.MeshPulsarSearch.kept_chunk_program is None
    assert s.retained_arena_bytes == 0
    assert mesh.build_chunked_search.cache_info().currsize == 0
    assert len(mesh._ROWS_SWEEPS) == 1
    sweep = next(iter(mesh._ROWS_SWEEPS.values()))
    c0 = _compiles()
    _search(path, hbm_budget_gb=tight).run()
    assert sweep._cache_size() == 0
    assert _compiles() - c0 == 2


def test_other_geometry_and_fused_path_unload_the_kept_program(tmp_path):
    """A kept chunk program and the rows sweep are unloaded before a
    search of another geometry loads its own chunk program, and before
    a fused search runs."""
    _search(_beam(tmp_path, SEEDS[0])).run()
    kept = mesh.MeshPulsarSearch.kept_chunk_program
    (sweep,) = mesh._ROWS_SWEEPS.values()
    assert kept._cache_size() > 0 and sweep._cache_size() > 0
    _search(_beam(tmp_path, SEEDS[0], nsamps=1 << 16)).run()
    other = mesh.MeshPulsarSearch.kept_chunk_program
    assert other is not kept and other._cache_size() > 0
    assert kept._cache_size() == 0 and sweep._cache_size() == 0
    assert sweep not in mesh._ROWS_SWEEPS.values()
    fused = mesh.MeshPulsarSearch(
        read_filterbank(_beam(tmp_path, SEEDS[0])),
        SearchConfig(dm_list=list(DMS), **SEARCH), mesh=mesh.make_mesh(4))
    fused.run()
    assert mesh.MeshPulsarSearch.kept_chunk_program is None
    assert other._cache_size() == 0 and not mesh._ROWS_SWEEPS
