"""A benchmark root at test size: the repository's BENCHMARK.json with
each configuration cut to a CPU-sized beam by the ``test_size`` object
in its own file (the same channel layout, bit depth and search flags,
fewer samples and channels), and the harness's own traffic mixes (at
most two beams each) and metric readers."""

from __future__ import annotations

import json
import os
import shutil

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: a 2-bit survey-like configuration at test size (the chunked path)
TWO_BIT = dict(
    nsamps=(1 << 16) + (1 << 10), nchans=64, tsamp=6.4e-05,
    fch1=1552.0 - 0.390625 * 8, foff=-0.390625 * 16, nbits=2, noise_max=4,
    dm_list={"start": 0.0, "stop": 100.0, "num": 8},
    acc_start=-50.0, acc_end=50.0, acc_step=25.0, nharmonics=4, npdmp=10,
    limit=1000, reduced=["dm_list"],
    limits={"table_gap": 0.15, "snr_gap": 1e-4, "fold_gap": 1e-4,
            "missed": 0.0})


def at_test_size(path: str) -> dict:
    """The configuration in ``path`` cut to its ``test_size``."""
    with open(path) as f:
        cfg = json.load(f)
    if "test_size" not in cfg:
        raise ValueError(f"{path} has no \"test_size\": the keys that cut "
                         "it to a CPU-sized beam")
    cfg.update(cfg["test_size"])
    return cfg


def make_root(path) -> str:
    """A root holding BENCHMARK.json, the configurations at test size
    and a copy of the harness's traffic mixes and metric readers."""
    root = str(path)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    os.makedirs(os.path.join(root, "perfbench", "configs"))
    for entry in bench["configs"]:
        cfg = at_test_size(os.path.join(REPO, entry["file"]))
        with open(os.path.join(root, entry["file"]), "w") as f:
            json.dump(cfg, f)
    for sub in ("traffic", "metrics"):
        shutil.copytree(os.path.join(REPO, "perfbench", sub),
                        os.path.join(root, "perfbench", sub))
    # two beams a mix: the multi-beam path, at test cost
    tdir = os.path.join(root, "perfbench", "traffic")
    for name in os.listdir(tdir):
        with open(os.path.join(tdir, name)) as f:
            mix = json.load(f)
        mix["beams"] = min(mix.get("beams", 1), 2)
        with open(os.path.join(tdir, name), "w") as f:
            json.dump(mix, f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def add_cell(root: str, name: str, config: dict, traffic: dict,
             chips: int = 1) -> None:
    """Add a configuration, a traffic mix and a cell on ``chips`` chips
    to ``root`` by writing their files and entries alone."""
    with open(os.path.join(root, "perfbench", "configs", name + ".json"),
              "w") as f:
        json.dump(config, f)
    with open(os.path.join(root, "perfbench", "traffic", name + ".json"),
              "w") as f:
        json.dump(traffic, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        b = json.load(f)
    b["configs"].append({"name": name, "source": "test",
                         "file": f"perfbench/configs/{name}.json",
                         "reduced": config.get("reduced", []), "why": "test"})
    b["workloads"].append({"name": name + ".cell", "config": name,
                           "traffic": name, "chips": chips, "why": "a test cell"})
    with open(path, "w") as f:
        json.dump(b, f)


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path / "root")
