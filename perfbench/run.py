"""One cell of ``BENCHMARK.json``: survey beams searched back to back
through the ``peasoup`` CLI, on the chip.

    python3 perfbench/run.py --workload NAME --seed N --seconds S --trace 0|1

The cell names a configuration (``configs/<config>.json``: the
observation geometry and the survey's search grid) and a traffic mix
(``traffic/<traffic>.json``: which trials are searched, the injected
pulsar's draw, how many distinct beams a run drains).  Per-layer metrics are
read by ``metrics/<name>.py``.  All are found by the names in
``BENCHMARK.json``, so a cell, a mix or a metric is added by adding
files and entries.

Set-up: JAX init, the mix's beams synthesised from ``--seed`` and
written, one warm-up CLI call on each (every program of the window,
data-shaped ones too, compiled or read from the compile cache inside
the checkout).  Window: CLI calls, one beam each, the beams in turn,
started while fewer than ``--seconds`` have passed; the call in flight
when time runs out is completed and counted.  Then the program's
device state is dropped and the plain reference (``reference.py``)
searches each beam, its DM rows spread over the cell's chips; every
candidate table the window wrote is compared with its beam's table
(``check.py``).

The last stdout line is the result; the compared numbers and their
limits are the last lines of stderr and the last key of the result.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# import the harness as a package, and not this directory's files as
# top-level modules
if sys.path and os.path.abspath(sys.path[0]) == HERE:
    sys.path[0] = ROOT
elif ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SEARCH_FLAGS = {
    "dm_start": "--dm_start", "dm_end": "--dm_end", "dm_tol": "--dm_tol",
    "dm_pulse_width": "--dm_pulse_width", "nharmonics": "-n",
    "npdmp": "--npdmp", "limit": "--limit", "min_snr": "--min_snr",
    "min_freq": "--min_freq", "max_freq": "--max_freq",
    "max_harm": "--max_harm_match", "freq_tol": "--freq_tol",
}
ACCEL_FLAGS = {
    "acc_start": "--acc_start", "acc_end": "--acc_end",
    "acc_step": "--acc_step", "acc_tol": "--acc_tol",
    "acc_pulse_width": "--acc_pulse_width",
}
OBS_KEYS = ("nsamps", "nchans", "tsamp", "fch1", "foff", "nbits", "noise_max")


def log(msg: str) -> None:
    print(f"[perfbench] {msg}", file=sys.stderr, flush=True)


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell ``name`` with its configuration, traffic mix, and the
    metrics it reports, all found by name from ``root``."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "perfbench", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)

    def mine(m):
        return name in m.get("workloads", [name])

    return dict(cell=cell, config=config, traffic=traffic, root=root,
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)])


def dm_grid(config: dict) -> "np.ndarray":
    import numpy as np

    from perfbench.reference import generate_dm_list

    if "dm_list" in config:
        g = config["dm_list"]
        return np.linspace(g["start"], g["stop"], g["num"]).astype(np.float32)
    return generate_dm_list(
        config["dm_start"], config["dm_end"], config["tsamp"],
        config.get("dm_pulse_width", 64.0), config["fch1"], config["foff"],
        config["nchans"], config.get("dm_tol", 1.1))


def draw_pulsar(seed: int, traffic: dict) -> dict:
    """The injected pulsar, drawn from the seed within the mix's ranges
    (the period log-uniformly); a range of one value fixes the quantity
    for every seed."""
    import numpy as np

    rng = np.random.default_rng([seed, 1])
    p = traffic["pulsar"]
    lo, hi = p["period_s"]
    return dict(period=float(np.exp(rng.uniform(np.log(lo), np.log(hi)))),
                dm=float(rng.uniform(*p["dm"])),
                accel=float(rng.uniform(*p["accel_ms2"])),
                duty=p["duty"], snr=p["snr"])


def search_config(config: dict, traffic: dict) -> dict:
    """The search the cell runs: the configuration's, without its
    acceleration grid where the mix searches zero acceleration only."""
    if traffic["accel"]:
        return config
    return {k: v for k, v in config.items() if k not in ACCEL_FLAGS}


def cli_args(search: dict, dm_file: str | None, zap_file: str | None) -> list[str]:
    args = ["--dm_file", dm_file] if dm_file else []
    args += ["-z", zap_file] if zap_file else []
    for key, flag in dict(SEARCH_FLAGS, **ACCEL_FLAGS).items():
        if key in search and not (dm_file and key.startswith("dm_")):
            args += [flag, repr(search[key])]
    return args


def _compiles() -> int:
    from peasoup_tpu.obs.metrics import REGISTRY

    return int(REGISTRY.snapshot()["counters"].get("jit.backend_compiles", 0))


def observe(fil: str, args: list[str], outdir: str) -> dict:
    """One observation through the CLI, in this process."""
    import jax

    from peasoup_tpu import cli

    c0 = _compiles()
    t0 = time.perf_counter()
    error = None
    with jax.profiler.TraceAnnotation("perfbench.obs"):
        try:
            rc = cli.main(["-i", fil, "-o", outdir, *args])
        except Exception as exc:  # a failed observation is counted, not fatal
            rc, error = 1, f"{type(exc).__name__}: {exc}"
    return dict(wall_s=time.perf_counter() - t0, compiles=_compiles() - c0,
                rc=rc, error=error, outdir=outdir)


class CacheLog(logging.Handler):
    """Which programs JAX compiled and which it read from the persistent
    compile cache (its compiler logs both at debug level)."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.miss, self.hit = [], []
        log_ = logging.getLogger("jax._src.compiler")
        log_.setLevel(logging.DEBUG)
        log_.propagate = False
        log_.addHandler(self)
        shown = logging.StreamHandler(sys.stderr)
        shown.setLevel(logging.WARNING)
        log_.addHandler(shown)

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("PERSISTENT COMPILATION CACHE MISS"):
            self.miss.append(msg.split("'")[1])
        elif msg.startswith("Persistent compilation cache hit"):
            self.hit.append(msg.split("'")[1])

    def take(self) -> str:
        out = f"{len(self.miss)} compiled {sorted(set(self.miss))}, {len(self.hit)} from the cache"
        self.miss, self.hit = [], []
        return out


class Run:
    """What a per-layer metric reader sees of a run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def reports(self) -> list[dict]:
        out = []
        for o in self.obs:
            path = os.path.join(o["outdir"], "run_report.json")
            if os.path.exists(path):
                with open(path) as f:
                    out.append(json.load(f))
        return out

    def span_seconds(self) -> list[dict]:
        """Per observation: total seconds of each program span, from the
        CLI's ``trace.json`` (begin/end pairs)."""
        out = []
        for o in self.obs:
            path = os.path.join(o["outdir"], "trace.json")
            if not os.path.exists(path):
                continue
            with open(path) as f:
                events = json.load(f)["traceEvents"]
            open_, tot = {}, {}
            for e in events:
                key = (e.get("name"), e.get("tid"), e.get("pid"))
                if e.get("ph") == "B":
                    open_.setdefault(key, []).append(e["ts"])
                elif e.get("ph") == "E" and open_.get(key):
                    t = e["ts"] - open_[key].pop()
                    tot[e["name"]] = tot.get(e["name"], 0.0) + t / 1e6
            out.append(tot)
        return out


def read_metric(name: str, run: Run, root: str = ROOT):
    path = os.path.join(root, "perfbench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def _peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    return None if None in peaks else int(max(peaks))


def drop_device_state() -> None:
    """Free what the program left on the device: the window is over."""
    import gc

    import jax

    gc.collect()
    jax.clear_caches()
    for a in jax.live_arrays():
        a.delete()


def reference(spec: dict, prep: dict, payload, dtype=None, npdmp: int = 0,
              devices=None):
    """The plain reference over one of the cell's beams on ``devices``
    and its table (``npdmp``: how many of its candidates it folds
    itself)."""
    from perfbench import check
    from perfbench.reference import Reference

    kw = {} if dtype is None else {"dtype": dtype}
    ref = Reference(prep["obs"], payload, float(prep["dms"].max()),
                    birdies=spec["config"].get("birdies", ()),
                    devices=devices, **kw)
    return ref, check.as_table(ref, ref.search(prep["dms"], prep["search"]),
                               npdmp)


def correctness(tables: list, ref, ref_table: list, prep: dict,
                log=None) -> dict:
    """The compared numbers of ``tables`` (check.py)."""
    import numpy as np

    from perfbench import check

    nums = check.compare(tables, ref_table, ref, log=log)
    dms = prep["dms"]
    step = float(np.max(np.diff(dms))) if len(dms) > 1 else 0.0
    nums["missed"] = float(sum(not check.recovered(t, prep["psr"], prep["obs"],
                                                   step) for t in tables))
    return nums


def check_beams(spec: dict, prep: dict, tables: list[list], log=None,
                devices=None) -> dict:
    """The compared numbers over every beam: ``tables[k]`` are the
    tables the window wrote for beam ``k``, each held against that
    beam's reference on ``devices``; the worst gap, and the tables that
    missed."""
    out = {"table_gap": 0.0, "snr_gap": 0.0, "fold_gap": 0.0,
           "missed": 0.0, "compared": 0.0}
    for k, (beam, tabs) in enumerate(zip(prep["beams"], tables)):
        if not tabs:
            continue
        nums = correctness(tabs, *reference(spec, prep, beam["payload"],
                                             devices=devices),
                           prep, log and (lambda m, k=k: log(f"beam {k} {m}")))
        for k in ("table_gap", "snr_gap", "fold_gap"):
            out[k] = max(out[k], nums[k])
        out["missed"] += nums["missed"]
        out["compared"] += nums["compared"]
    return out


def prepare(spec: dict, seed: int, workdir: str) -> dict:
    """Synthesise the mix's beams (``beams``, one if not given: each
    with its own noise from the seed, the same pulsar) and write them
    with the files the CLI reads (set-up)."""
    import numpy as np

    from perfbench import synth

    config, traffic = spec["config"], spec["traffic"]
    obs = {k: config[k] for k in OBS_KEYS}
    dms = dm_grid(config)
    psr = draw_pulsar(seed, traffic)
    t_synth = t_write = 0.0
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    beams = []
    for k in range(int(traffic.get("beams", 1))):
        t0 = time.perf_counter()
        payload, psr_k = synth.synthesize(obs, psr, [seed, 2, k])
        t_synth += time.perf_counter() - t0
        fil = os.path.join(workdir, f"beam{k}.fil")
        t0 = time.perf_counter()
        synth.write(fil, obs, payload)
        t_write += time.perf_counter() - t0
        beams.append(dict(payload=payload, fil=fil))
    psr = psr_k
    t0 = time.perf_counter()
    dm_file = zap_file = None
    if "dm_list" in config:
        dm_file = os.path.join(workdir, "dms.txt")
        np.savetxt(dm_file, dms, fmt="%.6f")
    if config.get("birdies"):
        zap_file = os.path.join(workdir, "birdies.txt")
        np.savetxt(zap_file, np.asarray(config["birdies"]), fmt="%.6f")
    t_write += time.perf_counter() - t0
    search = search_config(config, traffic)
    return dict(obs=obs, dms=dms, psr=psr, beams=beams,
                search=search, args=cli_args(search, dm_file, zap_file),
                synth_s=t_synth, write_s=t_write)


def start(chips: int, require_tpu: bool, root: str):
    """JAX on the cell's chips, with the one compile cache inside the
    checkout keeping every program: the devices, or None where there
    are fewer TPU chips than the cell asks for or no program."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".cache", "xla")
    import jax

    try:
        import peasoup_tpu.cli  # noqa: F401  (the system under test)
    except ImportError as exc:
        log(f"the program is not here: {exc}")
        return None
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        log(f"needs {chips} TPU chip(s), found {len(devices)} "
            f"{devices[0].platform} device(s); nothing run")
        return None
    if jax.default_backend() != "cpu":
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return devices[:chips]


def main(argv=None, *, require_tpu: bool = True, root: str = ROOT) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args(argv)
    seed = a.seed % (1 << 64)
    spec = load_cell(a.workload, root)
    chips = int(spec["cell"]["chips"])

    devices = start(chips, require_tpu, root)
    if devices is None:
        return 3
    import jax

    from perfbench import work

    cache_log = CacheLog()
    peak = work.peaks(devices[0].device_kind) if require_tpu else None
    t_init = time.time() - T_START

    workdir = os.path.join(root, ".cache", "perfbench", a.workload)
    prep = prepare(spec, seed, workdir)
    args = prep["args"] + ["-t", str(chips)]
    beams = prep["beams"]
    warm = [observe(b["fil"], args, os.path.join(workdir, f"warmup{k}"))
            for k, b in enumerate(beams)]
    for w in warm:
        if w["rc"] != 0:
            log(f"warm-up call failed: {w['error'] or w['rc']}")
            return 4
    log(f"set-up: init {t_init:.3f} s, synthesis {prep['synth_s']:.3f} s, "
        f"write {prep['write_s']:.3f} s, warm-up calls "
        + ", ".join(f"{w['wall_s']:.3f} s/{w['compiles']}c" for w in warm)
        + f" ({cache_log.take()}); {len(beams)} beams, "
        f"pulsar {prep['psr']}")

    profile_dir = os.path.join(workdir, "profile")
    if a.trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(profile_dir, profiler_options=opts)
    t_window = time.time()
    setup_s = t_window - T_START
    obs_runs = []
    with jax.profiler.TraceAnnotation("perfbench.window"):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < a.seconds:
            i = len(obs_runs)
            obs_runs.append(observe(beams[i % len(beams)]["fil"], args,
                                    os.path.join(workdir, f"obs{i:04d}")))
            obs_runs[-1]["beam"] = i % len(beams)
        window_s = time.perf_counter() - t0
    if a.trace:
        jax.profiler.stop_trace()
    done = [o for o in obs_runs if o["rc"] == 0]
    failed = len(obs_runs) - len(done)
    for o in obs_runs:
        if o["rc"] != 0:
            log(f"observation failed: {o['error'] or o['rc']}")
    log("window: " + ", ".join(f"{o['wall_s']:.4f} s/{o['compiles']}c"
                               for o in obs_runs) + f"; {cache_log.take()}")
    memory_peak = _peak_bytes(devices)

    metrics = {}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {}
    if done:
        obs_wall = window_s / len(done)
    if not a.trace:
        for m in spec["end_to_end"]:
            value = {"obs_wall_s": obs_wall if done else None,
                     "setup_s": setup_s}[m["name"]]
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        from perfbench import xplane

        trace = xplane.load(xplane.find(profile_dir))
        win = [h for h in trace["host"] if h[0] == "perfbench.window"]
        t0_ns, t1_ns = (win[0][1], win[0][2]) if win else (0, float("inf"))
        busy = xplane.busy_seconds(trace, t0_ns, t1_ns)
        device.update(busy_s=busy, window_s=window_s)
        run = Run(obs=done, trace=trace, t0_ns=t0_ns, t1_ns=t1_ns,
                  busy_s=busy, window_s=window_s, peak=peak,
                  memory_peak_bytes=memory_peak,
                  work=work.counts(prep["obs"], len(prep["dms"]), sum(
                      work.accel_trials(prep["search"], prep["obs"], float(d))
                      for d in prep["dms"])))
        for m in spec["per_layer"]:
            value = read_metric(m["name"], run, root)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        spans = set().union(*run.span_seconds()) | {"perfbench.obs"}
        result["breakdown"] = {
            "device_ops": xplane.top_ops(trace, t0_ns, t1_ns),
            "idle_gaps": xplane.idle_gaps(trace, t0_ns, t1_ns, spans)}
        del trace, run
    shutil.rmtree(profile_dir, ignore_errors=True)

    drop_device_state()
    from perfbench.check import read_candidates

    tables = [[] for _ in beams]
    for o in done:
        tables[o["beam"]].append(read_candidates(
            os.path.join(o["outdir"], "overview.xml")))
    t0 = time.perf_counter()
    nums = check_beams(spec, prep, tables, log, devices)
    log(f"reference check of {len(done)} tables of {len(beams)} beams, "
        f"{int(nums['compared'])} candidates matched: "
        f"{time.perf_counter() - t0:.3f} s")
    limits = spec["config"]["limits"]
    checks = {k: {"value": nums[k], "limit": limits[k]} for k in limits}
    correct = bool(done) and failed == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    for b in beams:
        os.remove(b["fil"])
    result = {"correct": correct, "attempted": len(obs_runs), "failed": failed,
              "metrics": metrics, "device": device, **result,
              "check": checks}
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
