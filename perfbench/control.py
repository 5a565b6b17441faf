"""The readings that the limits of ``correct`` are set from.

    python3 -m perfbench.control --workload NAME --seeds 1,2,3

For each seed, in one process: the cell's beams, one CLI call on each
(the window at the cell's own load: every beam of the mix once), and
then two sets of the numbers ``check.py`` compares, both against each
beam's float32 reference table:

* ``program``: the program's tables, as a benchmark run reads them;
* ``control``: the tables of the reference computed in bfloat16, put
  in the program's place (it folds its own strongest candidates).

One JSON line per seed.  The benchmark's own runs do not run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

KEYS = ("table_gap", "snr_gap", "fold_gap", "missed", "compared")


def _merge(per_beam: list[dict]) -> dict:
    """The worst gap over the beams, and the sums of the counts (as
    ``run.check_beams`` merges them)."""
    return {k: (sum if k in ("missed", "compared") else max)(
        n[k] for n in per_beam) for k in KEYS}


def readings(spec: dict, seed: int, workdir: str, devices=None) -> dict:
    """One seed's two sets of numbers; the CLI runs on as many chips as
    ``devices`` holds (one if not given), the references on them."""
    import jax.numpy as jnp

    from perfbench import check, run

    prep = run.prepare(spec, seed, workdir)
    chips = len(devices) if devices else 1
    obs = [run.observe(b["fil"], prep["args"] + ["-t", str(chips)],
                       os.path.join(workdir, f"obs{k}"))
           for k, b in enumerate(prep["beams"])]
    bad = [o for o in obs if o["rc"] != 0]
    if bad:
        return {"seed": seed, "error": bad[0]["error"] or bad[0]["rc"]}
    run.drop_device_state()
    program, control, sizes = [], [], []
    for b, o in zip(prep["beams"], obs):
        table = check.read_candidates(os.path.join(o["outdir"], "overview.xml"))
        ref, ref_table = run.reference(spec, prep, b["payload"],
                                       devices=devices)
        _, ctl_table = run.reference(spec, prep, b["payload"], jnp.bfloat16,
                                     spec["config"]["npdmp"], devices)
        sizes.append([len(table), len(ref_table), len(ctl_table)])
        program.append(run.correctness([table], ref, ref_table, prep))
        control.append(run.correctness([ctl_table], ref, ref_table, prep))
        os.remove(b["fil"])
    return {"seed": seed, "obs_wall_s": [o["wall_s"] for o in obs],
            "candidates": sizes, "program": _merge(program),
            "control": _merge(control)}


def main(argv=None, *, require_tpu: bool = True) -> int:
    from perfbench import run

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated seeds")
    a = p.parse_args(argv)
    spec = run.load_cell(a.workload, run.ROOT)
    chips = int(spec["cell"]["chips"])
    devices = run.start(chips, require_tpu, run.ROOT)
    if devices is None:
        return 3
    workdir = os.path.join(run.ROOT, ".cache", "perfbench",
                           a.workload + ".control")
    for s in a.seeds.split(","):
        t0 = time.perf_counter()
        out = readings(spec, int(s) % (1 << 64), workdir, devices)
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
