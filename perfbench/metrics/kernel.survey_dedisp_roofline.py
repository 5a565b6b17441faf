"""Share of the roofline of the Pallas dedispersion kernel on the first
chip of a mesh: the least time of that chip's own DM rows (work.py, a
whole-grid count cut to ``ceil(ndm / chips)`` rows, the filterbank
read once; the DM count and FFT length from each observation's
``run_report.json``) over the device time of the kernel's operations on
``/device:TPU:0``.  The chips are the device planes of the trace that
ran any operation.  The fold stage re-dedisperses its candidates' rows
with the same kernel; that time counts, its work does not, so the share
reads low rather than high."""

import math

from perfbench import work, xplane

PATTERN = r"^dedisperse"
CHIP0 = "/device:TPU:0"


def read(run):
    if run.peak is None or CHIP0 not in run.trace["devices"]:
        return None
    reports = [r for r in run.reports() if "n_dm_trials" in r
               and "search.fft_size" in r.get("gauges", {})]
    if not reports:
        return None
    ndm = int(reports[0]["n_dm_trials"])
    n = float(reports[0]["gauges"]["search.fft_size"])
    chips = sum(1 for ops in run.trace["devices"].values() if ops)
    rows = math.ceil(ndm / max(chips, 1))
    # least work is linear in the rows: ndm x nchans x n adds, and the
    # filterbank (read once by every chip) plus 4 n bytes a row written
    ops_row = run.work["dedisp_ops"] / ndm
    fb = run.work["dedisp_bytes"] - 4.0 * ndm * n
    chip0 = {CHIP0: run.trace["devices"][CHIP0]}
    secs = xplane.op_seconds({"devices": chip0}, PATTERN, run.t0_ns,
                             run.t1_ns)
    share, _ = work.roofline_share(ops_row * rows * len(run.obs),
                                   (fb + 4.0 * rows * n) * len(run.obs),
                                   secs, run.peak)
    return share
