"""peasoup-compatible command-line interface.

Flags and defaults match the reference CLI
(`include/utils/cmdline.hpp:69-209`); the default output directory is
``./YYYY-MM-DD-HH:MM_peasoup/`` (UTC), like ``get_utc_str``.
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def default_outdir() -> str:
    return time.strftime("./%Y-%m-%d-%H:%M_peasoup/", time.gmtime())


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="peasoup-tpu",
        description="Peasoup-TPU - a TPU pulsar search pipeline",
    )
    p.add_argument("-i", "--inputfile", required=True, dest="infilename",
                   help="File to process (.fil)")
    p.add_argument("-o", "--outdir", default=None, help="The output directory")
    p.add_argument("-k", "--killfile", default="", dest="killfilename",
                   help="Channel mask file")
    p.add_argument("-z", "--zapfile", default="", dest="zapfilename",
                   help="Birdie list file")
    p.add_argument("-t", "--num_threads", type=int, default=14,
                   dest="max_num_threads",
                   help="The number of devices to use")
    p.add_argument("--limit", type=int, default=1000,
                   help="upper limit on number of candidates to write out")
    p.add_argument("--fft_size", type=int, default=0, dest="size",
                   help="Transform size to use (defaults to lower power of two)")
    p.add_argument("--dm_start", type=float, default=0.0)
    p.add_argument("--dm_end", type=float, default=100.0)
    p.add_argument("--dm_file", default="", dest="dm_file",
                   help="file with one DM trial per line (overrides "
                        "dm_start/dm_end/dm_tol)")
    p.add_argument("--dm_tol", type=float, default=1.10)
    p.add_argument("--dm_pulse_width", type=float, default=64.0)
    p.add_argument("--acc_start", type=float, default=0.0)
    p.add_argument("--acc_end", type=float, default=0.0)
    p.add_argument("--acc_tol", type=float, default=1.10)
    p.add_argument("--acc_step", type=float, default=0.0,
                   help="Fixed acceleration step (the unshipped serial "
                        "driver's 0.5 m/s/s grid, src/pipeline.cpp:287); "
                        "0 = tolerance-stepped DM-dependent grid")
    p.add_argument("--acc_pulse_width", type=float, default=64.0)
    p.add_argument("--jerk_start", type=float, default=0.0,
                   help="jerk (accel-derivative) grid start, m/s^3; "
                        "start=end=0 (default) disables the jerk axis")
    p.add_argument("--jerk_end", type=float, default=0.0,
                   help="jerk grid end, m/s^3")
    p.add_argument("--jerk_step", type=float, default=0.0,
                   help="fixed jerk step, m/s^3 (required nonzero when "
                        "start != end); the grid always includes 0 "
                        "when the range straddles it")
    p.add_argument("--boundary_5_freq", type=float, default=0.05)
    p.add_argument("--boundary_25_freq", type=float, default=0.5)
    p.add_argument("-n", "--nharmonics", type=int, default=4)
    p.add_argument("--npdmp", type=int, default=0)
    p.add_argument("-m", "--min_snr", type=float, default=9.0)
    p.add_argument("--min_freq", type=float, default=0.1)
    p.add_argument("--max_freq", type=float, default=1100.0)
    p.add_argument("--max_harm_match", type=int, default=16, dest="max_harm")
    p.add_argument("--freq_tol", type=float, default=0.0001)
    p.add_argument("-v", "--verbose", action="store_true")
    p.add_argument("-p", "--progress_bar", action="store_true")
    # TPU-build extras
    p.add_argument("--peak_capacity", type=int, default=1024)
    p.add_argument("--accel_chunk", type=int, default=16)
    p.add_argument("--compact_capacity", type=int, default=131072,
                   help="per-shard compacted peak buffer (fused search)")
    p.add_argument("--checkpoint_file", default="",
                   help="candidate checkpoint for crash-resume")
    p.add_argument("--checkpoint_interval", type=int, default=8,
                   help="DM trials between checkpoint saves (host loop)")
    p.add_argument("--tune_file", default="",
                   help="persistent buffer-tuning sidecar: repeat runs "
                        "of the same search size their peak buffers "
                        "from the recorded high-waters (no clipped-row "
                        "re-search, minimal transfers)")
    p.add_argument("--peaks_method", default="auto", dest="peaks_method",
                   choices=("auto", "sort", "two_stage", "pallas"),
                   help="peak-extraction lowering: auto lets the tuner "
                        "pick per (device kind, stop bucket, capacity) "
                        "from measured costs; force sort (full device "
                        "sorts), two_stage (row-reduced top_k) or "
                        "pallas (O(survivors) threshold-compaction "
                        "kernel) for A/B benchmarking — all three "
                        "produce identical candidates")
    p.add_argument("--subband", default="never", dest="subband_dedisp",
                   choices=("auto", "always", "never"),
                   help="two-stage sub-band dedispersion (dedisp's "
                        "algorithm class; sub-sample smearing like "
                        "dedisp itself): auto = use when the DM grid "
                        "is dense enough to compress >= 2x; default "
                        "never = exact direct sweep")
    p.add_argument("--subband_eps", default=0.5, type=float,
                   help="sub-band stage-2 residual smearing bound in "
                        "samples (0 = bit-identical to the direct "
                        "sweep; larger = more anchor compression)")
    p.add_argument("--pipeline_depth", type=int, default=2,
                   help="async dispatch pipeline depth (chunked "
                        "driver): 2 overlaps the next chunk's dispatch "
                        "and the async result fetch with host decode "
                        "(default), 1 is the unpipelined A/B "
                        "reference; candidates are bit-identical at "
                        "every depth")
    p.add_argument("--trial_nbits", type=int, default=32,
                   choices=(8, 32),
                   help="dedispersed trial sample format: 32 keeps f32 "
                        "sums (default; strictly more information), 8 "
                        "reproduces the reference's uint8 trial "
                        "quantisation (dedisp out_nbits=8) exactly")
    p.add_argument("--trial_lattice", default="auto",
                   choices=("auto", "f32", "u8", "bf16"),
                   help="dedispersed trial storage lattice: auto "
                        "(default) consults the tuner sidecar's "
                        "parity-gated pick for this device/geometry "
                        "and falls back to f32; f32/u8/bf16 force a "
                        "dtype (u8 requires nbits<=8 input)")
    p.add_argument("--measure_stages",
                   action=argparse.BooleanOptionalAction, default=False,
                   help="clock a dedicated dedispersion dispatch so "
                        "overview.xml <execution_times> carries real "
                        "per-stage numbers (the mesh programs fuse "
                        "dedispersion into the search dispatch); off by "
                        "default — it costs one extra dispatch")
    p.add_argument("--no_compile_cache", action="store_true",
                   help="disable the persistent XLA compilation cache "
                        "(cache dir: $JAX_COMPILATION_CACHE_DIR, else "
                        ".cache/xla in the checkout)")
    p.add_argument("--no_lineage", action="store_true",
                   help="disable the candidate-provenance ledger "
                        "(<outdir>/lineage.jsonl records every "
                        "selection decision for the `why` verb; "
                        "candidate output is bit-identical either way)")
    p.add_argument("--dump_dir", default="",
                   help="Dump per-DM-trial whitening stages (power "
                        "spectrum, running median, whitened series) as "
                        ".npy for golden-file debugging")
    p.add_argument("--profile_dir", default="",
                   help="capture a jax.profiler trace into this directory")
    p.add_argument("--events_log", default="",
                   help="structured JSONL event log (peak-buffer "
                        "overflows, escalations, checkpoint/tune I/O "
                        "failures, ...); default: <outdir>/events.jsonl")
    p.add_argument("--metrics_json", default="",
                   help="machine-readable end-of-run report (stage "
                        "timers with host/device split, counters, "
                        "event summary, device + HBM figures); "
                        "default: <outdir>/run_report.json")
    p.add_argument("--trace_json", default="",
                   help="Chrome trace-event JSON of the run's span "
                        "tree (per-chunk/per-trial attribution, HBM "
                        "watermarks; open in Perfetto or "
                        "chrome://tracing, or summarise with "
                        "python -m peasoup_tpu.tools.trace_report); "
                        "multihost runs write one merged trace from "
                        "process 0; default: <outdir>/trace.json")
    p.add_argument("--single_device", action="store_true",
                   help="disable mesh sharding even with multiple devices")
    return p


def args_to_config(args):
    from .search.plan import SearchConfig

    cfg = SearchConfig()
    for key in vars(args):
        if hasattr(cfg, key) and getattr(args, key) is not None:
            setattr(cfg, key, getattr(args, key))
    if args.outdir is None:
        cfg.outdir = default_outdir()
    return cfg


def write_search_output(result, outdir: str) -> dict:
    """Write candidates.peasoup + overview.xml + run_report.json for a
    SearchResult; returns the run-report dict (obs/report.py)."""
    from .obs.report import write_run_report
    from .output.binary import write_candidate_binary
    from .output.xml_writer import OutputFileWriter

    os.makedirs(outdir, exist_ok=True)
    cfg = result.config
    report_path = (getattr(cfg, "metrics_json", "") or
                   os.path.join(outdir, "run_report.json"))
    injection = getattr(result, "injection", None)
    report = write_run_report(
        report_path, result,
        extra=({"injection": injection} if injection else None))
    byte_mapping = write_candidate_binary(
        result.candidates, os.path.join(outdir, "candidates.peasoup")
    )
    writer = OutputFileWriter()
    writer.add_misc_info()
    writer.add_header(result.header)
    writer.add_search_parameters(result.config)
    writer.add_dm_list(result.dm_list)
    writer.add_acc_list(result.acc_list_dm0)
    writer.add_device_info()
    prov = getattr(result, "provenance", None)
    if prov:
        writer.add_provenance(prov)
        from .obs.lineage import candidate_uid

        cand_ids = [candidate_uid(prov.get("run", ""), c)
                    for c in result.candidates]
    else:
        cand_ids = None
    writer.add_candidates(result.candidates, byte_mapping,
                          cand_ids=cand_ids)
    writer.add_timing_info(result.timers)
    writer.add_telemetry(report)
    writer.to_file(os.path.join(outdir, "overview.xml"))
    return report


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    # subcommand dispatch: `peasoup-tpu coincidencer <filterbanks...>`
    if argv and argv[0] == "coincidencer":
        return coincidencer_main(argv[1:])
    if argv and argv[0] == "accmap":
        return accmap_main(argv[1:])
    # `peasoup-tpu obs query|top|tail|diff|baseline|ingest` — the
    # flight-recorder verb family (ISSUE 16)
    if argv and argv[0] == "obs":
        from .obs.cli import main as obs_main

        return obs_main(argv[1:])
    args = build_parser().parse_args(argv)
    cfg = args_to_config(args)
    if not args.no_compile_cache:
        from .utils import enable_compile_cache

        enable_compile_cache()
    # telemetry sinks, live BEFORE the run so events stream as they
    # happen (a crash still leaves the JSONL trail on disk)
    from .obs.events import configure_event_log
    from .obs.metrics import install_compile_hook
    from .obs.trace import get_tracer, span

    install_compile_hook()
    os.makedirs(cfg.outdir, exist_ok=True)
    configure_event_log(
        cfg.events_log or os.path.join(cfg.outdir, "events.jsonl"))
    # geometry-keyed compile ledger (ISSUE 18): every backend compile
    # this run pays lands in <outdir>/compiles.jsonl attributed to the
    # search geometry (`peasoup-tpu obs compiles` reads it back)
    from .obs.compilation import (
        configure_compile_ledger,
        install_compile_ledger,
    )

    configure_compile_ledger(os.path.join(cfg.outdir, "compiles.jsonl"))
    install_compile_ledger()
    # candidate provenance ledger (ISSUE 19): every selection decision
    # between peak decode and the emitted candidate list, keyed by a
    # run id = the observation basename (`peasoup-tpu obs why` and the
    # serve `why` verb reconstruct decision chains from it)
    from .obs import lineage

    cfg.lineage_run = os.path.basename(cfg.infilename)
    lineage.configure_lineage(
        "" if args.no_lineage
        else os.path.join(cfg.outdir, "lineage.jsonl"))
    # per-run span tree: the trace file must describe THIS run, not
    # every run of a long-lived process
    get_tracer().reset()
    import time as _time

    t_total = _time.time()
    t0 = _time.time()
    from .io import read_filterbank

    with span("Read"):
        fil = read_filterbank(cfg.infilename)
    t_read = _time.time() - t0

    if args.verbose:
        print(f"Read {cfg.infilename}: {fil.nsamps} samples x "
              f"{fil.nchans} chans, {fil.header.nbits}-bit", file=sys.stderr)

    import jax

    from .search.pipeline import PulsarSearch

    # The fused mesh program is the default even on one device: a
    # single dispatch + compact transfer beats the per-DM host loop by
    # an order of magnitude on remote-attached accelerators.
    with span("Search-Setup"):
        if args.single_device:
            search = PulsarSearch(fil, cfg)
        else:
            from .parallel.mesh import MeshPulsarSearch

            search = MeshPulsarSearch(
                fil, cfg, max_devices=args.max_num_threads
            )
    if args.profile_dir:
        from .utils import start_trace

        start_trace(args.profile_dir)
    try:
        result = search.run()
    finally:
        if args.profile_dir:
            from .utils import stop_trace

            stop_trace()
    result.timers["reading"] = t_read
    result.timers["total"] = _time.time() - t_total
    with span("Output"):
        report = write_search_output(result, cfg.outdir)
    # span trace LAST (it covers the output-writing tail too); on
    # multihost runs every process gathers, process 0 writes the merge
    from .obs.trace import write_merged_trace

    trace_path = write_merged_trace(
        cfg.trace_json or os.path.join(cfg.outdir, "trace.json"))
    if args.verbose:
        from .obs.report import format_stage_table

        print(format_stage_table(report), file=sys.stderr)
        if trace_path:
            print(f"Wrote span trace to {trace_path} (open in Perfetto "
                  f"or summarise with python -m "
                  f"peasoup_tpu.tools.trace_report)", file=sys.stderr)
        print(f"Wrote {len(result.candidates)} candidates to {cfg.outdir}",
              file=sys.stderr)
    return 0


def accmap_main(argv=None) -> int:
    """Inter-antenna delay finder CLI over ``ops.correlate.find_delays``.

    Equivalent of the reference's ``bin/accmap`` (`src/accmap.cpp`),
    which is broken in-tree (hardcoded DADA path, missing dada.hpp);
    this version reads the same payload layout — per antenna, ``size``
    interleaved complex8 (int8 re, int8 im) samples of one channel —
    from a raw binary file and prints one line per baseline.
    """
    import argparse

    import numpy as np

    p = argparse.ArgumentParser(
        prog="peasoup-tpu-accmap",
        description="Peasoup-TPU - FFT cross-correlation delay finder",
    )
    p.add_argument("datafile", help="raw int8 file: nant x size x 2 "
                                    "(interleaved re/im)")
    p.add_argument("--nant", type=int, default=2)
    p.add_argument("--size", type=int, default=65536,
                   help="samples per antenna (accmap.cpp:13)")
    p.add_argument("--max_delay", type=int, default=2048,
                   help="correlation search window (accmap.cpp:27)")
    args = p.parse_args(argv)

    raw = np.fromfile(args.datafile, dtype=np.int8)
    need = args.nant * args.size * 2
    if raw.size < need:
        print(f"error: {args.datafile} holds {raw.size} bytes; need "
              f"{need} for nant={args.nant} size={args.size}",
              file=sys.stderr)
        return 1
    z = raw[:need].reshape(args.nant, args.size, 2).astype(np.float32)
    arrays = z[..., 0] + 1j * z[..., 1]
    from .ops.correlate import find_delays

    for rec in find_delays(arrays, args.max_delay):
        print(f"baseline {rec['i']}-{rec['j']}: lag {rec['lag']} "
              f"samples  power {rec['power']:.3f}")
    return 0


def coincidencer_main(argv=None) -> int:
    """Multibeam RFI coincidencer CLI (`src/coincidencer.cpp:46-120`)."""
    p = argparse.ArgumentParser(
        prog="peasoup-tpu-coincidencer",
        description="Peasoup-TPU - multibeam RFI coincidencer",
    )
    p.add_argument("filterbanks", nargs="+", help="File names")
    p.add_argument("--o", dest="samp_outfilename", default="rfi.eb_mask",
                   help="Sample mask output filename")
    p.add_argument("--o2", dest="spec_outfilename", default="birdies.txt",
                   help="Birdie list output filename")
    p.add_argument("-l", "--boundary_5_freq", type=float, default=0.05)
    p.add_argument("-a", "--boundary_25_freq", type=float, default=0.5)
    p.add_argument("--thresh", type=float, default=4.0,
                   help="S/N threshold for coincidence matching")
    p.add_argument("--beam_thresh", type=int, default=4,
                   help="number of beams a candidate must appear in")
    p.add_argument("-v", "--verbose", action="store_true")
    args = p.parse_args(argv)

    from .search.coincidence import CoincidencerConfig, run_coincidencer

    cfg = CoincidencerConfig(
        samp_outfilename=args.samp_outfilename,
        spec_outfilename=args.spec_outfilename,
        boundary_5_freq=args.boundary_5_freq,
        boundary_25_freq=args.boundary_25_freq,
        threshold=args.thresh,
        beam_threshold=args.beam_thresh,
        verbose=args.verbose,
    )
    run_coincidencer(args.filterbanks, cfg)
    if args.verbose:
        print(f"Wrote {cfg.samp_outfilename} and {cfg.spec_outfilename}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
