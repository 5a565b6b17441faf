"""Measured HBM footprints: memory_analysis probes + device stats.

The planner's capacity arithmetic ran on hand-measured constants
("68 MB/row", "96 B/samp", "48 B/element" — `parallel/mesh.py`,
`search/pipeline.py`) that were calibrated once against
``memory_analysis`` output on v5e and then frozen into the source.
This module makes the measurement a first-class, repeatable probe:

* :func:`device_memory_stats` / :func:`hbm_watermark` — the ONE
  ``device.memory_stats()`` call site in the tree (obs/trace.py and
  any sampler delegate here), normalizing the backend key variants
  (``bytes_in_use`` / ``peak_bytes_in_use``) and no-opping gracefully
  (None) on backends without stats (CPU).
* :func:`memory_analysis_probe` — ``jit(fn).lower().compile()
  .memory_analysis()`` distilled to plain argument/output/temp/
  generated-code byte counts (None where the backend provides no
  analysis), the memory-side twin of
  :func:`.costmodel.xla_cost_analysis`.
* :func:`program_footprints` — the probe run over all five registered
  pipeline programs (``analysis/jaxpr_check.py``) at their
  lint-checker shapes, process-cached; :func:`memory_join` joins the
  rows against the cost model's modelled bytes at the same shapes
  (agreement bounded by :data:`MEMORY_CLOSURE_FACTOR`, the memory
  twin of ``CROSSCHECK_FACTOR``).
* :func:`memory_report` — the ``run_report.json`` ``memory`` section:
  cached footprints + model join + the live device watermark.  With
  the default ``probe=False`` it never compiles anything (a per-job
  run report must stay cheap); explicit probing happens via
  ``obs memory --probe``, bench and the tests.
* :func:`probed_bytes_per` — measured replacements for the three
  hardcoded capacity coefficients, as the marginal compiled
  working-set slope between two sizes of a small representative
  program.  Off-TPU it returns None so the calibrated constants (and
  every existing CPU test plan) stay authoritative; ``force=True``
  exercises the machinery anywhere.
"""

from __future__ import annotations

import threading

from .metrics import REGISTRY

#: documented agreement factor between the cost model's modelled bytes
#: and the compiled program's memory_analysis working set: the model
#: counts algorithmic traffic (reads + writes per element) while XLA
#: reports buffer-assignment sizes after fusion/rematerialisation, so
#: exact agreement is impossible — but drift beyond this factor means
#: the model no longer describes the compiled program
MEMORY_CLOSURE_FACTOR = 32.0

#: probe kinds -> the planner constant each replaces (documentation;
#: the call sites fall back to their hand-measured value on None)
PROBE_KINDS = ("spectrum", "row", "fold_samp")


# -- device memory stats (the one memory_stats call site) --------------------

def device_memory_stats(device) -> dict | None:
    """Normalized ``device.memory_stats()`` for one device:
    ``{"bytes_in_use", "peak_bytes_in_use"}`` — or None on backends
    without memory stats (CPU), never an exception."""
    try:
        ms = device.memory_stats()
    except Exception:
        return None
    if not ms:
        return None
    in_use = int(ms.get("bytes_in_use", 0))
    return {
        "bytes_in_use": in_use,
        "peak_bytes_in_use": int(ms.get("peak_bytes_in_use", in_use)),
    }


def hbm_watermark() -> dict | None:
    """Max normalized stats over all local devices, or None when no
    device reports memory stats — the caller treats None as
    "unsupported" and stops polling (``obs/trace.py`` delegates
    here)."""
    try:
        import jax

        devices = jax.local_devices()
    except Exception:
        return None
    out = None
    for d in devices:
        ms = device_memory_stats(d)
        if not ms:
            continue
        if out is None:
            out = {"bytes_in_use": 0, "peak_bytes_in_use": 0}
        out["bytes_in_use"] = max(
            out["bytes_in_use"], ms["bytes_in_use"])
        out["peak_bytes_in_use"] = max(
            out["peak_bytes_in_use"], ms["peak_bytes_in_use"])
    return out


# -- compiled-program memory analysis ----------------------------------------

def memory_analysis_probe(fn, args) -> dict | None:
    """``jax.jit(fn).lower(*args).compile().memory_analysis()``
    distilled to plain byte counts, or None when the backend/jax
    version provides no analysis."""
    try:
        import jax

        return compiled_footprint(jax.jit(fn).lower(*args).compile())
    except Exception:
        return None


def compiled_footprint(compiled) -> dict | None:
    """A compiled executable's ``memory_analysis()`` distilled to plain
    byte counts, or None when the backend/jax version provides no
    analysis."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if isinstance(ma, (list, tuple)):
        ma = ma[0] if ma else None
    if ma is None:
        return None

    def grab(name):
        try:
            return int(getattr(ma, name))
        except Exception:
            return 0

    out = {
        "argument_bytes": grab("argument_size_in_bytes"),
        "output_bytes": grab("output_size_in_bytes"),
        "temp_bytes": grab("temp_size_in_bytes"),
        "alias_bytes": grab("alias_size_in_bytes"),
        "generated_code_bytes": grab("generated_code_size_in_bytes"),
    }
    out["total_bytes"] = (
        out["argument_bytes"] + out["output_bytes"]
        + out["temp_bytes"] + out["generated_code_bytes"]
        - out["alias_bytes"]
    )
    return out


_cache_lock = threading.Lock()
_footprints: list[dict] | None = None
_probe_cache: dict[str, float | None] = {}


def program_footprints(refresh: bool = False) -> list[dict]:
    """memory_analysis rows for the five registered pipeline programs
    at their lint-checker shapes: ``{program, measured}`` where
    ``measured`` is a :func:`memory_analysis_probe` dict or None.
    Probes compile; the result is process-cached (``refresh=True``
    re-probes)."""
    global _footprints
    with _cache_lock:
        if _footprints is not None and not refresh:
            return [dict(r) for r in _footprints]
    from ..analysis.jaxpr_check import registered_programs

    rows: list[dict] = []
    for spec in registered_programs():
        measured = None
        try:
            fn, args = spec.build()
            measured = memory_analysis_probe(fn, args)
        except Exception:
            measured = None
        rows.append({"program": spec.name, "measured": measured})
    with _cache_lock:
        _footprints = [dict(r) for r in rows]
    return rows


def cached_footprints() -> list[dict] | None:
    """The cached :func:`program_footprints` rows, or None when no
    probe has run this process (never compiles)."""
    with _cache_lock:
        if _footprints is None:
            return None
        return [dict(r) for r in _footprints]


def reset_footprints() -> None:
    """Drop the process caches (tests)."""
    global _footprints
    with _cache_lock:
        _footprints = None
        _probe_cache.clear()


def memory_join(footprints: list[dict]) -> list[dict]:
    """Join measured footprints against the cost model's modelled
    bytes at the same shapes.

    One row per program: ``{program, model_bytes, measured,
    measured_bytes, ratio, ok}``.  ``measured_bytes`` is the compiled
    working set (argument + output + temp); ``ok`` is True when the
    ratio stays within :data:`MEMORY_CLOSURE_FACTOR` — and trivially
    True where the backend measured nothing (CPU without analysis),
    mirroring ``crosscheck_registered_programs``."""
    from .costmodel import _crosscheck_shapes

    model = _crosscheck_shapes()
    rows: list[dict] = []
    for fp in footprints:
        est = model.get(fp["program"])
        measured = fp.get("measured")
        row = {
            "program": fp["program"],
            "model_bytes": (round(est.bytes_total)
                            if est is not None else None),
            "measured": measured,
            "measured_bytes": None,
            "ratio": None,
            "ok": True,
        }
        if measured and est is not None:
            working = (measured["argument_bytes"]
                       + measured["output_bytes"]
                       + measured["temp_bytes"])
            row["measured_bytes"] = working
            if working > 0 and est.bytes_total > 0:
                ratio = est.bytes_total / working
                row["ratio"] = round(ratio, 4)
                row["ok"] = (1.0 / MEMORY_CLOSURE_FACTOR <= ratio
                             <= MEMORY_CLOSURE_FACTOR)
        rows.append(row)
    return rows


def memory_report(probe: bool = False) -> dict:
    """The ``run_report.json`` ``memory`` section.

    ``probe=False`` (the per-job default) assembles only what is
    already known — cached program footprints and the live device
    watermark; ``probe=True`` compiles the five registered programs
    first (``obs memory --probe``, bench, tests)."""
    fps = program_footprints() if probe else cached_footprints()
    out: dict = {"closure_factor": MEMORY_CLOSURE_FACTOR}
    if fps is not None:
        out["programs"] = memory_join(fps)
    wm = hbm_watermark()
    if wm is not None:
        out["watermark"] = wm
    with _cache_lock:
        probes = {k: v for k, v in _probe_cache.items()
                  if v is not None}
    if probes:
        out["probed_coefficients"] = probes
    return out


# -- planner capacity probes -------------------------------------------------

def _probe_build(kind: str, size: int):
    """``(fn, args, units, include_args)`` for one capacity probe at
    ``size``: a small representative program whose working set scales
    with the planner's unit, plus the unit count it covers at this
    size.  ``include_args`` is False where the planner constant
    budgets only the produced buffers (the dedispersion input is the
    shared filterbank, already budgeted by ``_data_bytes``)."""
    from functools import partial

    import jax.numpy as jnp

    if kind == "spectrum":
        # per live accel-spectrum element (mesh._SPECTRUM_BYTES)
        from ..search import pipeline as pl

        tim = jnp.zeros((size,), jnp.float32)
        none = jnp.zeros((0,), jnp.float32)
        fn = partial(pl.whiten_core, bin_width=1.0 / size, b5=0.05,
                     b25=0.5, use_zap=False)
        return fn, (tim, none, none), size, True
    if kind == "row":
        # per output sample per DM row (mesh "68 MB/row" planner)
        import importlib

        dd = importlib.import_module("peasoup_tpu.ops.dedisperse")
        data = jnp.zeros((16, 2 * size), jnp.float32)
        delays = jnp.zeros((4, 16), jnp.int32)
        fn = partial(dd.dedisperse, out_nsamps=size)
        return fn, (data, delays), 4 * size, False
    if kind == "fold_samp":
        # per fold sample per candidate (pipeline bytes_per_samp)
        from ..ops.fold import fold_time_series_core, optimise_device

        def fold_and_optimise(tim):
            return optimise_device(
                fold_time_series_core(tim, 0.007, 6.4e-5, 64, 16))

        return fold_and_optimise, (jnp.zeros((size,), jnp.float32),), \
            size, True
    raise ValueError(f"unknown probe kind {kind!r}")


def _probe_slope(kind: str, small: int, large: int) -> float | None:
    """Marginal working-set bytes per unit between two probe sizes."""
    measured = []
    for size in (small, large):
        try:
            fn, args, units, include_args = _probe_build(kind, size)
            ma = memory_analysis_probe(fn, args)
        except Exception:
            return None
        if ma is None:
            return None
        working = ma["output_bytes"] + ma["temp_bytes"]
        if include_args:
            working += ma["argument_bytes"]
        measured.append((units, working))
    (u0, b0), (u1, b1) = measured
    if u1 <= u0:
        return None
    slope = (b1 - b0) / float(u1 - u0)
    return slope if slope > 0 else None


#: probe sizes per kind — the lint-checker shape and its double
_PROBE_SIZES = {
    "spectrum": (2048, 4096),
    "row": (1024, 2048),
    "fold_samp": (16384, 32768),
}

#: catalogued gauge carrying each successful probe
_PROBE_GAUGES = {
    "spectrum": "hbm.probed_spectrum_bytes",
    "row": "hbm.probed_row_bytes",
    "fold_samp": "hbm.probed_fold_samp_bytes",
}


def probed_bytes_per(kind: str, force: bool = False) -> float | None:
    """Measured marginal bytes-per-unit for one planner coefficient,
    or None — the caller then falls back to its hand-measured
    constant.

    Off-TPU this returns None WITHOUT probing (the frozen constants
    are TPU HBM figures; CPU plans — and every CPU test — must not
    shift under a CPU-shaped probe).  On TPU the probe compiles two
    sizes of a small representative program once per process and
    caches the slope; a successful probe also lands in the
    ``hbm.probed_*`` gauges so telemetry and the run report carry the
    measured coefficient.  ``force=True`` probes on any backend
    (tests, ``obs memory --probe``)."""
    if kind not in _PROBE_SIZES:
        raise ValueError(f"unknown probe kind {kind!r}")
    if not force:
        try:
            import jax

            if jax.devices()[0].platform != "tpu":
                return None
        except Exception:
            return None
    with _cache_lock:
        if kind in _probe_cache:
            return _probe_cache[kind]
    small, large = _PROBE_SIZES[kind]
    slope = _probe_slope(kind, small, large)
    with _cache_lock:
        _probe_cache[kind] = slope
    if slope is not None:
        REGISTRY.gauge(_PROBE_GAUGES[kind], slope)
    return slope
