"""Device-mesh parallelism for the trial grid.

TPU-native replacement of the reference's multi-GPU strategy: where
`src/pipeline_multi.cu:33-81` runs a mutex-guarded DM-trial work queue
over pthread workers (one per GPU) and merges candidate vectors after
join, here the DM axis is a named mesh axis:

* dedispersion is one jitted program whose delay table and output
  carry a ``NamedSharding`` over ``("dm",)`` — XLA partitions the
  channel sweep so each device produces only its DM rows (the input
  filterbank block is replicated, as dedisp's multi-GPU plan does);
* the search is a ``shard_map`` program: each device scans its local
  block of DM trials (whiten -> accel-batch search) and emits
  fixed-capacity peak buffers, which are device-local outputs of the
  same sharding — a single device->host gather replaces the pthread
  join + append of the reference;
* the dynamic DM dispenser becomes a static balanced assignment: DM
  trials cost the same per trial, and ragged accel lists are padded to
  a rectangle with a validity mask (SURVEY.md section 7).

On multi-host systems the same program runs under
``jax.distributed.initialize`` with a global mesh: the per-shard peak
buffers are all-gathered over ICI/DCN by the final host transfer, and
candidate distillation remains a (cheap) host-side pass.
"""

from __future__ import annotations

import os
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..errors import ConfigError, HBMBudgetError
from ..obs import lineage
from ..obs.events import warn_event
from ..obs.metrics import REGISTRY as METRICS
from ..obs.trace import span, span_cursor
from ..ops.dedisperse import (
    dedisperse,
    dedisperse_flat,
    quantise_trials_bf16,
    quantise_trials_u8,
    split_flat_channels,
)
from .dispatch import DispatchPipeline
from ..search.pipeline import (
    FoldInputCache,
    PulsarSearch,
    SearchResult,
    fold_epilogue_core,
    search_one_accel,
    search_one_accel_legacy,
    whiten_core,
)
from ..search.plan import SearchConfig
from ..data.candidates import CandidateCollection
from ..io.unpack import pack_bits
from ..ops.peaks import segmented_unique_peaks


from ..utils.hostfetch import (  # re-exported; also used below
    fetch_to_host,
    finish_fetch,
    put_global,
    start_fetch,
)


def make_mesh(max_devices: int | None = None, axis: str = "dm") -> Mesh:
    devs = jax.devices()
    if max_devices:
        devs = devs[: max_devices]
    return Mesh(np.array(devs), (axis,))


def _onehot_select_rows(values, row_ids, n_rows: int,
                        select_dtype=jnp.bfloat16):
    """Row gather as a one-hot matmul: ``values[row_ids]`` computed on
    the MXU (a ``jnp.take`` row gather measured 28 ms on v5e for the
    kernel2 stage-2 selection this implements; the matmul is ~1 ms).

    Exact by construction when the contraction keeps the f32 operand
    at full precision: every one-hot entry is 0.0 or 1.0 (exact in
    ``select_dtype``), so each output element is one f32 value times
    1.0 plus zeros — ``assert_onehot_selection_exact`` proves the
    bit-identity ON DEVICE before any driver trusts this path (ADVICE
    round 5: the claim was only ever tested against a host float32
    einsum)."""
    onehot = (
        row_ids[:, None] == jnp.arange(n_rows, dtype=jnp.int32)[None, :]
    ).astype(select_dtype)
    return jnp.einsum(
        "rp,pl->rl", onehot, values,
        precision=(lax.Precision.DEFAULT, lax.Precision.HIGHEST),
        preferred_element_type=jnp.float32,
    )


_onehot_exact_checked: dict[tuple, bool] = {}


def assert_onehot_selection_exact(select_dtype=jnp.bfloat16,
                                  value_dtype=jnp.float32,
                                  n_rows: int = 96,
                                  row_len: int = 512) -> None:
    """On-device proof that :func:`_onehot_select_rows` is bit-exact.

    Runs the REAL einsum (same dtypes/precision as the kernel2 row
    selection) on this process's default device over full-mantissa
    random values — including exact-integer and subnormal-adjacent
    magnitudes — and compares bitwise against ``jnp.take``.  Raises
    ``DomainError`` on any mismatch: a backend where
    ``Precision.HIGHEST`` is not an exact limb decomposition of the
    f32 operand would otherwise silently break stage-2 bit-parity
    with the direct sweep.  Cached per (backend, dtypes) — the check
    costs one tiny dispatch, once per process.

    ``value_dtype`` exists for the negative test: casting the VALUES
    through an inexact dtype (e.g. bfloat16) truncates mantissas and
    must trip the assert (tests/test_parallel.py).
    """
    from ..errors import DomainError

    backend = jax.default_backend()
    key = (backend, jnp.dtype(select_dtype).name,
           jnp.dtype(value_dtype).name, n_rows, row_len)
    if _onehot_exact_checked.get(key):
        return
    rng = np.random.default_rng(1234)
    # full f32 mantissas across magnitudes the dedispersed partials
    # span; bf16-truncation of any of these changes the bits
    vals32 = np.concatenate([
        rng.normal(size=(n_rows - 2, row_len)).astype(np.float32)
        * np.logspace(-6, 6, n_rows - 2, dtype=np.float32)[:, None],
        np.full((1, row_len), np.float32(1.0 + 2.0 ** -23)),
        rng.integers(0, 2 ** 23, (1, row_len)).astype(np.float32),
    ])
    row_ids = rng.integers(0, n_rows, size=2 * n_rows).astype(np.int32)
    vals_d = jnp.asarray(vals32).astype(value_dtype)
    sel = jax.jit(
        partial(_onehot_select_rows, n_rows=n_rows,
                select_dtype=select_dtype)
    )(vals_d, jnp.asarray(row_ids))
    want = np.asarray(vals32)[row_ids]
    got = np.asarray(sel)
    if got.dtype != want.dtype or not np.array_equal(
            got.view(np.uint32), want.view(np.uint32)):
        bad = int((got != want).sum())
        raise DomainError(
            f"one-hot row selection is NOT bit-exact on backend "
            f"{backend!r} (select_dtype={jnp.dtype(select_dtype).name}, "
            f"value_dtype={jnp.dtype(value_dtype).name}): {bad} of "
            f"{got.size} elements differ from the jnp.take gather — "
            f"the sub-band kernel2 path would silently break bit-"
            f"parity; use dedisp_method='xla' for stage 2 or report "
            f"the backend"
        )
    _onehot_exact_checked[key] = True


from functools import lru_cache


def _compact_peaks(idxs, snrs, counts, compact_k, method: str = "xla"):
    """Shared device-side tail of both fused programs: compact all
    (dm, accel, level) peak buffers of a shard into one packed f32
    buffer (layout documented in :func:`build_fused_search`).

    Ships BOTH the true above-threshold ``counts`` (escalation sizing)
    and the per-spectrum DELIVERED slot counts (= how many valid
    entries each spectrum actually contributed to the stream).  The
    host segments the stream by ``delivered``, so a device-side
    extraction anomaly (a backend bug under-filling a top-k buffer)
    can never desynchronise the (dm, accel, level) attribution of
    later spectra — it surfaces as ``delivered < min(count, cap)`` on
    the affected spectrum, which the drivers re-search like any
    clipped row.

    ``method``: ``"xla"`` (cumsum+scatter) or ``"pallas"`` (the
    ops/peaks_pallas.py threshold-compaction kernel applied to slot
    validity — bit-identical output, O(n) streaming instead of a
    whole-buffer cumsum+scatter pair).  The drivers pick via
    :meth:`MeshPulsarSearch.compact_method_for`.
    """
    ns = counts.reshape(-1).shape[0]
    delivered = jnp.sum(
        (idxs >= 0).reshape(ns, -1), axis=1, dtype=jnp.int32)
    flat_bin = idxs.reshape(-1)
    flat_snr = snrs.reshape(-1)
    n = flat_bin.shape[0]
    if n > 2**31 - 2:
        raise ConfigError(
            f"peak-buffer slot count {n} overflows int32 slot indices; "
            f"reduce peak_capacity, accel count per dispatch "
            f"(accel_block) or DM rows per shard"
        )
    if method == "pallas":
        from ..ops.peaks_pallas import (
            compact_valid_slots_pallas,
            pallas_interpret,
        )

        sel_bin, sel_snr, nv = compact_valid_slots_pallas(
            flat_bin, flat_snr, compact_k, interpret=pallas_interpret(),
        )
        nvalid = nv.reshape(-1)[:1].astype(jnp.int32)
    else:
        valid = flat_bin >= 0
        # stream compaction via cumsum + scatter.  (A top_k(score,
        # compact_k) formulation is algebraically equivalent but
        # k ~ 10^5 top_k MISCOMPILES on v5e: shape-dependent garbage
        # output or a TPU worker crash.  The scatter runs once per
        # dispatch.)
        pos = jnp.cumsum(valid.astype(jnp.int32)) - 1
        dest = jnp.where(valid, pos, compact_k)  # OOB -> dropped
        # the host reconstructs each entry's (dm, accel, level, slot)
        # tag from ``counts`` alone: valid slots appear in flat
        # spectrum order, so only bins+snrs are shipped
        sel_bin = (
            jnp.full((compact_k,), -1, flat_bin.dtype)
            .at[dest].set(flat_bin, mode="drop")
        )
        sel_snr = (
            jnp.zeros((compact_k,), jnp.float32)
            .at[dest].set(flat_snr.astype(jnp.float32), mode="drop")
        )
        nvalid = jnp.sum(valid, dtype=jnp.int32)[None]
    counts_f = counts.reshape(-1)
    # pack everything into ONE f32 buffer so the host pays a single
    # device->host round trip.  Every int travels as TWO 16-bit halves
    # in plain f32 (exactly representable), so bin indices, counts and
    # nvalid are exact at ANY spectrum length that fits int32 — the
    # reference has no size ceiling either (`src/pipeline_multi.cu:
    # 326-331`).  Floor-div semantics keep the -1 invalid sentinel
    # exact: -1 -> (hi -1, lo 65535) -> -65536 + 65535 = -1.
    # (bitcast_convert_type int32->f32 MISCOMPILES inside this program
    # on v5e: shape-dependent zeroed outputs — hence halves, not bits.)
    return jnp.concatenate([
        (sel_bin // 65536).astype(jnp.float32),
        (sel_bin % 65536).astype(jnp.float32),
        sel_snr,
        (counts_f // 65536).astype(jnp.float32),
        (counts_f % 65536).astype(jnp.float32),
        (delivered // 65536).astype(jnp.float32),
        (delivered % 65536).astype(jnp.float32),
        (nvalid // 65536).astype(jnp.float32),
        (nvalid % 65536).astype(jnp.float32),
    ])


@lru_cache(maxsize=32)
def build_fused_search(
    mesh: Mesh,
    *,
    nbits: int,
    nchans: int,
    nsamps: int,
    out_nsamps: int,
    size: int,
    bin_width: float,
    tsamp: float,
    nharms: int,
    bounds: tuple,
    capacity: int,
    min_snr: float,
    b5: float,
    b25: float,
    use_zap: bool,
    use_killmask: bool,
    compact_k: int,
    max_shift: int | None = None,
    block: int | None = None,
    dedisp_pallas: tuple | None = None,
    lattice: str = "f32",
    use_jerks: bool = False,
    peaks_methods: tuple | None = None,
    compact_method: str = "xla",
    batch: int = 1,
):
    """One jitted program for the ENTIRE device side of the search.

    packed filterbank bytes (replicated) -> device bit-unpack ->
    dedisperse (DM rows sharded over the mesh) -> per-DM whiten ->
    batched accel trials -> harmonic sums -> thresholded peaks ->
    global compaction of all (dm, accel, level) peak buffers into one
    small tagged buffer per shard.

    This exists because device->host transfers and program dispatches
    dominate wall-clock on a remote-attached TPU: the reference pays
    neither (its host loop talks to a local PCIe GPU per DM trial,
    `src/pipeline_multi.cu:145-244`), so the TPU-native design moves the
    whole search into one dispatch and ships home ONE packed f32 buffer
    per shard, laid out as (k = compact_k, ns = ndm_local*naccel*
    nlevels; every int travels as two 16-bit halves in plain f32, so
    transport is exact at any int32 spectrum length):

    * ``[0:k]`` / ``[k:2k]``      bin index hi / lo halves
    * ``[2k:3k]``                 SNR values (f32)
    * ``[3k:3k+ns]`` / ``+2ns``   per-spectrum above-threshold count
      hi / lo halves (overflow check / escalation sizing)
    * ``+2ns:+4ns``               per-spectrum DELIVERED slot count
      hi / lo halves — the key to reconstructing each entry's
      (dm, accel, level) tag; derived from the same buffers the
      compaction scatters, so host segmentation can never desync
    * ``[-2]`` / ``[-1]``         true total valid count hi / lo

    plus ``trials`` (ndm_local, out_nsamps) f32 — full-width, staying
    device-resident for the folding phase; never copied to host.

    Returns a jitted callable
    ``fn(raw, delays, killmask, accs, uidx, d0_u, pos_u, step_u,
    birdies, widths)``.  The table args are always required; when
    ``block`` is None (legacy on-device resampler path) they are
    unused dummies (see ``MeshPulsarSearch._resample_tables``).

    ``dedisp_pallas``: optional static (dm_tile, time_tile, slack,
    pad_to, max_delay) from ``_plan_fused_pallas_dedisp`` — replaces
    the XLA channel-scan sweep with the flat Pallas kernel on the
    uint8 data (measured 2.1 ms vs 46 ms at tutorial scale on v5e;
    the vmapped dynamic_slice lowers to a batched gather).  Requires
    per-shard DM rows divisible by dm_tile and nbits <= 8.

    ``lattice``: the RESOLVED trial dtype (``PulsarSearch.lattice``,
    see search/tuning.py): ``"u8"`` applies the dedisp out_nbits=8
    staircase, ``"bf16"`` the half-bandwidth round-trip cast, ``"f32"``
    nothing.

    ``use_jerks``: jerk-axis search on the LEGACY (``block=None``)
    resampler — an extra trailing ``jerks`` input (same (ndm, namax)
    shape/sharding as ``accs``, the combined trial axis's per-slot
    jerk) is vmapped into :func:`search_one_accel_legacy`.  The table
    path never needs it: unique (accel, jerk) pair tables bake the
    cubic term host-side (``resample2_unique_tables``), so the program
    body is byte-identical with or without a jerk axis there.

    ``batch``: leading observation axis B (ISSUE 9).  ``batch == 1``
    is byte-for-byte the historical single-observation program.  For
    ``batch > 1`` the ``raw`` input becomes ``(B, rawlen)`` packed
    bytes (replicated) and the per-observation body is UNROLLED B
    times — deliberately not vmapped: the Pallas dedisperse /
    compaction kernels take no batch dim, and unrolling keeps each
    beam's HLO identical to the B=1 program so per-beam results stay
    bit-identical to sequential runs (the batched-parity gate).
    Outputs become ``packed (B, ndev*blk_len)`` — row ``b`` is
    exactly the B=1 packed global buffer — and ``trials
    (B, ndm, out_nsamps)``.  Everything else (delay tables, accel
    grid, masks) is shared: callers must only batch observations from
    the same geometry bucket.
    """
    from ..ops.unpack import unpack_bits_device

    nlevels = nharms + 1
    use_tables = block is not None
    take_jerks = use_jerks and not use_tables

    def one_obs(raw, delays, killmask, accs, uidx, d0_u, pos_u, step_u,
                birdies, widths, jerks=None):
        vals = unpack_bits_device(raw, nbits)[: nsamps * nchans]
        # full-width trials are returned for the folding phase (which
        # must see prev_power_of_two(out_nsamps) real samples exactly
        # like the single-device path, `folder.hpp:352-406`); the
        # search itself runs on the fft-size-truncated/padded view
        if dedisp_pallas is not None:
            from ..ops.dedisperse_pallas import dedisperse_pallas_flat

            dd_tile, dd_T, dd_slack, dd_pad, dd_maxdelay = dedisp_pallas
            # true uint8 (unpack yields int32): the kernel's flat
            # buffer needs the u8 1024-element tiling
            data8 = vals.astype(jnp.uint8).reshape(nsamps, nchans).T
            if use_killmask:
                data8 = jnp.where(
                    killmask[:, None] > 0, data8,
                    jnp.zeros((), data8.dtype))
            flat = jnp.pad(
                data8, ((0, 0), (0, dd_pad - nsamps))).reshape(-1)
            trials = dedisperse_pallas_flat(
                [flat], delays, dd_pad, out_nsamps,
                window_slack=dd_slack, dm_tile=dd_tile,
                time_tile=dd_T, chan_group=16, max_delay=dd_maxdelay,
            )
        else:
            data = vals.reshape(nsamps, nchans).T.astype(jnp.float32)
            if use_killmask:
                data = data * killmask[:, None]
            trials = dedisperse(data, delays, out_nsamps)
        if lattice == "u8":  # dedisp's out_nbits=8 staircase
            trials = quantise_trials_u8(trials, nbits, nchans)
        elif lattice == "bf16":
            trials = quantise_trials_bf16(trials)
        if out_nsamps >= size:
            trials_sz = trials[:, :size]
        else:
            pad_mean = jnp.mean(trials, axis=1, keepdims=True)
            pad = jnp.broadcast_to(
                pad_mean, (trials.shape[0], size - out_nsamps)
            )
            trials_sz = jnp.concatenate([trials, pad], axis=1)

        # whiten once per DM row, then FLATTEN (dm, accel) into one wide
        # batch: a single-level vmap keeps every FFT/top_k one big
        # batched op (the nested dm-over-accel vmap measured ~25 ms
        # slower at 59x3 trials on v5e)
        tw, mean, std = jax.vmap(
            lambda t: whiten_core(t, birdies, widths, bin_width, b5, b25,
                                  use_zap)
        )(trials_sz)
        namax = accs.shape[1]
        tw_f = jnp.repeat(tw, namax, axis=0)
        mean_f = jnp.repeat(mean, namax)
        std_f = jnp.repeat(std, namax)
        accs_f = accs.reshape(-1)
        if use_tables:
            search = lambda t, m, s, ui: search_one_accel(
                t, (d0_u[ui], pos_u[ui], step_u[ui]), m, s, tsamp,
                nharms, bounds, capacity, min_snr, max_shift, block,
                peaks_methods,
            )
            idxs, snrs, counts = jax.vmap(search)(
                tw_f, mean_f, std_f, uidx.reshape(-1))
        elif take_jerks:
            search = lambda t, m, s, a, j: search_one_accel_legacy(
                t, jnp.nan_to_num(a), m, s, tsamp, nharms, bounds,
                capacity, min_snr, max_shift, peaks_methods,
                jnp.nan_to_num(j),
            )
            idxs, snrs, counts = jax.vmap(search)(
                tw_f, mean_f, std_f, accs_f, jerks.reshape(-1))
        else:
            search = lambda t, m, s, a: search_one_accel_legacy(
                t, jnp.nan_to_num(a), m, s, tsamp, nharms, bounds,
                capacity, min_snr, max_shift, peaks_methods,
            )
            idxs, snrs, counts = jax.vmap(search)(
                tw_f, mean_f, std_f, accs_f)
        valid = ~jnp.isnan(accs_f)
        idxs = jnp.where(valid[:, None, None], idxs, -1)
        snrs = jnp.where(valid[:, None, None], snrs, 0.0)
        counts = jnp.where(valid[:, None], counts, 0)
        # flat batch is (dm-major, accel) row order — exactly the
        # (dm, accel, level, slot) layout _compact_peaks flattens to
        packed = _compact_peaks(idxs, snrs, counts, compact_k,
                                compact_method)
        return packed, trials

    if batch == 1:
        shard_fn = one_obs
        out_specs = (P("dm"), P("dm", None))
    else:
        def shard_fn(raw, delays, killmask, accs, uidx, d0_u, pos_u,
                     step_u, birdies, widths, jerks=None):
            outs = [one_obs(raw[b], delays, killmask, accs, uidx, d0_u,
                            pos_u, step_u, birdies, widths, jerks)
                    for b in range(batch)]
            packed = jnp.stack([o[0] for o in outs])
            trials = jnp.stack([o[1] for o in outs])
            return packed, trials

        # packed: shards concatenate along the buffer axis so row b of
        # the global (B, ndev*blk_len) result IS the B=1 packed layout
        # _decode_packed already understands; trials keep dm sharded
        out_specs = (P(None, "dm"), P(None, "dm", None))

    mapped = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            P(), P("dm", None), P(), P("dm", None), P("dm", None),
            P(), P(), P(), P(), P(),
        ) + ((P("dm", None),) if take_jerks else ()),
        out_specs=out_specs,
        # pallas_call out_shapes carry no varying-mesh-axes annotation
        # (same waiver as build_chunked_search)
        check_vma=False,
    )
    return jax.jit(mapped)


@lru_cache(maxsize=16)
def build_chunked_search(
    mesh: Mesh,
    *,
    nchans: int,
    out_nsamps: int,
    size: int,
    ndm_local: int,
    dm_chunk: int,
    namax: int,
    accel_block: int,
    bin_width: float,
    tsamp: float,
    nharms: int,
    bounds: tuple,
    capacity: int,
    min_snr: float,
    b5: float,
    b25: float,
    use_zap: bool,
    compact_k: int,
    max_shift: int | None,
    dedisp_method: str,
    window_slack: int = 0,
    dm_tile: int = 32,
    time_tile: int = 15360,
    chan_group: int = 16,
    max_delay_samples: int = 0,
    block: int | None = None,
    n_parts: int = 1,
    subband: tuple | None = None,
    quantise_nbits: int = 0,
    lattice: str = "f32",
    use_jerks: bool = False,
    peaks_methods: tuple | None = None,
    compact_method: str = "xla",
):
    """Bounded-HBM variant of :func:`build_fused_search`.

    The full-materialisation program holds ``(ndm_local, out_nsamps)``
    trials plus ``ndm_local*namax`` batched search intermediates — at
    SURVEY-scale inputs (2^23 samples x 10^3 DM trials) that is
    terabytes. This program is the same single dispatch, but streams
    the work in the shape the reference streams it
    (`src/pipeline_multi.cu:145-157` processes one trial at a time):

    * an outer ``lax.scan`` over DM chunks of ``dm_chunk`` trials:
      dedisperse (Pallas kernel or XLA scan) -> per-row whiten;
    * an inner ``lax.scan`` over accel blocks of ``accel_block``
      trials, so at most ``dm_chunk * accel_block`` spectra worth of
      FFT/harmonic intermediates are ever live;
    * only the fixed-size peak buffers survive each step (stacked by
      the scans), and the usual global compaction ships ONE packed
      buffer per shard home.

    ``trials`` are NOT returned: at this scale they cannot stay
    HBM-resident, so folding re-dedisperses just the candidate DM rows
    (see ``MeshPulsarSearch._fold_trials_provider``).

    ``data`` is channel-major and stays uint8 in HBM for 8-bit inputs
    (f32 at 4096 chans x 2^23 samples would be 34 GB); the caller
    pre-applies the killmask and pre-pads the tail so the Pallas
    kernel's window padding is a no-op on the hot path.

    Returns a jitted ``fn(data, delays, accs, uidx, d0_u, pos_u,
    step_u, birdies, widths) -> packed`` with delays/accs/uidx sharded
    over ``dm`` and ``ndm_local = n_chunks * dm_chunk`` rows per shard.
    The table args are always required; with ``block=None`` they are
    unused dummies (see ``MeshPulsarSearch._resample_tables``).

    ``lattice`` selects the resolved trial dtype exactly like
    :func:`build_fused_search` (``quantise_nbits`` is the INPUT nbits
    the u8 staircase scales by, only read when ``lattice="u8"``), and
    ``use_jerks`` + ``block=None`` adds a per-slot ``jerks`` input
    between ``accs`` and ``uidx`` for the legacy resampler — the table
    path bakes jerk into the unique (accel, jerk) pair tables instead.

    ``subband``: optional static 9-tuple (bounds, L1, n_anchor_p,
    slack, csub, t_sub, k_sub, dm_tile, kernel2) —
    two-stage sub-band dedispersion (``_plan_subband_chunks``): three
    extra leading inputs follow the data parts, all dm-sharded.  With
    ``kernel2`` None they are anchor_delays (n_anchor_p, nchans),
    assign (dm_chunk,), shifts (dm_chunk, nsub) and the per-chunk
    direct sweep is replaced by ``dedisperse_subband_flat`` (anchor
    sweeps + shifted-window XLA assembly).  With ``kernel2`` = (R2,
    slack2, shift_max, chan_group2, dm_tile2, T2) — the Pallas path —
    they are anchor_delays, delays2 (R2, nsub), unpad (dm_chunk,),
    and stage 2 runs as ONE direct-kernel launch over the flat f32
    partials followed by an exact one-hot row selection (see
    ``subband_trials``).  Requires the driver's one-chunk-per-dispatch
    shape.
    """
    from ..ops.dedisperse_pallas import (
        dedisperse_pallas_flat,
        dedisperse_pallas_flat_subband,
    )
    from ..ops.dedisperse import dedisperse_subband_flat

    nlevels = nharms + 1
    n_chunks = ndm_local // dm_chunk
    n_ablocks = namax // accel_block
    assert ndm_local == n_chunks * dm_chunk
    assert namax == n_ablocks * accel_block
    assert subband is None or n_chunks == 1, \
        "sub-band mode needs one chunk per dispatch (the driver's shape)"
    use_tables = block is not None
    take_jerks = use_jerks and not use_tables

    def shard_fn(*args):
        # data arrives AND STAYS flat, split into int32-indexable
        # whole-channel parts — any 2-D view (even a reshape) costs a
        # full-size relayout copy under shard_map, 8 GB at production
        # scale (see ops.dedisperse.dedisperse_flat)
        parts = list(args[:n_parts])
        if subband is not None:
            # in kernel2 mode the last two are (delays2, unpad) — see
            # subband_trials; names kept for the shared unpack
            (anchor_delays, sb_assign, sb_shifts) = args[n_parts:n_parts + 3]
            rest = args[n_parts + 3:]
        else:
            rest = args[n_parts:]
        if take_jerks:
            (delays, accs, jerks, uidx, d0_u, pos_u, step_u, birdies,
             widths) = rest
        else:
            jerks = None
            (delays, accs, uidx, d0_u, pos_u, step_u, birdies,
             widths) = rest
        nsamps_dev = sum(p.shape[0] for p in parts) // nchans

        if subband is not None:
            (sb_bounds, sb_L1, sb_nanch, sb_slack, sb_csub,
             sb_T, sb_K, sb_dm_tile, sb_kernel2) = subband
            if dedisp_method == "pallas":
                # one-launch stage 1 (grid over sub-bands, K-tile
                # windows — see _dedisperse_flat_sb_kernel)
                def stage1(ad):
                    return dedisperse_pallas_flat_subband(
                        parts, ad, nsamps_dev, sb_L1, csub=sb_csub,
                        window_slack=sb_slack, dm_tile=sb_dm_tile,
                        time_tile=sb_T, k_tiles=sb_K,
                        chan_group=chan_group,
                        max_delay=max_delay_samples,
                    )
            else:
                def stage1(cr, ad):
                    return dedisperse_flat(parts, ad, nsamps_dev, sb_L1,
                                           chan_range=cr)

        def subband_trials():
            if dedisp_method == "pallas" and sb_kernel2 is not None:
                # stage 2 as ONE direct-kernel launch over the flat
                # f32 partials (synthetic nsub-channel filterbank,
                # per-row delays = anchor stride + shift); the padded
                # rows are then selected back to chunk order with an
                # exact one-hot matmul — a jnp.take row gather
                # measured 28 ms for the same selection on v5e
                (k2_R2, k2_slack, k2_maxd, k2_G, k2_tile, k2_T) = \
                    sb_kernel2
                partials = stage1(anchor_delays)
                out2 = dedisperse_pallas_flat(
                    [partials.reshape(-1)], sb_assign, sb_L1,
                    out_nsamps, window_slack=k2_slack,
                    max_delay=k2_maxd, dm_tile=k2_tile,
                    time_tile=k2_T, chan_group=k2_G,
                    data_tail_ok=True,
                )
                return _onehot_select_rows(out2, sb_shifts, k2_R2)
            return dedisperse_subband_flat(
                anchor_delays, sb_assign, sb_shifts, out_nsamps,
                bounds=sb_bounds, L1=sb_L1, stage1=stage1,
            )

        def chunk_body(_, ci):
            z = jnp.int32(0)  # literal 0 is weak-i64 under x64
            delays_c = lax.dynamic_slice(
                delays, (ci * dm_chunk, z), (dm_chunk, nchans)
            )
            accs_c = lax.dynamic_slice(
                accs, (ci * dm_chunk, z), (dm_chunk, namax)
            )
            uidx_c = lax.dynamic_slice(
                uidx, (ci * dm_chunk, z), (dm_chunk, namax)
            )
            if take_jerks:
                jerks_c = lax.dynamic_slice(
                    jerks, (ci * dm_chunk, z), (dm_chunk, namax)
                )
            if subband is not None:
                trials = subband_trials()
            elif dedisp_method == "pallas":
                trials = dedisperse_pallas_flat(
                    parts, delays_c, nsamps_dev, out_nsamps,
                    window_slack=window_slack, dm_tile=dm_tile,
                    time_tile=time_tile, chan_group=chan_group,
                    max_delay=max_delay_samples,
                )
            else:
                trials = dedisperse_flat(
                    parts, delays_c, nsamps_dev, out_nsamps)
            if lattice == "u8":  # dedisp's out_nbits=8 staircase
                trials = quantise_trials_u8(
                    trials, quantise_nbits, nchans)
            elif lattice == "bf16":
                trials = quantise_trials_bf16(trials)
            if out_nsamps >= size:
                trials_sz = trials[:, :size]
            else:
                pad_mean = jnp.mean(trials, axis=1, keepdims=True)
                pad = jnp.broadcast_to(
                    pad_mean, (dm_chunk, size - out_nsamps)
                )
                trials_sz = jnp.concatenate([trials, pad], axis=1)

            # scan over DM ROWS with a WIDE accel vmap per step: a
            # wide trial batch keeps the chip fed (measured 18.6
            # ms/trial at 2^23 for a 21-wide vmap vs ~72 ms/trial for
            # the inverted nesting of an 8-row vmap stepping accels
            # one at a time); accel_block bounds the live spectra per
            # step for the HBM budget
            def row_body(_, row_in):
                tim, arow, urow = row_in[:3]
                jrow = row_in[3] if take_jerks else None
                tw, m, s = whiten_core(
                    tim, birdies, widths, bin_width, b5, b25, use_zap
                )

                def ab_body(__, ai):
                    a_blk = lax.dynamic_slice(
                        arow, (ai * accel_block,), (accel_block,))
                    u_blk = lax.dynamic_slice(
                        urow, (ai * accel_block,), (accel_block,))
                    if use_tables:
                        search = lambda ui: search_one_accel(
                            tw, (d0_u[ui], pos_u[ui], step_u[ui]), m, s,
                            tsamp, nharms, bounds, capacity, min_snr,
                            max_shift, block, peaks_methods,
                        )
                        i2, s2, c2 = jax.vmap(search)(u_blk)
                    elif take_jerks:
                        j_blk = lax.dynamic_slice(
                            jrow, (ai * accel_block,), (accel_block,))
                        search = lambda a, j: search_one_accel_legacy(
                            tw, jnp.nan_to_num(a), m, s, tsamp, nharms,
                            bounds, capacity, min_snr, max_shift,
                            peaks_methods, jnp.nan_to_num(j),
                        )
                        i2, s2, c2 = jax.vmap(search)(a_blk, j_blk)
                    else:
                        search = lambda a: search_one_accel_legacy(
                            tw, jnp.nan_to_num(a), m, s, tsamp, nharms,
                            bounds, capacity, min_snr, max_shift,
                            peaks_methods,
                        )
                        i2, s2, c2 = jax.vmap(search)(a_blk)
                    valid = ~jnp.isnan(a_blk)
                    i2 = jnp.where(valid[:, None, None], i2, -1)
                    s2 = jnp.where(valid[:, None, None], s2, 0.0)
                    c2 = jnp.where(valid[:, None], c2, 0)
                    return 0, (i2, s2, c2)

                _, (bi, bs, bc) = lax.scan(
                    ab_body, 0, jnp.arange(n_ablocks, dtype=jnp.int32)
                )
                return 0, (
                    bi.reshape(namax, nlevels, capacity),
                    bs.reshape(namax, nlevels, capacity),
                    bc.reshape(namax, nlevels),
                )

            _, (bi, bs, bc) = lax.scan(
                row_body, 0,
                (trials_sz, accs_c, uidx_c)
                + ((jerks_c,) if take_jerks else ()),
            )
            return 0, (bi, bs, bc)

        _, (idxs, snrs, counts) = lax.scan(
            chunk_body, 0, jnp.arange(n_chunks, dtype=jnp.int32)
        )
        idxs = idxs.reshape(ndm_local, namax, nlevels, capacity)
        snrs = snrs.reshape(ndm_local, namax, nlevels, capacity)
        counts = counts.reshape(ndm_local, namax, nlevels)
        return _compact_peaks(idxs, snrs, counts, compact_k,
                              compact_method)

    if subband is None:
        sb_specs = ()
    elif dedisp_method == "pallas" and subband[8] is not None:
        # kernel2 transport: delays2 (R2, nsub) + unpad (dm_chunk,)
        sb_specs = (P("dm", None), P("dm", None), P("dm"))
    else:
        sb_specs = (P("dm", None), P("dm"), P("dm", None))
    n_rowspecs = 4 if take_jerks else 3
    mapped = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(),) * n_parts + sb_specs
        + (P("dm", None),) * n_rowspecs + (P(), P(), P(), P(), P()),
        out_specs=P("dm"),
        # pallas_call out_shapes carry no varying-mesh-axes annotation;
        # every output here is trivially dm-varying, so skip the check
        check_vma=False,
    )
    # the per-chunk uploads (sub-band tables + delays/accs/[jerks/]
    # uidx) are consumed by exactly one dispatch each — donate their
    # buffers so depth>=2 pipelining doesn't hold two chunks' worth of
    # input HBM.  The resident operands (data parts, resample tables,
    # birdies) are reused by every chunk and must NOT be donated.  CPU
    # jax can't donate (every dispatch would warn) so the hint is
    # dropped there.
    donate = ()
    if jax.default_backend() != "cpu":
        donate = tuple(
            range(n_parts, n_parts + len(sb_specs) + n_rowspecs))
    return jax.jit(mapped, donate_argnums=donate)


@jax.jit
def _parts_landed(*parts):
    """A scalar computed over every flat part: fetching it proves the
    host-to-device upload of the parts has landed."""
    return sum(p[-1].astype(jnp.float32) for p in parts)


#: the programs that gather sharded flat parts to replicated, by mesh
#: and part shapes (``put_flat_parts``); an all-gather holds no arena,
#: so they stay loaded for the process
_PART_GATHERS: dict = {}


def put_flat_parts(mesh: Mesh, host_parts) -> tuple[tuple, int, int]:
    """Put the flat filterbank parts on every device of ``mesh``,
    replicated as ``NamedSharding(mesh, P())``.

    A part whose length divides by the device count crosses the host
    link once: each device takes its contiguous slice (``P("dm")``)
    and one program all-gathers every such part over the interconnect.
    Any other part, and every part on one device, is put replicated,
    a whole copy to each device.  Returns the device parts, how many
    were gathered, and the bytes handed to the host link summed over
    devices."""
    ndev = mesh.devices.size
    rep = NamedSharding(mesh, P())
    parts = [None] * len(host_parts)
    shards = {}
    link_bytes = 0
    for i, p in enumerate(host_parts):
        if ndev > 1 and p.shape[0] % ndev == 0:
            shards[i] = put_global(p, NamedSharding(mesh, P("dm")))
            link_bytes += p.nbytes
        else:
            parts[i] = put_global(p, rep)
            link_bytes += p.nbytes * ndev
    if shards:
        key = (mesh, tuple((s.shape, s.dtype) for s in shards.values()))
        gather = _PART_GATHERS.get(key)
        if gather is None:
            gather = _PART_GATHERS[key] = jax.jit(
                lambda *xs: xs, out_shardings=rep)
        # the shards are dropped on return: each device frees its slice
        # once the gather that reads it has run
        for i, g in zip(shards, gather(*shards.values())):
            parts[i] = g
    return tuple(parts), len(shards), link_bytes


#: the rows sweeps built so far, by shape and mesh (``build_rows_sweep``)
_ROWS_SWEEPS: dict = {}


def build_rows_sweep(mesh: Mesh, *, dm_tile: int, n_parts: int,
                     nsamps_dev: int, out_nsamps: int, dedisp_method: str,
                     window_slack: int, time_tile: int, chan_group: int,
                     max_delay: int):
    """The jitted dedispersion of a block of delay rows over the
    replicated flat parts (the fold's and the re-search's rows):
    ``fn(rows, *parts)``, rows sharded over ``dm``.  Kept by shape and
    mesh until :func:`release_kept_programs`, so the searches of one
    geometry share one program."""
    key = (mesh, dm_tile, n_parts, nsamps_dev, out_nsamps, dedisp_method,
           window_slack, time_tile, chan_group, max_delay)
    fn = _ROWS_SWEEPS.get(key)
    if fn is not None:
        return fn
    if dedisp_method == "pallas":
        from ..ops.dedisperse_pallas import dedisperse_pallas_flat

        def sweep(d, *fs):
            return dedisperse_pallas_flat(
                list(fs), d, nsamps_dev, out_nsamps,
                window_slack=window_slack, dm_tile=dm_tile,
                time_tile=time_tile, chan_group=chan_group,
                max_delay=max_delay,
            )
    else:
        def sweep(d, *fs):
            return dedisperse_flat(list(fs), d, nsamps_dev, out_nsamps)
    # each device sweeps its block of rows over the replicated parts:
    # a Mosaic kernel is never partitioned automatically
    fn = _ROWS_SWEEPS[key] = jax.jit(jax.shard_map(
        sweep, mesh=mesh,
        in_specs=(P("dm", None),) + (P(),) * n_parts,
        out_specs=P("dm", None), check_vma=False))
    return fn


def _loaded_arena_bytes(program, arg_specs) -> int | None:
    """Temp and code bytes of the executable ``program`` loaded for
    arguments of ``arg_specs``, from its own memory_analysis (lowering
    again with the dispatches' shapes reads that executable from the
    jit's cache and compiles nothing); None where the backend gives no
    analysis."""
    from ..obs.memprof import compiled_footprint

    try:
        fp = compiled_footprint(program.lower(*arg_specs).compile())
    except Exception:
        return None
    return None if fp is None else fp["temp_bytes"] + fp["generated_code_bytes"]


def release_kept_programs(keep=None) -> None:
    """Unload what earlier chunked searches kept loaded, so that no
    arena a search did not plan for holds HBM beside its own: the chunk
    program one kept (``MeshPulsarSearch.kept_chunk_program``) and the
    rows sweeps.  Where that chunk program is ``keep`` (the same
    geometry again), both stay: ``_chunk_program_fits`` reckoned the
    fold beside them."""
    kept = MeshPulsarSearch.kept_chunk_program
    MeshPulsarSearch.kept_chunk_program = None
    if kept is not None and kept is keep:
        return
    if kept is not None:
        kept.clear_cache()
    for fn in _ROWS_SWEEPS.values():
        fn.clear_cache()
    _ROWS_SWEEPS.clear()


class MeshPulsarSearch(PulsarSearch):
    """Multi-device search: DM trials sharded over a 1-D device mesh."""

    #: the chunk program a chunked search kept loaded for the next
    #: search of its geometry (``_run_chunked``), process-wide: at most
    #: one, released by :func:`release_kept_programs`
    kept_chunk_program = None

    def __init__(self, fil, config: SearchConfig, max_devices=None,
                 mesh: Mesh | None = None):
        super().__init__(fil, config)
        self.mesh = mesh if mesh is not None else make_mesh(max_devices)
        self.ndev = self.mesh.devices.size

    def _padded_trial_count(self) -> int:
        ndm = len(self.dm_list)
        return int(np.ceil(ndm / self.ndev)) * self.ndev

    def _trial_lists(self, acc_lists):
        """Combined (accel, jerk) per-DM trial lists (ISSUE 13).

        Returns ``(trial_accs, trial_jerks)`` where each DM row's lists
        flatten the accel x jerk product with accel varying fastest
        (``search/plan.py:combine_trials``).  Jerk-free plans return
        the accel lists UNCHANGED with ``trial_jerks=None``, so every
        downstream grid, table and compiled program is bit-identical
        to the accel-only search."""
        if self.jerk_plan.max_abs == 0.0:
            return acc_lists, None
        from ..search.plan import combine_trials

        jl = self.jerk_plan.jerk_list()
        pairs = [combine_trials(a, jl) for a in acc_lists]
        return [p[0] for p in pairs], [p[1] for p in pairs]

    def _legacy_jerks(self) -> bool:
        """True when the legacy (table-free) resampler must receive an
        explicit per-slot jerks input — the table path bakes jerk into
        the unique (accel, jerk) pair tables instead."""
        return (self.jerk_plan.max_abs > 0.0
                and self.resample_block is None)

    def compact_method_for(self, compact_k: int) -> str:
        """Lowering of the whole-buffer stream compaction
        (:func:`_compact_peaks`): the ops/peaks_pallas.py threshold-
        compaction kernel replaces the cumsum+scatter when the
        compiled kernel is available and the compacted buffer is small
        enough that the kernel's one-hot scatter tiles stay in VMEM.
        ``COMPACT_PALLAS_MAX_K`` admits exactly the tuned common case
        (the drivers round ``ck_hw`` up in 4096 quanta with a 4096
        floor); bigger untuned buffers keep the XLA lowering.  Forced
        ``peaks_method="sort"/"two_stage"`` pins XLA — the compaction
        is peak-path machinery, so the A/B forcing flag governs it
        too; forced ``"pallas"`` off-TPU stays XLA here (an interpret-
        mode compaction inside the fused program would serialise the
        whole dispatch ~100x; per-level extraction keeps its own
        forced-pallas fallback story).
        """
        from ..ops.peaks_pallas import COMPACT_PALLAS_MAX_K
        from ..search.pipeline import _pallas_mode

        if (int(compact_k) <= COMPACT_PALLAS_MAX_K
                and self.config.peaks_method in ("auto", "pallas")
                and _pallas_mode() == "compiled"):
            METRICS.inc("peaks.compact_pallas")
            return "pallas"
        return "xla"

    def _plan_fused_pallas_dedisp(self) -> dict | None:
        """Flat Pallas-kernel dedispersion for the FUSED path.

        The XLA channel-scan sweep (``ops.dedisperse.dedisperse``)
        lowers its vmapped dynamic_slice to a batched gather: 46 ms at
        tutorial scale (59 rows x 64 chans x 2^17) on v5e where the
        flat kernel runs 2.1 ms, bit-exact.  Only for <=8-bit inputs
        (the kernel's in-program flat buffer needs the uint8 1024-
        element tiling; an f32 reshape gets a mismatched layout) and
        TPU.  Returns {ndm_p, params} or None; ndm_p is widened so
        every shard's rows divide dm_tile.  Cached on the search
        object (the slack scan is O(ndm_p x nchans) host work and the
        inputs are fixed per search).
        """
        if "_dd_pallas_plan" in self.__dict__:
            return self._dd_pallas_plan
        self._dd_pallas_plan = self._plan_fused_pallas_dedisp_uncached()
        return self._dd_pallas_plan

    def _plan_fused_pallas_dedisp_uncached(self) -> dict | None:
        if self.mesh.devices.flat[0].platform != "tpu":
            return None
        if self.fil.header.nbits > 8 or self.fil.nchans % 32:
            return None
        if self.killmask is not None and not np.isin(
                self.killmask, (0.0, 1.0)).all():
            # the uint8 branch gates channels with where(mask > 0);
            # only a strict 0/1 mask matches the f32 multiply semantics
            return None
        from ..ops.dedisperse_pallas import (
            dedisperse_flat_pad_to,
            pick_flat_tiles,
        )

        dm_tile = 8
        ndm = len(self.dm_list)
        step = self.ndev * dm_tile
        ndm_p = -(-ndm // step) * step
        # edge-pad (matches _device_inputs): zero-delay pad rows next
        # to max-delay rows would explode the slack bound
        delays_p = np.empty((ndm_p, self.fil.nchans), np.int32)
        delays_p[:ndm] = self.delays
        delays_p[ndm:] = self.delays[-1]
        tiles = pick_flat_tiles(delays_p, self.out_nsamps, (dm_tile,),
                                (15360,))
        if tiles is None:
            return None
        _, T, slack = tiles
        pad_to = dedisperse_flat_pad_to(
            self.out_nsamps, self.max_delay, slack, T)
        return dict(
            ndm_p=ndm_p,
            params=(dm_tile, T, slack, pad_to, self.max_delay),
        )

    def _tune_scoped_key(self, driver: str) -> str:
        """Tune-sidecar key including mesh geometry: the recorded
        high-waters are per-SHARD quantities (and fused/chunked count
        them differently), so a record from another device count or
        driver must not alias this one."""
        return f"{driver}:ndev={self.ndev}:" + self._tune_key()

    def _expected_raw_len(self) -> int:
        """Length of the packed raw-bytes vector ``_pack_raw`` builds
        (f32 count at nbits=32, else ``pack_bits``'s ceil-divided byte
        count) — the shape a prefetch-staged upload must match."""
        n = self.fil.nsamps * self.fil.nchans
        nbits = self.fil.header.nbits
        if nbits == 32:
            return n
        spb = 8 // nbits
        return (n + spb - 1) // spb

    def _staged_raw_device(self, rep):
        """Consume a prefetch-thread device staging (ISSUE 11): the
        survey worker's ``ObservationPrefetcher`` packs + device_puts
        the raw filterbank bytes while the PREVIOUS job computes
        (``SurveyWorker._stage_observation``), parking the result on
        ``self._staged_raw``.  Returns the replicated device array, or
        None when nothing usable was staged (wrong geometry after a
        header surprise, multi-process runs — where the staging thread
        can't build the global array safely — or no worker at all)."""
        staged = getattr(self, "_staged_raw", None)
        if staged is None or jax.process_count() != 1:
            return None
        dtype = np.float32 if self.fil.header.nbits == 32 else np.uint8
        if (getattr(staged, "shape", None) != (self._expected_raw_len(),)
                or staged.dtype != dtype):
            return None
        METRICS.inc("scheduler.staged_raw_hits")
        # no-op when the staging thread already committed this sharding
        return jax.device_put(staged, rep)

    def dedisperse_sharded(self) -> jax.Array:
        """Dedisperse with the DM axis sharded across the mesh.

        Consumes the PACKED filterbank bytes and unpacks on device —
        exactly like the fused search program — so the only permanent
        HBM residents are the (1x) packed bytes, shared with
        ``_device_inputs`` when that cache exists.  (A previous version
        permanently cached a replicated f32 host transpose: 4x the u8
        footprint, invisible to ``_plan_chunking``'s budget, and the
        reason near-boundary fused searches could RESOURCE_EXHAUST
        once stage measurement warmed it.)
        """
        cached = getattr(self, "_dedisp_sharded_state", None)
        if cached is None:
            from ..ops.unpack import unpack_bits_device

            rep = NamedSharding(self.mesh, P())
            shard = NamedSharding(self.mesh, P("dm", None))
            if getattr(self, "_dev_inputs", None) is not None:
                # the fused program's resident inputs already hold the
                # packed bytes, padded delay table and killmask
                raw_d, delays_d, km_d = self._dev_inputs[:3]
            else:
                ndm = len(self.dm_list)
                ndm_p = self._padded_trial_count()
                delays = np.zeros((ndm_p, self.fil.nchans), np.int32)
                delays[:ndm] = self.delays
                nbits = self.fil.header.nbits
                raw_d = self._staged_raw_device(rep)
                if raw_d is None:
                    if nbits == 32:
                        raw = np.ascontiguousarray(
                            self.fil.data, np.float32).ravel()
                    else:
                        raw = pack_bits(self.fil.data.ravel(), nbits)
                    raw_d = put_global(raw, rep)
                km = (
                    np.asarray(self.killmask, dtype=np.float32)
                    if self.killmask is not None
                    else np.ones(self.fil.nchans, np.float32)
                )
                delays_d = put_global(delays, shard)
                km_d = put_global(km, rep)
            nbits = self.fil.header.nbits
            nchans, nsamps = self.fil.nchans, self.fil.nsamps
            use_km = self.killmask is not None

            def dedisp_from_raw(raw, delays, km):
                # the f32 channel-major view is a transient inside this
                # program (the fused search program materialises the
                # same transient, so this fits whenever it does)
                vals = unpack_bits_device(raw, nbits)[: nsamps * nchans]
                data = vals.reshape(nsamps, nchans).T.astype(jnp.float32)
                if use_km:
                    data = data * km[:, None]
                return dedisperse(data, delays, self.out_nsamps)

            fn = jax.jit(dedisp_from_raw, out_shardings=shard)
            cached = (fn, raw_d, delays_d, km_d)
            self._dedisp_sharded_state = cached
        fn, raw_d, delays_d, km_d = cached
        return fn(raw_d, delays_d, km_d)

    def _device_inputs(self, acc_lists, ndm_p: int, namax: int,
                       jerk_lists=None):
        """Build (once) and cache the device-resident static inputs.

        The filterbank bytes, delay table, killmask and accel grid are
        constant for a given search object, so they live in HBM across
        ``run()`` calls — re-uploading them per run costs more than the
        entire device search on a remote-attached TPU.

        ``acc_lists``/``jerk_lists`` are the COMBINED trial lists
        (``_trial_lists``): jerk is folded into the unique-pair
        resample tables, and a trailing jerks grid joins the residents
        only on the legacy table-free path (``_legacy_jerks``).
        """
        if getattr(self, "_dev_inputs", None) is not None:
            return self._dev_inputs
        ndm = len(self.dm_list)
        accs = np.full((ndm_p, namax), np.nan, np.float32)
        jerks = (np.full((ndm_p, namax), np.nan, np.float32)
                 if jerk_lists is not None else None)
        for i, a in enumerate(acc_lists):
            accs[i, : len(a)] = a
            if jerks is not None:
                jerks[i, : len(a)] = jerk_lists[i]
        # edge-pad the DM rows (their accel slots are NaN, so they
        # emit nothing): zero-delay pad rows would sit next to
        # max-delay rows in the Pallas kernel's last dm_tile block and
        # explode its window-slack bound
        delays = np.empty((ndm_p, self.fil.nchans), np.int32)
        delays[:ndm] = self.delays
        delays[ndm:] = self.delays[-1] if ndm else 0
        killmask = (
            self.killmask
            if self.killmask is not None
            else np.ones(self.fil.nchans, np.float32)
        )
        nbits = self.fil.header.nbits
        rep = NamedSharding(self.mesh, P())
        shard = NamedSharding(self.mesh, P("dm", None))
        raw_d = self._staged_raw_device(rep)
        if raw_d is None:
            if nbits == 32:  # float data: nothing to pack
                raw = np.ascontiguousarray(
                    self.fil.data, np.float32).ravel()
            else:
                raw = pack_bits(self.fil.data.ravel(), nbits)
            raw_d = put_global(raw, rep)
        uidx, d0_u, pos_u, step_u = self._resample_tables(accs, jerks)
        self._dev_inputs = (
            raw_d,
            put_global(delays, shard),
            put_global(np.asarray(killmask, dtype=np.float32), rep),
            put_global(accs, shard),
            put_global(uidx, shard),
            put_global(d0_u, rep),
            put_global(pos_u, rep),
            put_global(step_u, rep),
            put_global(self.birdies, rep),
            put_global(self.bwidths, rep),
        ) + ((put_global(jerks, shard),)
             if jerks is not None and self._legacy_jerks() else ())
        return self._dev_inputs

    def _resample_tables(self, accs: np.ndarray, jerks=None):
        """Host-exact unique-accel resample tables for a NaN-padded
        accel grid (dummies when the legacy path is active).  A jerks
        grid (same shape) switches the dedup to unique (accel, jerk)
        PAIRS with the jerk term baked into each table row."""
        if self.resample_block is None:
            return (
                np.zeros(accs.shape, np.int32),
                np.zeros((1, 1), np.int32),
                np.zeros((1, 1, 1), np.int32),
                np.zeros((1, 1, 1), np.int32),
            )
        from ..ops.resample import resample2_unique_tables

        d0_u, pos_u, step_u, uidx = resample2_unique_tables(
            accs, float(self.fil.tsamp), self.size, self.max_shift,
            block=self.resample_block,
            jerks_grid=jerks,
            width=(self.table_width if jerks is not None else None),
        )
        return uidx, d0_u, pos_u, step_u

    # -- bounded-HBM chunked path (production scale) --------------------

    # per-element planner coefficient, validated against
    # compiled-program memory_analysis at 2^23 x 1024 chans on v5e
    # (temp = ~0.42 GB per live accel spectrum at accel_block 8->12):
    # ~12 full-length f32 buffers per live spectrum (resample windows,
    # fft, interbin, harmonic-sum einsum windows).  Since ISSUE 18 this
    # hand-measured figure is only the FALLBACK: on TPU _plan_chunking
    # asks obs/memprof.probed_bytes_per("spectrum") for the live
    # compiler's measured slope first.
    _SPECTRUM_BYTES = 48

    def _plan_chunking(self, namax: int) -> dict | None:
        """Decide full-materialisation vs chunked execution and pick
        chunk sizes within ``config.hbm_budget_gb``.

        Returns None for the (small-input) full path, else a plan dict.
        """
        cfg = self.config
        budget = int(cfg.hbm_budget_gb * 1e9)
        # measured planner coefficients (ISSUE 18): on TPU the
        # obs/memprof compiled-program probes supply the B/element
        # slopes this planner previously hardcoded; the literals below
        # remain the documented fallbacks (the probe returns None off
        # TPU and on any probe failure, so CPU plans are unchanged)
        from ..obs.memprof import probed_bytes_per

        spectrum_bytes = int(probed_bytes_per("spectrum")
                             or self._SPECTRUM_BYTES)
        row_bytes = int(probed_bytes_per("row") or 8)
        ndm = len(self.dm_list)
        ndm_local = int(np.ceil(ndm / self.ndev))
        dd = self._plan_fused_pallas_dedisp()
        if dd is not None:
            # the fused path widens the per-shard rows to a dm_tile
            # multiple (Pallas dedispersion); budget the rows it will
            # actually run, not the narrower pre-widening count
            ndm_local = dd["ndm_p"] // self.ndev
        est_full = (
            spectrum_bytes * ndm_local * namax * self.size
            + row_bytes * ndm_local * self.out_nsamps
            + self._data_bytes()
            # the fused program's device unpack materialises a full f32
            # channel-major transient alongside the packed input
            + 4 * self.fil.nchans * self.fil.nsamps
        )
        METRICS.gauge("hbm.est_full_bytes", est_full)
        METRICS.gauge("hbm.budget_bytes", budget)
        if est_full <= budget and not cfg.dm_chunk and not cfg.accel_block:
            return None

        avail = budget - self._data_bytes()
        if avail <= 0:
            raise HBMBudgetError(
                f"filterbank alone ({self._data_bytes()/1e9:.1f} GB) "
                f"exceeds hbm_budget_gb={cfg.hbm_budget_gb}"
            )
        # the row scan keeps ONE whiten + accel_block spectra live;
        # dm_chunk rows only cost their dedispersed trials.  A quarter
        # of the budget goes to trials, the rest to the accel batch
        # (wider batches keep the chip fed: 21-wide measured 18.6
        # ms/trial vs 72 ms/trial for 8-row x 1-accel nesting)
        if cfg.dm_chunk:
            dm_chunk = cfg.dm_chunk
        else:
            # marginal HBM cost per DM row, validated against the
            # compiler's memory_analysis at 2^23 x 1024 chans: 68 MB/row
            # = two f32 trial-length buffers (the whiten workspace is
            # per-spectrum, not per-row — one row is whitened at a time
            # inside the scan).  Larger chunks matter: dedispersion
            # re-reads the whole filterbank once per chunk
            per_row = row_bytes * self.out_nsamps
            dm_chunk = int(max(1, min(32, (avail // 4) // per_row)))
        if cfg.accel_block:
            accel_block = cfg.accel_block
        else:
            live = (avail * 3 // 4) // (spectrum_bytes * self.size)
            accel_block = int(max(1, min(namax, live)))
        ndm_local_p = int(np.ceil(ndm_local / dm_chunk)) * dm_chunk
        namax_p = int(np.ceil(namax / accel_block)) * accel_block

        # dedispersion method: the FLAT-input tiled Pallas kernel
        # (ops/dedisperse_pallas.py:_dedisperse_flat_kernel) needs a
        # TPU, a 2*chan_group-divisible channel count (pairwise static
        # double buffering) and a full time tile.  The XLA scan
        # fallback's unaligned u8 slices run at ~3% of the HBM
        # roofline — 11.2 s vs the kernel's ~0.7 s per 9-row chunk at
        # 2^23 x 1024 chans on v5e.
        chan_group = 16
        time_tile = next(
            (t for t in (31744, 15360, 7168, 3072, 1024)
             if t <= self.out_nsamps), 0,
        )
        # VMEM out-block is (dm_tile, 8, TQ) f32 — cap the tile at 32
        # rows so a large user-set dm_chunk cannot blow VMEM; the
        # largest divisor <= 32 always tiles dm_chunk evenly
        dm_tile = next(t for t in range(min(32, dm_chunk), 0, -1)
                       if dm_chunk % t == 0)
        plan = dict(
            dm_chunk=dm_chunk, accel_block=accel_block,
            ndm_local_p=ndm_local_p, namax_p=namax_p,
            dedisp_method="scan",
            dm_tile=dm_tile, time_tile=time_tile, chan_group=chan_group,
            window_slack=0, pad_to=self.fil.nsamps,
        )
        if (self.mesh.devices.flat[0].platform != "tpu"
                or self.fil.nchans % (2 * chan_group)):
            return plan
        from ..ops.dedisperse_pallas import (
            dedisperse_flat_pad_to,
            pick_flat_tiles,
        )

        ndm_pp = ndm_local_p * self.ndev
        # edge-pad (like the kernel wrapper): zero-padding would put
        # max-delay rows next to zero rows in the last DM tile and
        # explode the slack bound to ~max_delay
        delays_p = np.empty((ndm_pp, self.fil.nchans), np.int32)
        delays_p[:ndm] = self.delays
        delays_p[ndm:] = self.delays[-1]
        # the widest DM tile (divisor of dm_chunk) and time tile
        # (8*TQ with TQ+128 a power of two, TQ >= 896) whose window
        # slack still fits the 16 MiB scoped VMEM
        tiles = pick_flat_tiles(
            delays_p, self.out_nsamps,
            dm_tiles=[t for t in range(dm_tile, 0, -1)
                      if dm_chunk % t == 0],
            time_tiles=(31744, 15360, 7168), chan_group=chan_group,
            itemsize=1 if self.fil.header.nbits <= 8 else 4)
        if tiles is None:
            return plan
        dm_tile, time_tile, slack = tiles
        plan.update(
            dedisp_method="pallas", dm_tile=dm_tile, time_tile=time_tile,
            window_slack=slack,
            pad_to=dedisperse_flat_pad_to(
                self.out_nsamps, self.max_delay, slack, time_tile))
        return plan

    def _plan_subband_chunks(self, plan) -> dict | None:
        """Sub-band (two-stage) dedispersion plan for the chunked
        driver, honouring ``config.subband_dedisp`` (never/auto/always).

        Anchors are chosen per (chunk, shard) cell so partial sums
        never cross a dispatch; "auto" engages only when the total
        adds compress at least 2x.  The fold/re-search paths keep the
        EXACT direct sweep for their few rows regardless (their trials
        come from ``_dedisperse_rows_device``), so folded SNRs are
        never affected by the bounded stage-2 smearing."""
        cfg = self.config
        mode = cfg.subband_dedisp
        if mode == "never":
            return None
        if mode not in ("auto", "always"):
            raise ConfigError(
                f"subband_dedisp={mode!r}: use auto, always or never")
        from ..ops.dedisperse import subband_chunk_plan
        from ..ops.dedisperse_pallas import (
            dedisperse_flat_pad_to,
            dedisperse_window_slack,
        )

        ndm = len(self.dm_list)
        ndev = self.ndev
        ndm_local_p = plan["ndm_local_p"]
        dm_chunk = plan["dm_chunk"]
        ndm_pp = ndm_local_p * ndev
        nchans = self.fil.nchans
        dm_pad = np.concatenate([
            np.asarray(self.dm_list, np.float64),
            np.repeat(float(self.dm_list[-1]), ndm_pp - ndm),
        ])
        delays_p = np.empty((ndm_pp, nchans), np.int32)
        delays_p[:ndm] = self.delays
        delays_p[ndm:] = self.delays[-1]
        n_chunks = ndm_local_p // dm_chunk
        cells = [
            np.arange(d * ndm_local_p + ci * dm_chunk,
                      d * ndm_local_p + ci * dm_chunk + dm_chunk)
            for ci in range(n_chunks)
            for d in range(ndev)
        ]
        use_pallas = plan["dedisp_method"] == "pallas"
        chan_align = 2 * plan["chan_group"] if use_pallas else 1
        sbp = subband_chunk_plan(
            dm_pad, delays_p, self.delay_tab, cells,
            chan_align=chan_align, eps=cfg.subband_eps,
        )

        def infeasible(reason):
            # an explicitly requested mode must not silently degrade to
            # the direct sweep; auto simply declines
            if mode == "always":
                raise ConfigError(
                    f"subband_dedisp=always, but the two-stage plan is "
                    f"infeasible for this search: {reason}")
            if cfg.verbose:
                print(f"sub-band dedispersion declined: {reason}")
            return None

        if sbp is None:
            return infeasible(
                "no valid anchor decomposition (nchans not aligned, "
                "non-ascending DM list, or negative stage-2 shifts)")
        if mode == "auto" and sbp["cost_ratio"] > 0.5:
            return None
        L1 = self.out_nsamps + sbp["shift_max"]
        n_anchor_p = sbp["n_anchor_p"]
        csub = sbp["bounds"][0][1] - sbp["bounds"][0][0]
        t_sub = k_sub = dm_tile_sub = None
        if use_pallas:
            # stage-1 kernel geometry (dedisperse_pallas_flat_subband).
            # Its VMEM footprint has three parts: the double-buffered
            # (D, 1, K, 8, TQ) f32 out blocks (2*D*K*T*4 bytes — the
            # dominant term once anchors pile up), the 2*chan_group
            # window buffers of W1 ~ K*T samples each, and the
            # (chan_group, 8, WQ) f32 accumulator.  Search (D, K)
            # largest-first under a 14 MB budget so a large anchor
            # count can never hit a Mosaic VMEM compile error (the
            # direct kernel caps dm_tile at 32 for the same reason).
            G = plan["chan_group"]
            t_sub = plan["time_tile"]
            if L1 < t_sub:
                return infeasible(
                    f"output too short for the stage-1 kernel window "
                    f"({L1} < time_tile={t_sub})")
            itemsize = 1 if self.fil.header.nbits <= 8 else 4
            align = 1024  # flat-kernel DMA alignment, any dtype
            # each device runs the kernel on ITS cell's n_anchor_p rows
            # (blocked from row 0 at stride D), so the slack bound must
            # be the max over per-cell tables — blocking one big
            # concatenated table would misalign when D does not divide
            # n_anchor_p and underestimate the window
            cell_tables = [
                delays_p[pad_rows] for pad_rows, _a, _s in sbp["per_cell"]
            ]
            # dm tiles the kernel can keep SMEM-blocked: the whole
            # anchor block (ntiles == 1) or sublane multiples of 8
            for D in [n_anchor_p] + [
                    d for d in (32, 24, 16, 8) if d < n_anchor_p]:
                slack_d = max(
                    int(dedisperse_window_slack(t, D, G))
                    for t in cell_tables
                )
                WL = -(-(t_sub + slack_d + align) // align) * align
                acc_b = G * 8 * (t_sub // 8 + slack_d + align) * 4
                for K in (4, 3, 2, 1):
                    W1 = -(-((K - 1) * t_sub + WL) // align) * align
                    vmem = (2 * D * K * t_sub * 4
                            + 2 * G * W1 * itemsize + acc_b)
                    if vmem <= (14 << 20):
                        dm_tile_sub, k_sub, slack = D, K, slack_d
                        break
                if k_sub is not None:
                    break
            if k_sub is None:
                return infeasible(
                    f"stage-1 kernel cannot fit VMEM even at "
                    f"dm_tile=8, k_tiles=1 (chan_group={G}, "
                    f"time_tile={t_sub}, slack={slack_d})")
            # stage 2 AS a dedispersion: the flat (n_anchor_p, nsub,
            # L1) f32 partials are a synthetic nsub-channel filterbank
            # and each fine row's assembly is one direct-kernel row
            # with delays ``assign*nsub*L1 + shift`` — one launch
            # replaces ndm*nsub XLA dynamic slices (~0.19 s/chunk on
            # v5e, more than the stage-1 sweep itself).  Rows are
            # padded per anchor (subband_stage2_layout) so no tile
            # straddles two anchors and the slack stays at the shift
            # spread, not the anchor stride.
            kernel2 = None
            nsub = sbp["nsub"]
            T2 = t_sub
            # the stage-2 kernel needs nsub % (2*chan_group) == 0
            G2 = next((g for g in (16, 8, 4, 2, 1)
                       if nsub % (2 * g) == 0), None)
            if G2 is not None and self.out_nsamps >= T2:
                from ..ops.dedisperse import subband_stage2_layout

                dm_tile2 = 8
                _, cells2p = subband_stage2_layout(
                    sbp["per_cell"], 0, dm_tile2)
                slack2 = max(
                    int(dedisperse_window_slack(c[0], dm_tile2, G2))
                    for c in cells2p)
                need2 = dedisperse_flat_pad_to(
                    self.out_nsamps, sbp["shift_max"], slack2, T2)
                L1k = -(-max(L1, need2) // align) * align
                if (n_anchor_p * nsub * L1k < 2**31
                        and (n_anchor_p - 1) * nsub * L1k
                        + sbp["shift_max"] < 2**31):
                    # int32 flat offsets hold: engage the kernel path.
                    # The path's final row selection is a bf16 one-hot
                    # einsum — prove ON THIS DEVICE, once per process,
                    # that it is bit-identical to a plain row gather
                    # before trusting it with stage-2 output
                    assert_onehot_selection_exact()
                    L1 = L1k
                    R2, cells2 = subband_stage2_layout(
                        sbp["per_cell"], L1, dm_tile2)
                    kernel2 = (R2, int(slack2), int(sbp["shift_max"]),
                               G2, dm_tile2, T2)
            # slack + align: the sb kernel's per-kk aligned slices
            # round its window one alignment unit past the K*T formula
            pad_sub = dedisperse_flat_pad_to(
                L1, self.max_delay, slack + align, k_sub * t_sub,
            )
            # every flat part must hold whole sub-bands
            plan["part_align"] = max(2 * G, csub)
        else:
            kernel2 = None
            slack = 0
            pad_sub = self.out_nsamps + self.max_delay + sbp["shift_max"]
        plan["pad_to"] = max(plan["pad_to"], pad_sub)
        # per-ci transport arrays (cells are ci-major, shard-minor)
        per_ci = []
        for ci in range(n_chunks):
            cell = sbp["per_cell"][ci * ndev : (ci + 1) * ndev]
            if kernel2 is not None:
                c2 = cells2[ci * ndev : (ci + 1) * ndev]
                per_ci.append((
                    np.concatenate([c[0] for c in cell]),      # anchor rows
                    np.concatenate([d for d, _u in c2]),       # delays2
                    np.concatenate([u for _d, u in c2]),       # unpad
                ))
            else:
                per_ci.append((
                    np.concatenate([c[0] for c in cell]),      # anchor rows
                    np.concatenate([c[1] for c in cell]),      # assign
                    np.concatenate([c[2] for c in cell], axis=0),  # shifts
                ))
        if self.config.verbose:
            print(
                f"sub-band dedispersion: nsub={sbp['nsub']} "
                f"anchors<={n_anchor_p}/cell cost_ratio="
                f"{sbp['cost_ratio']:.2f} max_err={sbp['max_err']} "
                f"samples"
            )
        return dict(
            bounds=sbp["bounds"], L1=L1, n_anchor_p=n_anchor_p,
            slack=int(slack), per_ci=per_ci, max_err=sbp["max_err"],
            cost_ratio=sbp["cost_ratio"], nsub=sbp["nsub"],
            csub=csub, t_sub=t_sub, k_sub=k_sub,
            dm_tile_sub=dm_tile_sub, kernel2=kernel2,
        )

    def _device_inputs_chunked(self, plan, acc_lists, jerk_lists=None):
        """Upload-once device state for the per-chunk dispatches.

        Big replicated arrays (flat data, unique resample tables,
        zap lists) live in HBM across all dispatches in
        ``self._dev_chunk_static``; the per-row arrays (delays, accel
        grid, per-slot jerks, table indices) stay HOST-side in
        ``self._host_chunk_arrays`` — each dispatch uploads only its
        chunk's (tiny) row slices.  ``acc_lists``/``jerk_lists`` are
        the COMBINED trial lists (``_trial_lists``)."""
        if getattr(self, "_dev_chunk_static", None) is not None:
            return
        # the host prep under one root span: the channel-major layout
        # as ``Prep-Transpose`` and every upload as ``Prep-Upload``,
        # each with its ``bytes``
        with span("Chunk-Prep"):
            ndm = len(self.dm_list)
            ndm_pp = plan["ndm_local_p"] * self.ndev
            namax_p = plan["namax_p"]
            accs = np.full((ndm_pp, namax_p), np.nan, np.float32)
            jerks = np.full((ndm_pp, namax_p), np.nan, np.float32)
            for i, a in enumerate(acc_lists):
                accs[i, : len(a)] = a
                if jerk_lists is not None:
                    jerks[i, : len(a)] = jerk_lists[i]
            # edge-pad to match the planner's slack bound (padded rows emit
            # nothing: their accel slots are all NaN)
            delays = np.empty((ndm_pp, self.fil.nchans), np.int32)
            delays[:ndm] = self.delays
            delays[ndm:] = self.delays[-1]
            nbits = self.fil.header.nbits
            nchans, nsamps = self.fil.nchans, self.fil.nsamps
            # single allocation: transpose-copy + killmask + tail pad in
            # place (three sequential full copies would transiently need
            # ~3x the multi-GB input on the host).  The transpose itself is
            # threaded over channel blocks: a byte-granular (nsamps, nchans)
            # -> (nchans, nsamps) strided assignment is the single largest
            # host cost of the production prep (numpy releases the GIL in
            # the strided copy, so threads scale)
            data = np.zeros(
                (nchans, max(plan["pad_to"], nsamps)),
                np.uint8 if nbits <= 8 else np.float32,
            )
            src = self.fil.data
            km = self.killmask

            def _tblock(c0):
                c1 = min(c0 + 64, nchans)
                data[c0:c1, :nsamps] = src[:, c0:c1].T
                if km is not None:
                    data[c0:c1, :nsamps] *= km[c0:c1, None].astype(data.dtype)

            from concurrent.futures import ThreadPoolExecutor

            with span("Prep-Transpose", bytes=int(data.nbytes)):
                with ThreadPoolExecutor(min(16, os.cpu_count() or 8)) as ex:
                    list(ex.map(_tblock, range(0, nchans, 64)))
            rep = NamedSharding(self.mesh, P())
            uidx, d0_u, pos_u, step_u = self._resample_tables(
                accs, jerks if jerk_lists is not None else None)
            self._host_chunk_arrays = (delays, accs, jerks, uidx)
            host_parts = split_flat_channels(
                data,
                # part_align: sub-band stage 1 needs every part to hold
                # whole sub-bands (set by _plan_subband_chunks)
                align=plan.get(
                    "part_align",
                    2 * plan["chan_group"]
                    if plan["dedisp_method"] == "pallas" else 1),
            )
            tables = (d0_u, pos_u, step_u, self.birdies, self.bwidths)
            table_bytes = sum(int(np.asarray(a).nbytes) for a in tables)
            nbytes = sum(int(p.nbytes) for p in host_parts) + table_bytes
            # the copies start here; what is left of the transfer is
            # waited for by the first chunk (its ``upload`` phase)
            with span("Prep-Upload", bytes=nbytes) as sp:
                parts, gathered, link_bytes = put_flat_parts(
                    self.mesh, host_parts)
                self._dev_chunk_static = (parts,) + tuple(
                    put_global(a, rep) for a in tables)
                link_bytes += table_bytes * self.ndev
                sp.set(gathered=gathered, link_bytes=link_bytes)
            METRICS.inc("upload.parts_gathered", gathered)
            METRICS.inc("upload.link_bytes", link_bytes)

    def _dedisperse_rows_device(self, delays_rows, dm_tile=1, min_rows=0):
        """One dedispersion-only dispatch over the resident flat parts
        for the given delay rows (fold re-dedispersion and stage
        measurement).

        ``dm_tile=1`` is always slack-valid — a (1, chan_group)
        block's delay spread is <= the plan's (dm_tile, chan_group)
        bound — and is required when the rows are scattered DMs; the
        stage measurement passes the plan's tile to reflect the real
        chunk configuration.

        The rows are edge-padded to at least ``min_rows`` and to whole
        dm_tiles on every device, and the padded block is returned (row
        ``i`` is ``delays_rows[i]``): a caller that passes one
        ``min_rows`` for any smaller row count gets one program shape."""
        data_parts = self._dev_chunk_static[0]  # flat parts (see
        nchans = self.fil.nchans                # _device_inputs_chunked)
        fn = self._dedisp_rows_program(
            dm_tile, len(data_parts),
            sum(p.shape[0] for p in data_parts) // nchans)
        n = len(delays_rows)
        step = self.ndev * dm_tile
        rows = np.empty((-(-max(n, min_rows) // step) * step, nchans),
                        np.int32)
        rows[:n] = delays_rows
        rows[n:] = delays_rows[-1]
        rows_d = put_global(rows, NamedSharding(self.mesh, P("dm", None)))
        with span("Dedisperse", metric="dedispersion",
                  n_rows=int(n),
                  dm_tile=int(dm_tile),
                  gflops=round(self._dedisp_rows_gflops(n), 3)) as sp:
            return self._maybe_quantise(sp.block(fn(rows_d, *data_parts)))

    def _dedisp_rows_program(self, dm_tile: int, n_parts: int,
                             nsamps_dev: int):
        """The jitted rows sweep of :meth:`_dedisperse_rows_device`
        (:func:`build_rows_sweep` for this search's plan)."""
        plan = self._chunk_plan
        return build_rows_sweep(
            self.mesh, dm_tile=int(dm_tile), n_parts=int(n_parts),
            nsamps_dev=int(nsamps_dev), out_nsamps=int(self.out_nsamps),
            dedisp_method=plan["dedisp_method"],
            window_slack=int(plan["window_slack"]),
            time_tile=int(plan["time_tile"]),
            chan_group=int(plan["chan_group"]),
            max_delay=int(self.max_delay))

    def _dedisp_rows_gflops(self, n_rows: int) -> float:
        """Modelled Gflops of an ``n_rows``-row dedispersion dispatch
        (obs/costmodel.py — the span attribute trace viewers read)."""
        from ..obs.costmodel import dedisperse_cost

        return dedisperse_cost(
            int(n_rows), self.fil.nchans, self.out_nsamps,
            1 if self.fil.header.nbits <= 8 else 4,
        ).flops / 1e9

    def measure_dedispersion_stage(self) -> float:
        """One warm + one timed dedispersion-only dispatch; returns the
        steady-state stage seconds (also recorded as a ``Dedisperse``
        span / ``dedispersion`` stage timer).

        The mesh programs fuse dedispersion into the search dispatch,
        so there is no in-run stage boundary to clock — this dedicated
        dispatch is how ``--measure_stages`` (and bench.py) put a real
        number in ``timers["dedispersion"]`` instead of the 0.0 the
        fused path otherwise reports.
        """
        import time

        warm = self.dedisperse_sharded()
        np.asarray(warm[:1, :1])  # compile + execute untimed
        t0 = time.time()
        with span("Dedisperse", metric="dedispersion",
                  n_dm_trials=len(self.dm_list), measured=True,
                  gflops=round(self._dedisp_rows_gflops(
                      len(self.dm_list)), 3)) as sp:
            trials = self.dedisperse_sharded()
            sp.block(trials)
        return time.time() - t0

    def _fold_trials_provider(self, dm_idxs):
        """Re-dedisperse just the candidate DM rows for folding (the
        chunked program cannot keep (ndm, out_nsamps) trials resident;
        the reference holds them host-side, `pipeline_multi.cu:258`).
        At least ``npdmp`` rows are swept, so the folds of every
        observation of one geometry share one program shape."""
        uniq = sorted(set(int(i) for i in dm_idxs))
        row_map = {dm: r for r, dm in enumerate(uniq)}
        trials = self._dedisperse_rows_device(
            self.delays[uniq], min_rows=self.config.npdmp)
        return trials, row_map

    def _fused_fold_provider(self, dm_idxs):
        """On-device fold fusion (ISSUE 11): (dm_idxs) -> (fold_program,
        row_map) for ``_finalise``'s ``fold_fuser`` seam.

        The returned program composes candidate-row dedispersion with
        :func:`fold_epilogue_core` in ONE dispatch: unpack the resident
        packed filterbank bytes, dedisperse just the ``len(uniq)``
        candidate DM rows, (optionally) quantise exactly as
        ``_maybe_quantise`` would, then whiten/resample/fold/optimise.
        The trial lattice never leaves the device — the only
        device->host traffic of the whole folding phase is the packed
        optimum-per-candidate buffer, so candidates cross the link
        once per job.  Numerically identical to the host-resident
        path: the per-row dedisperse -> (quantise) -> epilogue chain
        is the same jnp graph, just composed into one program."""
        from ..ops.unpack import unpack_bits_device

        uniq = sorted(set(int(i) for i in dm_idxs))
        row_map = {dm: r for r, dm in enumerate(uniq)}
        rep = NamedSharding(self.mesh, P())
        nbits = self.fil.header.nbits
        if getattr(self, "_dev_inputs", None) is not None:
            # the fused search program's residents already hold the
            # packed bytes and killmask — zero re-upload
            raw_d, _dl, km_d = self._dev_inputs[:3]
        elif getattr(self, "_dedisp_sharded_state", None) is not None:
            _fn, raw_d, _dl, km_d = self._dedisp_sharded_state
        else:
            if nbits == 32:  # float data: nothing to pack
                raw = np.ascontiguousarray(
                    self.fil.data, np.float32).ravel()
            else:
                raw = pack_bits(self.fil.data.ravel(), nbits)
            km = (
                np.asarray(self.killmask, dtype=np.float32)
                if self.killmask is not None
                else np.ones(self.fil.nchans, np.float32)
            )
            raw_d = put_global(raw, rep)
            km_d = put_global(km, rep)
        delays_d = put_global(self.delays[uniq].astype(np.int32), rep)
        nchans, nsamps_in = self.fil.nchans, self.fil.nsamps
        out_nsamps = self.out_nsamps
        use_km = self.killmask is not None
        lattice = self.lattice

        @partial(jax.jit, static_argnames=(
            "bin_width", "fold_nsamps", "tsamp", "nbins", "nints",
            "max_shift", "block", "nu", "nb", "w"))
        def fused(raw, km, delays, packed_in, periods, *, bin_width,
                  fold_nsamps, tsamp, nbins, nints, max_shift, block,
                  nu, nb, w):
            # same transient channel-major view as dedisperse_sharded
            vals = unpack_bits_device(raw, nbits)[: nsamps_in * nchans]
            data = vals.reshape(nsamps_in, nchans).T.astype(jnp.float32)
            if use_km:
                data = data * km[:, None]
            trials = dedisperse(data, delays, out_nsamps)
            if lattice == "u8":
                trials = quantise_trials_u8(trials, nbits, nchans)
            elif lattice == "bf16":
                trials = quantise_trials_bf16(trials)
            return fold_epilogue_core(
                trials, packed_in, periods, bin_width, fold_nsamps,
                tsamp, nbins, nints, max_shift, block, nu, nb, w)

        def fold_program(packed_d, periods_d, bin_width, fold_nsamps,
                         tsamp, nbins, nints, max_shift, block, nu,
                         nb, w):
            METRICS.inc("runs.fused_fold_dispatches")
            return fused(
                raw_d, km_d, delays_d, packed_d, periods_d,
                bin_width=bin_width, fold_nsamps=fold_nsamps,
                tsamp=tsamp, nbins=nbins, nints=nints,
                max_shift=max_shift, block=block, nu=nu, nb=nb, w=w)

        return fold_program, row_map

    def _run_chunked(self, plan, acc_lists, namax, timers, t_total, ckpt,
                     ckpt_done, jerk_lists=None):
        """Bounded-HBM production driver: ONE dispatch per DM chunk.

        A single whole-search dispatch at production scale (500 DM x
        21 accel x 2^23 samples) runs for minutes inside one XLA
        program — long enough to hit backend execution limits (the v5e
        worker died mid-run with a kernel-fault report), with no
        progress visibility and an all-or-nothing failure mode.  Each
        chunk of ``dm_chunk`` rows per device is instead its own
        dispatch (~10 s of device time): the per-chunk program is
        compiled once (and persistent-cached), results stream home,
        the checkpoint advances as chunks land, and buffer escalation
        re-runs one chunk instead of the whole search.  The reference
        streams trials the same way (`src/pipeline_multi.cu:145-157`).
        """
        import time

        cfg = self.config
        METRICS.inc("runs.mesh_chunked")
        if cfg.dump_dir:
            warn_event(
                "path_fallback",
                "--dump_dir is ignored on the bounded-HBM chunked path "
                "(trials are never all resident); re-run with "
                "--single_device or a smaller input to dump whitening "
                "stages",
                what="dump_dir", path="chunked",
            )
        ndm = len(self.dm_list)
        ndm_local_p = plan["ndm_local_p"]
        dm_chunk = plan["dm_chunk"]
        namax_p = plan["namax_p"]
        nlevels = cfg.nharmonics + 1
        # persistent buffer tuning: a prior run of the SAME search
        # recorded its true high-water counts, so this run can size the
        # per-spectrum capacity for the bulk of rows (pathological
        # ones stay on the re-search path by design) and
        # the compacted transfer buffer to the observed total (+margin)
        # instead of the worst case.  Results are identical either way;
        # see search/tuning.py.
        from ..search.tuning import load_tuning, round_up, save_tuning

        tune = (load_tuning(cfg.tune_file, self._tune_scoped_key("chunked"))
                if cfg.tune_file else None)
        if tune is not None:
            from ..search.tuning import (
                calibration_constants,
                pick_row_capacity,
            )

            # bound the capacity so the stacked per-chunk peak buffers
            # (dm_chunk x namax x nlevels x cap, idx+snr) stay <= 1 GB
            cap_ceil = max(64, (1 << 30) // (dm_chunk * namax_p
                                             * nlevels * 8))
            if tune.get("row_hw"):
                # per-row counts known: cover the BULK of rows and
                # leave pathological ones to the cheap re-search (a
                # 13k-count pulsar row must not make every spectrum's
                # top_k 13x bigger — measured +330 s at full scale);
                # cost constants are this device's measured calibration
                # when the sidecar has one, v5e defaults otherwise
                n_tr = sum(len(a) for a in acc_lists)
                cal = calibration_constants(cfg.tune_file)
                cap = round_up(
                    pick_row_capacity(
                        tune["row_hw"], n_tr,
                        slot_s=cal["slot_s"],
                        research_s=cal["research_s"],
                        compile_s=cal["compile_s"]),
                    64, 64, cap_ceil)
            else:
                cap = round_up(tune["cap_hw"] + 32, 64, 64, cap_ceil)
        else:
            cap = cfg.peak_capacity
        # per-SHARD slot count: compact_k and nvalid are per-shard
        chunk_slots = dm_chunk * namax_p * nlevels * cap
        if tune is not None:
            # margin absorbs same-data jitter; a genuinely different
            # input mismatches the tune key and never reaches here
            compact_k = round_up(int(tune["ck_hw"] * 1.2) + 1024, 4096,
                                 4096, chunk_slots)
        else:
            compact_k = chunk_slots
        # observability: the benchmark's transfer model reads these
        self._chunk_buffer_shapes = (cap, compact_k)
        self._chunk_plan = plan
        self.record_peaks_selection(cap)
        METRICS.inc(f"dedisp.method_{plan['dedisp_method']}")
        METRICS.gauge("chunk.dm_chunk", dm_chunk)
        METRICS.gauge("chunk.accel_block", plan["accel_block"])
        METRICS.gauge("chunk.peak_capacity", cap)
        METRICS.gauge("chunk.compact_k", compact_k)

        t0 = time.time()
        # sub-band (two-stage) dedispersion plan — must precede the
        # data upload: stage-1 windows may need extra tail padding
        # (plan["pad_to"] is updated in place)
        sb = self._plan_subband_chunks(plan)
        self._device_inputs_chunked(plan, acc_lists, jerk_lists)
        data_parts, d0_u, pos_u, step_u, birdies_d, widths_d = (
            self._dev_chunk_static
        )
        delays_h, accs_h, jerks_h, uidx_h = self._host_chunk_arrays
        use_jerks = self._legacy_jerks()
        rep = NamedSharding(self.mesh, P())
        shard = NamedSharding(self.mesh, P("dm", None))
        shard1 = NamedSharding(self.mesh, P("dm"))

        def build(cap_, ck_):
            return build_chunked_search(
                self.mesh,
                nchans=self.fil.nchans,
                out_nsamps=self.out_nsamps,
                size=self.size,
                ndm_local=dm_chunk,
                dm_chunk=dm_chunk,
                namax=namax_p,
                accel_block=plan["accel_block"],
                bin_width=self.bin_width,
                tsamp=float(self.fil.tsamp),
                nharms=cfg.nharmonics,
                bounds=self.bounds,
                capacity=cap_,
                min_snr=cfg.min_snr,
                b5=cfg.boundary_5_freq,
                b25=cfg.boundary_25_freq,
                use_zap=bool(len(self.birdies)),
                compact_k=ck_,
                max_shift=self.max_shift,
                dedisp_method=plan["dedisp_method"],
                window_slack=plan["window_slack"],
                dm_tile=plan["dm_tile"],
                time_tile=plan["time_tile"],
                chan_group=plan["chan_group"],
                max_delay_samples=self.max_delay,
                block=self.resample_block,
                n_parts=len(data_parts),
                subband=(
                    (sb["bounds"], sb["L1"], sb["n_anchor_p"],
                     sb["slack"], sb["csub"], sb["t_sub"],
                     sb["k_sub"], sb["dm_tile_sub"], sb["kernel2"])
                    if sb is not None else None
                ),
                quantise_nbits=(
                    self.fil.header.nbits
                    if self.lattice == "u8" else 0
                ),
                lattice=self.lattice,
                use_jerks=use_jerks,
                peaks_methods=self.peaks_methods_for(cap_),
                compact_method=self.compact_method_for(ck_),
            )

        n_chunks = ndm_local_p // dm_chunk
        dm_cands = CandidateCollection()
        all_clipped: dict[int, int] = {}  # global row -> max count
        # per-phase breakdown across all chunks (VERDICT r2 item 2:
        # the wall/device-model gap must be attributable).  "prep" is
        # the host-side setup before the first dispatch — sub-band
        # planning, the threaded transpose into flat parts, resample
        # tables, upload initiation — previously unattributed (~200 s
        # of searching_device at production scale, VERDICT r3)
        phases = {"prep": 0.0, "upload": 0.0, "compile": 0.0,
                  "dispatch": 0.0, "fetch": 0.0, "decode": 0.0,
                  "distill": 0.0, "checkpoint": 0.0}
        self._chunk_phases = phases

        tc = time.time()
        phases["prep"] = tc - t0
        # untuned, the compacted buffer is the FULL slot count (~7 MB
        # at dm_chunk=8 x 21 accels x 5 levels x 1024): truncation is
        # impossible, so no escalation/recompile path exists here
        # (per-spectrum capacity overflow is handled by the row
        # re-runs below).  Tuned, compact_k < slots and a truncated
        # row (possible only if the data changed under the tune key)
        # joins the clipped set for the same re-run path.
        program = build(cap, compact_k)
        release_kept_programs(keep=program)
        arg_specs = []  # the shapes of a dispatch's arguments
        todo = []
        n_live = 0  # chunks holding any real (non-padding) DM row
        for ci in range(n_chunks):
            # per-device row block ci: rows d*ndm_local_p + [c0, c0+dm_chunk)
            c0 = ci * dm_chunk
            rows = np.concatenate([
                np.arange(d * ndm_local_p + c0,
                          d * ndm_local_p + c0 + dm_chunk)
                for d in range(self.ndev)
            ])
            n_live += any(int(r) < ndm for r in rows)
            if all(int(r) in ckpt_done or int(r) >= ndm for r in rows):
                continue  # checkpoint resume: chunk already searched
            todo.append((ci, rows))

        def dispatch(ci, rows):
            sb_args = ()
            if sb is not None:
                anchor_rows, a2, a3 = sb["per_ci"][ci]
                if (plan["dedisp_method"] == "pallas"
                        and sb["kernel2"] is not None):
                    # (delays2 (ndev*R2, nsub), unpad (ndev*dm_chunk,))
                    sb_args = (
                        put_global(delays_h[anchor_rows], shard),
                        put_global(a2, shard),
                        put_global(a3, shard1),
                    )
                else:
                    sb_args = (
                        put_global(delays_h[anchor_rows], shard),
                        put_global(a2, shard1),
                        put_global(a3, shard),
                    )
            # per-chunk attribution: which DM rows this dispatch covers
            # and how many real (non-padding) trials it searches.  NB
            # the span closes at dispatch RETURN (execution is async by
            # design — double-buffering); the wait shows up in the
            # fetch span of the same chunk.
            live = [int(r) for r in rows if int(r) < ndm]
            n_trials_chunk = sum(len(acc_lists[r]) for r in live)
            # modelled per-chunk work: each live trial's search cost
            # plus each live row's dedisp + whiten (obs/costmodel.py)
            gflops = (getattr(self, "_per_trial_gflops", 0.0)
                      * n_trials_chunk
                      + getattr(self, "_per_dmrow_gflops", 0.0)
                      * len(live))
            with span(f"Chunked-Search-{ci}", chunk=int(ci),
                      n_dm_rows=len(live),
                      dm_lo=(float(self.dm_list[min(live)])
                             if live else None),
                      dm_hi=(float(self.dm_list[max(live)])
                             if live else None),
                      n_trials=n_trials_chunk,
                      gflops=round(gflops, 3)):
                args = (
                    *data_parts,
                    *sb_args,
                    put_global(delays_h[rows], shard),
                    put_global(accs_h[rows], shard),
                    *((put_global(jerks_h[rows], shard),)
                      if use_jerks else ()),
                    put_global(uidx_h[rows], shard),
                    d0_u, pos_u, step_u, birdies_d, widths_d,
                )
                if not arg_specs:
                    arg_specs.extend(
                        jax.ShapeDtypeStruct(a.shape, a.dtype,
                                             sharding=a.sharding)
                        for a in args)
                return program(*args)

        hw_count = 0  # observed high-waters for the tune sidecar
        hw_valid = 0
        row_hw = np.zeros(ndm, np.int64)  # per-DM-row max counts
        first_dispatch = True

        def dispatch_item(item):
            # the first dispatch triggers the (possibly minutes-long
            # remote) XLA compile; charge it separately from steady
            # -state dispatch latency.  The multi-GB filterbank h2d
            # transfer (async since _device_inputs_chunked) overlaps
            # the compile; the residual wait is charged to "upload" so
            # the first chunk's fetch time stays comparable to the rest
            nonlocal first_dispatch, tc
            if first_dispatch:
                first_dispatch = False
                out = dispatch(*item)
                phases["compile"] = time.time() - tc
                tc = time.time()
                # a computed scalar over every part proves the h2d
                # upload landed (device_put'ed arrays keep a host copy,
                # so np.asarray of them returns instantly).  The probe
                # queues behind chunk 1's execution, so "upload" here =
                # residual transfer after compile + one chunk's device
                # time; the multi-GB transfer dominates at production
                # scale
                with span("Upload-Wait"):
                    np.asarray(_parts_landed(*data_parts))
                phases["upload"] = time.time() - tc
                return out
            tp = time.time()
            out = dispatch(*item)
            phases["dispatch"] += time.time() - tp
            return out

        def retire_item(token, item):
            nonlocal hw_count, hw_valid
            ci, rows = item
            tp = time.time()
            with span("Chunk-Fetch", chunk=int(ci)) as sp_f:
                tf = time.time()
                packed = finish_fetch(token)
                # the fetch wait IS device (+link) time: the dispatch
                # span closed at async return, so the wait lands here
                sp_f.add_device_time(time.time() - tf)
            phases["fetch"] += time.time() - tp
            tp = time.time()
            with span("Peak-Decode", metric="peak_decode",
                      chunk=int(ci)):
                (groups_l, mx_count, mx_valid, counts_l,
                 clipped_l, _truncated_l) = self._decode_packed(
                    packed, dm_chunk, namax_p, nlevels, cap, compact_k
                )
            hw_count = max(hw_count, mx_count)
            # per-shard TRUE totals (uncapped counts), not nvalid: when
            # this run clipped, nvalid under-measures what an unclipped
            # re-run will ship
            hw_valid = max(hw_valid, int(
                counts_l.reshape(self.ndev, -1).sum(axis=1).max()
            ))
            row_max_l = counts_l.max(axis=(1, 2))
            for key in range(len(rows)):
                ii = int(rows[key])
                if ii < ndm:
                    row_hw[ii] = max(row_hw[ii], int(row_max_l[key]))
            phases["decode"] += time.time() - tp
            for key in clipped_l:
                ii = int(rows[key])
                if ii < ndm:
                    all_clipped[ii] = int(counts_l[key].max())
            if clipped_l and lineage.enabled():
                # the clipped rows' partial decodes are discarded here;
                # the post-loop escalated re-search emits fresh
                # ``decoded`` marks for each row
                with lineage.marking():
                    for key in clipped_l:
                        ii = int(rows[key])
                        grp = groups_l.get(key)
                        if ii < ndm and grp is not None and len(grp[0]):
                            lineage.mark(
                                "superseded", run=self._lineage_run(),
                                n=int(len(grp[0])),
                                stage="clip_rerun", dm_idx=ii)
            # (overlapping the escalated re-search compiles with the
            # remaining chunks via a background warm thread was tried
            # and REVERTED: the warm executable's arena co-resides with
            # the chunk program's ~3.5 GB arena and the filterbank, and
            # an allocation failure would abort the MAIN dispatches —
            # the exact co-residency the post-loop clear exists to
            # avoid — for a benefit within run-to-run compile-cache
            # noise)
            # one segmented native call distills every non-clipped row
            # of the chunk (rows with no peaks get an empty group)
            tp = time.time()
            with span("Distill", metric="distillation", chunk=int(ci),
                      stage="dm_rows", native=self._segmented_distill()):
                batch = self._distill_rows_batch(
                    (int(rows[key]), groups_l.get(key),
                     acc_lists[int(rows[key])],
                     None if jerk_lists is None
                     else jerk_lists[int(rows[key])])
                    for key in range(len(rows))
                    if int(rows[key]) < ndm and key not in clipped_l
                )
            n_new = 0
            for ii, cands_ii in batch.items():
                ckpt_done[ii] = cands_ii
                n_new += 1
            phases["distill"] += time.time() - tp
            tp = time.time()
            if ckpt:
                # cfg.checkpoint_interval counts DM rows (host-loop
                # cadence); tick once per completed row
                for _ in range(n_new):
                    ckpt.maybe_save(ckpt_done)
            phases["checkpoint"] += time.time() - tp
            if cfg.verbose:
                print(f"chunk {ci + 1}/{n_chunks} done "
                      f"({time.time() - t0:.0f}s; "
                      + " ".join(f"{p}={v:.1f}" for p, v in
                                 phases.items()) + ")", flush=True)

        # generalised double-buffer (ISSUE 11): at depth d the pipeline
        # keeps up to d chunk programs in flight, retiring the oldest
        # (fetch -> decode -> distill -> checkpoint, all host work)
        # only when the window is full — so host post-processing hides
        # behind device execution.  depth=2 reproduces the historical
        # dispatch(k+1)-then-fetch(k) interleave exactly; depth=1 is
        # the unpipelined A/B reference.  start_fetch begins the d2h
        # copy of each chunk's packed buffer the moment its program is
        # enqueued, so the link transfer overlaps the next dispatch.
        depth = max(1, int(getattr(cfg, "pipeline_depth", 2) or 1))
        METRICS.gauge("chunk.pipeline_depth", depth)
        DispatchPipeline(
            dispatch_item, retire_item, depth=depth,
            start_fetch=start_fetch,
        ).run(todo)

        tp = time.time()
        # TPU executables reserve their temp arenas at load time, and
        # the chunk program's (accel_block full-length spectra, ~3.5 GB
        # at 2^23) plus the resident filterbank once left too little
        # HBM for the later phases (observed RESOURCE_EXHAUSTED at
        # production scale).  The chunk executable is KEPT loaded for
        # the next search of this geometry only where no row is
        # re-searched and the fold phase at its full batch fits beside
        # the arena its own memory_analysis gives
        # (``_chunk_program_fits``); the fold's budget then leaves that
        # arena out (``retained_arena_bytes``), and the next search
        # that loads any other chunk program, or takes the fused path,
        # unloads it first (``release_kept_programs``).  Otherwise (at
        # HTRU width on one chip and on four: the full fold batch is
        # ~10 GB) OUR per-chunk executables are RELEASED here, before
        # the re-search and fold phases, and the next search reads
        # them again from the compile cache.  Fine-grained — every
        # other compiled program (fold, whiten, tutorial-scale paths)
        # survives.  clear_cache() on the jit object itself: the local
        # `program` / `dispatch` closure still hold the callable, so
        # dropping only the lru entry would leave the executable (and
        # its arena) alive.  (Program caches keyed on Mesh are safe
        # across equal meshes: jax interns Mesh instances.)
        arena = (None if all_clipped or not arg_specs
                 else _loaded_arena_bytes(program, arg_specs))
        if arena is not None and self._chunk_program_fits(arena):
            MeshPulsarSearch.kept_chunk_program = program
            self.retained_arena_bytes = arena
        else:
            import gc

            program.clear_cache()
            build_chunked_search.cache_clear()
            gc.collect()
        # cleanup (cache drop + full-heap gc, ~1 s on a big host heap)
        # is charged to its own phase: billing it to "research" made
        # clip-free runs look like they paid a re-search
        phases["cleanup"] = time.time() - tp
        tp = time.time()
        rerun = self._rerun_clipped_rows(
            set(all_clipped), all_clipped, self._fold_trials_provider,
        )
        for ii, cands_ii in rerun.items():
            ckpt_done[ii] = cands_ii
        # (the escalated-capacity re-search executables are freed by
        # _finalise itself before folding, for every driver)
        phases["research"] = time.time() - tp
        phases["n_clipped_rows"] = len(all_clipped)
        if cfg.tune_file and len(todo) == n_live:
            # record high-waters only when EVERY live chunk was
            # observed this run (a checkpoint resume sees a subset and
            # would understate them)
            save_tuning(cfg.tune_file, self._tune_scoped_key("chunked"),
                        hw_count, hw_valid, row_hw=row_hw)
            from ..search.tuning import record_run_calibration

            record_run_calibration(
                cfg.tune_file,
                research_s=(phases["research"] / len(all_clipped)
                            if all_clipped else None))
        # dedispersion is fused into the chunk dispatches; when stage
        # measurement is on, time one real dedisp-only dispatch and
        # scale by the number of chunks executed
        timers["dedispersion"] = 0.0
        if cfg.measure_stages and todo:
            rows0 = todo[0][1]
            # warm (compile) untimed, then time a steady-state dispatch
            warm = self._dedisperse_rows_device(
                delays_h[rows0], dm_tile=plan["dm_tile"])
            np.asarray(warm[:1, :1])
            tp = time.time()
            trials0 = self._dedisperse_rows_device(
                delays_h[rows0], dm_tile=plan["dm_tile"])
            np.asarray(trials0[:1, :1])
            timers["dedispersion"] = (time.time() - tp) * len(todo)
        timers.update({f"chunk_{p}": v for p, v in phases.items()})
        timers["searching_device"] = time.time() - t0
        # mirror the per-phase breakdown into the metrics registry;
        # dispatch/fetch/compile are time spent waiting on the device
        # (or the link to it) — the chunked driver's device share
        for p, v in phases.items():
            if isinstance(v, float):
                METRICS.observe(f"chunk_{p}", v)
        METRICS.observe(
            "chunked_search", timers["searching_device"],
            phases["dispatch"] + phases["fetch"] + phases["compile"],
        )
        for ii in range(ndm):
            dm_cands.append(ckpt_done.get(ii, []))
        if ckpt:
            ckpt.save(ckpt_done)
        timers["searching"] = time.time() - t0
        result = self._finalise(
            dm_cands, None, timers, t_total,
            trials_provider=self._fold_trials_provider,
        )
        if ckpt:
            ckpt.remove()
        return result

    def _chunk_program_fits(self, arena: int) -> bool:
        """True where the fold phase at its full batch (``npdmp``
        candidates) fits beside the filterbank, a loaded chunk program's
        ``arena`` bytes and the fold's trial rows, with the 2 GB reserve
        ``_finalise`` keeps (the same budget and coefficients as
        ``_finalise`` and ``fold_candidates``)."""
        from ..search.pipeline import fold_bytes_per_sample
        from ..search.plan import prev_power_of_two

        cfg = self.config
        rows = -(-max(cfg.npdmp, 1) // self.ndev) * self.ndev
        fold = (cfg.npdmp * fold_bytes_per_sample()
                * prev_power_of_two(self.out_nsamps))
        need = (self._data_bytes() + arena
                + 4 * rows * self.out_nsamps + (2 << 30) + fold)
        return need <= int(cfg.hbm_budget_gb * 1e9)

    def _decode_packed(self, packed, ndm_local, namax, nlevels, cap,
                       compact_k):
        """Host decode of the per-shard packed peak buffers into
        (per_dm_groups, max_count, max_nvalid).

        ``max_count`` / ``max_nvalid`` are the TRUE high-water marks
        (the device reports true above-threshold counts even when the
        fixed buffers clipped) — the callers re-run with escalated
        buffer sizes when they exceed capacity, so no candidate is
        ever silently dropped (the reference simply sizes its buffer
        at 100000, `peakfinder.hpp:17,61`)."""
        ndev = self.ndev
        nspec_local = ndm_local * namax * nlevels
        # layout: bin_hi | bin_lo | sel_snr | counts_hi | counts_lo |
        # delivered_hi | delivered_lo | nvalid_hi | nvalid_lo — every
        # int travels as two 16-bit halves in plain f32 (exact at any
        # int32 spectrum length), see _compact_peaks
        blk_len = 3 * compact_k + 4 * nspec_local + 2
        sel_bin = np.empty(ndev * compact_k, np.int64)
        sel_snr = np.empty(ndev * compact_k, np.float32)
        counts = np.empty((ndev * ndm_local, namax, nlevels), np.int64)
        delivered = np.empty(ndev * nspec_local, np.int64)
        nvalid = np.empty(ndev, np.int64)
        for sidx in range(ndev):
            blk = packed[sidx * blk_len : (sidx + 1) * blk_len]
            sel_bin[sidx * compact_k : (sidx + 1) * compact_k] = (
                blk[:compact_k].astype(np.int64) * 65536
                + blk[compact_k : 2 * compact_k].astype(np.int64)
            )
            sel_snr[sidx * compact_k : (sidx + 1) * compact_k] = (
                blk[2 * compact_k : 3 * compact_k]
            )
            c0 = 3 * compact_k
            counts[sidx * ndm_local : (sidx + 1) * ndm_local] = (
                blk[c0 : c0 + nspec_local].astype(np.int64) * 65536
                + blk[c0 + nspec_local : c0 + 2 * nspec_local]
                .astype(np.int64)
            ).reshape(ndm_local, namax, nlevels)
            c1 = c0 + 2 * nspec_local
            delivered[sidx * nspec_local : (sidx + 1) * nspec_local] = (
                blk[c1 : c1 + nspec_local].astype(np.int64) * 65536
                + blk[c1 + nspec_local : c1 + 2 * nspec_local]
                .astype(np.int64)
            )
            nvalid[sidx] = int(blk[-2]) * 65536 + int(blk[-1])

        # reconstruct each entry's (dm_local, accel, level) tag from
        # the per-spectrum DELIVERED counts (the device compaction
        # keeps valid slots in flat spectrum order, and delivered is
        # derived from the same buffers the scatter read — so the
        # segmentation can never desynchronise even if a device-side
        # extraction anomaly under-fills a buffer), then run the
        # unique-peak merge over ALL spectra in one native segmented
        # call per shard
        factors = np.array([b[2] for b in self.bounds])
        per_dm_groups: dict[int, tuple] = {}
        clipped_rows: set[int] = set()
        truncated_rows: set[int] = set()
        for s in range(ndev):
            shard_counts = counts[s * ndm_local : (s + 1) * ndm_local]
            expect = np.minimum(shard_counts, cap).reshape(-1)
            k = delivered[s * nspec_local : (s + 1) * nspec_local]
            seg_bounds = np.minimum(
                np.concatenate([[0], np.cumsum(k)]), compact_k
            )
            # rows whose slots ran past the compacted buffer (dropped
            # tail), whose per-spectrum buffers clipped, or whose
            # extraction under-delivered: re-searched by the caller on
            # the small host path.  The causes are tracked separately:
            # only TRUNCATION is fixable by regrowing compact_k (see
            # `_escalated`)
            truncated = np.cumsum(k) > compact_k
            over = (shard_counts > cap).any(axis=(1, 2))
            under = k < expect
            if under.any():
                warn_event(
                    "peak_underdelivery",
                    f"device peak extraction under-delivered on "
                    f"{int(under.sum())} spectra (shard {s}): got "
                    f"{int(k[under].sum())} of "
                    f"{int(expect[under].sum())} expected slots — "
                    f"re-searching the affected DM rows on the host "
                    f"path (this indicates a backend top-k anomaly "
                    f"worth reporting)",
                    n_spectra=int(under.sum()), shard=int(s),
                    got=int(k[under].sum()),
                    expected=int(expect[under].sum()),
                )
            for d in range(ndm_local):
                sl = slice(d * namax * nlevels, (d + 1) * namax * nlevels)
                if truncated[sl].any():
                    truncated_rows.add(s * ndm_local + d)
                if truncated[sl].any() or over[d] or under[sl].any():
                    clipped_rows.add(s * ndm_local + d)
            total = int(seg_bounds[-1])
            blk = slice(s * compact_k, s * compact_k + total)
            # device buffers are SNR-ordered (extract_top_peaks); the
            # merge walk needs ascending bin order within each segment
            seg_id = np.repeat(
                np.arange(len(seg_bounds) - 1), np.diff(seg_bounds)
            )
            order = np.lexsort((sel_bin[blk], seg_id))
            merged_bin, merged_snr, seg_counts = segmented_unique_peaks(
                sel_bin[blk][order], sel_snr[blk][order], seg_bounds
            )
            spec = np.repeat(
                np.arange(nspec_local, dtype=np.int64), seg_counts
            )
            lvl = spec % nlevels
            acc_i = (spec // nlevels) % namax
            dml = spec // (nlevels * namax)
            freqs = merged_bin * factors[lvl]
            for d in np.unique(dml):
                m = dml == d
                per_dm_groups[int(s * ndm_local + d)] = (
                    freqs[m], merged_snr[m], acc_i[m], lvl[m]
                )
        return (per_dm_groups, int(counts.max(initial=0)),
                int(nvalid.max()), counts, clipped_rows, truncated_rows)

    def _rerun_clipped_rows(self, clipped_rows, counts, trials_provider):
        """Re-search DM rows whose peak buffers clipped, on the small
        host-loop path with a capacity sized to their true counts.

        Replaces the old escalate-and-redispatch design: the whole
        fused/chunked program would otherwise be recompiled and
        re-executed for a handful of RFI-loud rows (and large per-trial
        top_k capacities inside the big program crash the v5e
        backend).  Returns {dm_idx: distilled candidates}.
        """
        ndm = len(self.dm_list)
        rows = sorted(ii for ii in clipped_rows if ii < ndm)
        if not rows:
            return {}
        warn_event(
            "capacity_escalation",
            f"peak buffers clipped on {len(rows)} DM trial(s); "
            f"re-searching those rows with escalated capacity",
            n_rows=len(rows), rows=rows[:64],
        )
        # NOTE: a one-dispatch batched re-search (an escalated-capacity
        # chunk program over all clipped rows) was tried and REVERTED:
        # its fresh program shape cost a ~550 s remote compile at
        # production scale, more than the whole per-row loop below
        # (130-240 s, dominated by 1-2 search_accel_chunk compiles
        # shared across rows with equal escalated capacity).
        trials_sel, row_map = trials_provider(rows)

        def row_max_of(ii):
            # ``counts`` maps row -> max above-threshold count (or an
            # array indexable by row on the fused path)
            row_max = counts[ii]
            if not np.isscalar(row_max) and not isinstance(row_max, int):
                row_max = int(np.asarray(row_max).max())
            return int(row_max)

        # ONE shared escalated capacity across every clipped row: each
        # distinct capacity is a fresh search_accel_chunk compile
        # (~15-25 s through the remote compiler) while the extra top_k
        # slots cost milliseconds — per-row capacities measured 170 s
        # for 10 rows at production scale, mostly compiles
        cap2 = 1 << int(np.ceil(np.log2(max(
            max(row_max_of(ii) for ii in rows),
            self.config.peak_capacity) + 1)))
        out = {}
        for ii in rows:
            tim = self._trial_tim(trials_sel, row_map[ii])
            # narrow accel batches: at production scale the replicated
            # filterbank already occupies most of HBM, and escalated
            # capacities widen every per-trial buffer (a 16-wide batch
            # OOM'd on v5e with 8.6 GB of data resident)
            out[ii] = self._search_tim(tim, ii, start_capacity=cap2,
                                       accel_chunk=4)
        return out

    @staticmethod
    def _escalated(cap, compact_k, max_count, max_nvalid, total_slots,
                   n_truncated, ndm):
        """Next (capacity, compact_k) after a compacted-buffer
        overflow, or None.

        Per-spectrum capacity is NEVER escalated here (clipped rows are
        re-searched individually, `_rerun_clipped_rows`); the shared
        compacted buffer is only regrown when so many rows TRUNCATED
        by it (over-capacity rows would stay clipped regardless of
        compact_k) that per-row re-runs would cost more than
        recompiling the dispatch."""
        if (max_nvalid > compact_k and compact_k < total_slots
                and n_truncated > max(4, ndm // 4)):
            new_ck = int(min(
                total_slots, 1 << int(np.ceil(np.log2(max_nvalid)))
            ))
            warn_event(
                "compact_buffer_escalation",
                f"compacted peak buffer truncated {n_truncated} rows "
                f"({max_nvalid}/{compact_k}); re-running with "
                f"compact_capacity={new_ck}",
                n_truncated=int(n_truncated), max_nvalid=int(max_nvalid),
                compact_k=int(compact_k), new_compact_k=new_ck,
            )
            return cap, new_ck
        return None

    def run(self) -> SearchResult:
        import time

        from ..obs.compilation import set_compile_context
        from ..obs.metrics import install_compile_hook

        install_compile_hook()
        # compile attribution (ISSUE 18): ledger every backend compile
        # this run triggers against its search geometry
        set_compile_context(
            program="mesh.search",
            geometry={"nchans": int(self.fil.nchans),
                      "nbits": int(self.fil.header.nbits),
                      "size": int(self.size),
                      "out_nsamps": int(self.out_nsamps),
                      "n_dm": len(self.dm_list)})
        cfg = self.config
        timers: dict[str, float] = {}
        t_total = time.time()
        # duty-cycle ledger origin: _finalise sums device seconds of
        # every span recorded from here on (ISSUE 11)
        self._span_cursor0 = span_cursor()
        METRICS.gauge("hbm.data_bytes", self._data_bytes())
        METRICS.gauge("search.n_dm_trials", len(self.dm_list))
        METRICS.gauge("search.fft_size", self.size)
        METRICS.gauge("search.n_devices", self.ndev)

        ndm = len(self.dm_list)

        # checkpoint resume: the mesh search is a single dispatch, so a
        # complete checkpoint skips the device program entirely (trials
        # are re-dedispersed only if folding needs them)
        ckpt, ckpt_done = self._make_checkpoint()
        if ckpt and len(ckpt_done) == ndm:
            timers["dedispersion"] = 0.0
            timers["searching"] = 0.0
            dm_cands = CandidateCollection()
            for ii in range(ndm):
                dm_cands.append(ckpt_done[ii])
            # a production-scale resume must not fall back to full
            # trial materialisation: honour the bounded-HBM plan
            acc_lists = [
                self.acc_plan.generate_accel_list(dm)
                for dm in self.dm_list
            ]
            acc_lists, jerk_lists = self._trial_lists(acc_lists)
            namax = max(len(a) for a in acc_lists)
            plan = self._plan_chunking(namax) if cfg.npdmp > 0 else None
            if plan is not None:
                self._chunk_plan = plan
                self._device_inputs_chunked(plan, acc_lists, jerk_lists)
                result = self._finalise(
                    dm_cands, None, timers, t_total,
                    trials_provider=self._fold_trials_provider,
                )
            else:
                # fused fold (ISSUE 11): instead of materialising every
                # DM row's trial HBM-resident just to fold a handful of
                # candidates, _finalise hands the candidate DM set to
                # _fused_fold_provider, whose program dedisperses ONLY
                # those rows and folds them in the same dispatch — the
                # (ndm, out_nsamps) trials array never exists and the
                # only device->host traffic is the folded profiles
                result = self._finalise(
                    dm_cands, None, timers, t_total,
                    fold_fuser=self._fused_fold_provider,
                )
            ckpt.remove()
            return result
        ndm_p = self._padded_trial_count()
        ndev = self.ndev
        ndm_local = ndm_p // ndev
        acc_lists = [
            self.acc_plan.generate_accel_list(dm) for dm in self.dm_list
        ]
        # jerk axis (ISSUE 13): from here on acc_lists are the COMBINED
        # (accel, jerk) per-DM trial lists — identical objects when the
        # plan is jerk-free — so the padded grid, HBM budget, cost
        # model and dispatch attribution all scale with the full
        # trial product without further special-casing
        acc_lists, jerk_lists = self._trial_lists(acc_lists)
        namax = max(len(a) for a in acc_lists)
        n_trials_total = sum(len(a) for a in acc_lists)
        from ..obs.costmodel import record_run_costs

        run_costs = record_run_costs(self, acc_lists)["stages"]

        plan = self._plan_chunking(namax)
        if plan is not None:
            if cfg.verbose:
                print(
                    f"chunked search: dm_chunk={plan['dm_chunk']} "
                    f"accel_block={plan['accel_block']} "
                    f"dedisp={plan['dedisp_method']}"
                )
            return self._run_chunked(
                plan, acc_lists, namax, timers, t_total, ckpt,
                ckpt_done, jerk_lists,
            )
        # the fused program plans for no arena an earlier chunked
        # search kept loaded
        release_kept_programs()
        if cfg.subband_dedisp != "never":
            warn_event(
                "path_fallback",
                "subband_dedisp is ignored on the fused (small-input) "
                "mesh path: its one-dispatch program keeps the exact "
                "direct sweep, which is already cheap at this scale; "
                "the chunked production driver and --single_device "
                "honour it",
                what="subband_dedisp", path="fused",
            )
        nlevels = cfg.nharmonics + 1
        # Pallas-kernel dedispersion inside the fused program: needs DM
        # rows divisible by dm_tile per shard, so the row padding
        # widens before the device inputs are built
        dd_pallas = self._plan_fused_pallas_dedisp()
        if dd_pallas is not None:
            ndm_p = dd_pallas["ndm_p"]
            ndm_local = ndm_p // ndev
        METRICS.inc("dedisp.method_pallas" if dd_pallas is not None
                    else "dedisp.method_scan")
        # capacity auto-tune: a previous run on this object observed the
        # true per-spectrum high-water count, so later runs shrink the
        # per-spectrum top_k (its cost scales with k on v5e); overflow
        # stays impossible — clipped rows are re-searched with escalated
        # capacity like any other overflow
        from ..search.tuning import load_tuning, round_up, save_tuning

        if cfg.tune_file and getattr(self, "_cap_hint", None) is None:
            # cross-RUN seeding of the same hints (search/tuning.py)
            tune = load_tuning(cfg.tune_file, self._tune_scoped_key("fused"))
            if tune is not None:
                self._cap_hint = round_up(tune["cap_hw"] + 32, 64, 64,
                                          cfg.peak_capacity)
                self._ck_hint = round_up(int(tune["ck_hw"] * 1.1), 4096,
                                         4096, cfg.compact_capacity)
        cap = min(cfg.peak_capacity,
                  getattr(self, "_cap_hint", cfg.peak_capacity))
        # clamp to the shard's total slot count (small configs); a
        # previous run's true valid-peak count also tightens the
        # compacted buffer (every shipped megabyte is d2h time)
        compact_k = min(
            cfg.compact_capacity, ndm_local * namax * nlevels * cap,
            getattr(self, "_ck_hint", cfg.compact_capacity),
        )

        t0 = time.time()
        inputs = self._device_inputs(acc_lists, ndm_p, namax, jerk_lists)
        cap0 = cap
        self.record_peaks_selection(cap)

        def make_program(capacity, ck):
            return self._fused_program(capacity, ck, dd_pallas)

        METRICS.inc("runs.mesh_fused")
        while True:
            program = make_program(cap, compact_k)
            # modelled work of everything fused into this one dispatch
            # (dedispersion + whiten + per-trial spectra/harmonics/
            # peaks) so the trace slice reads as achieved Gflop/s
            fused_gflops = sum(
                run_costs[s].flops
                for s in ("dedisperse", "spectrum", "harmonics", "peaks")
            ) / 1e9
            with span("Fused-Search", metric="fused_search",
                      n_dm_trials=ndm, n_trials=int(n_trials_total),
                      dm_lo=float(self.dm_list[0]),
                      dm_hi=float(self.dm_list[-1]),
                      capacity=int(cap), compact_k=int(compact_k),
                      hbm_budget_bytes=float(cfg.hbm_budget_gb * 1e9),
                      gflops=round(fused_gflops, 3),
                      ) as sp:
                packed, trials = program(*inputs)
                # ONE gather over ICI/DCN -> host; ``trials`` stays on
                # device for the folding phase.  The fetch wait is the
                # device (plus link) share of this stage's wall-clock.
                # start_fetch begins the d2h copy the moment XLA
                # finishes the packed buffer, so the link transfer
                # overlaps whatever Python does before the blocking
                # finish (depth=1 A/B keeps the old synchronous fetch)
                if getattr(cfg, "pipeline_depth", 2) > 1:
                    start_fetch(packed)
                tf = time.time()
                packed = finish_fetch(packed)
                sp.add_device_time(time.time() - tf)
            with span("Peak-Decode", metric="peak_decode"):
                (per_dm_groups, mx_count, mx_valid, counts_arr,
                 clipped, truncated) = self._decode_packed(
                    packed, ndm_local, namax, nlevels, cap, compact_k
                )
            nxt = self._escalated(
                cap, compact_k, mx_count, mx_valid,
                ndm_local * namax * nlevels * cap,
                len(truncated), ndm,
            )
            if nxt is None:
                break
            if lineage.enabled():
                # the escalated re-dispatch discards this pass's decode
                # wholesale — its peaks never received candidate ids,
                # so the ledger carries an AGGREGATE count only (the
                # re-run emits fresh ``decoded`` marks)
                n_disc = sum(
                    len(g[0]) for ii, g in per_dm_groups.items()
                    if ii < ndm)
                if n_disc:
                    with lineage.marking():
                        lineage.mark("superseded", run=self._lineage_run(),
                                     n=n_disc, stage="redispatch")
            cap, compact_k = nxt
        rerun = self._rerun_clipped_rows(
            clipped, counts_arr,
            lambda rows: (trials, {ii: ii for ii in rows}),
        )
        if rerun and lineage.enabled():
            # clipped rows' partial decodes are discarded in favour of
            # the escalated host re-search (which emits its own
            # ``decoded`` marks via process_dm_peaks)
            lrun = self._lineage_run()
            with lineage.marking():
                for ii in sorted(rerun):
                    grp = per_dm_groups.get(ii)
                    if grp is not None and len(grp[0]):
                        lineage.mark("superseded", run=lrun,
                                     n=int(len(grp[0])),
                                     stage="clip_rerun", dm_idx=int(ii))
        if cfg.dump_dir:
            # debug buffer dumps work here because the fused path keeps
            # every dedispersed trial HBM-resident (the chunked driver
            # cannot; it warns instead)
            from ..search.pipeline import dump_whiten_stages

            for ii in range(ndm):
                dump_whiten_stages(
                    cfg.dump_dir, ii, self._trial_tim(trials, ii),
                    jnp.asarray(self.birdies), jnp.asarray(self.bwidths),
                    self.bin_width, cfg.boundary_5_freq,
                    cfg.boundary_25_freq, bool(len(self.birdies)),
                )
        # record the observed high-waters for the NEXT run's buffer
        # sizes (margins — +32 counts, x1.1 valid peaks — keep
        # same-data reruns from ever clipping; different data falls
        # back to the usual re-search/escalation paths)
        # multiple-of-64, not power-of-two: top_k/approx_max_k accept
        # any k and their cost scales with it, so the tightest safe
        # capacity wins (same arithmetic as the tune-file seeding above)
        hint = round_up(mx_count + 32, 64, 64, cfg.peak_capacity)
        ck_hint = round_up(int(mx_valid * 1.1), 4096, 4096,
                           cfg.compact_capacity)
        retune = (hint != getattr(self, "_cap_hint", None)
                  or ck_hint < getattr(self, "_ck_hint", 1 << 62))
        warm_shapes = None
        if retune:
            self._cap_hint = hint
            self._ck_hint = ck_hint
            new_ck = min(ck_hint, ndm_local * namax * nlevels * hint)
            if hint < cap0 or new_ck < compact_k:
                warm_shapes = (hint, new_ck)
        if cfg.tune_file:
            # true per-shard totals (see _run_chunked's hw_valid note)
            save_tuning(
                cfg.tune_file, self._tune_scoped_key("fused"), mx_count,
                int(counts_arr.reshape(self.ndev, -1).sum(axis=1).max()),
            )
            from ..search.tuning import record_run_calibration

            record_run_calibration(cfg.tune_file)
        timers["dedispersion"] = 0.0  # fused into the search program
        if cfg.measure_stages:
            timers["dedispersion"] = self.measure_dedispersion_stage()
        # sub-span of "searching" (which covers device + host decode)
        timers["searching_device"] = time.time() - t0
        dm_cands = CandidateCollection()
        ckpt_done = {}
        with span("Distill", metric="distillation", n_dm_trials=ndm,
                  stage="dm_rows", native=self._segmented_distill()):
            batch = self._distill_rows_batch(
                (ii, per_dm_groups.get(ii), acc_lists[ii],
                 None if jerk_lists is None else jerk_lists[ii])
                for ii in range(ndm) if ii not in rerun
            )
        for ii in range(ndm):
            cands_ii = rerun[ii] if ii in rerun else batch[ii]
            ckpt_done[ii] = cands_ii
            dm_cands.append(cands_ii)
        if ckpt:
            ckpt.save(ckpt_done)
        timers["searching"] = time.time() - t0
        result = self._finalise(dm_cands, trials, timers, t_total)
        if warm_shapes is not None and getattr(self, "prewarm_tuned",
                                               False):
            # pre-compile + warm the tuned program AFTER all timed
            # stages, so a later run on this object pays neither
            # compile nor jit-cache miss.  Opt-in (bench.py's repeated
            # -run pattern): a one-shot CLI run would pay an extra
            # compile and a duplicate search execution for nothing.
            wp, _wt = make_program(*warm_shapes)(*inputs)
            np.asarray(wp[-1:])  # sync: don't queue ahead of next run
        if ckpt:
            ckpt.remove()
        return result

    # -- batched multi-observation dispatch (ISSUE 9) --------------------

    def _fused_program(self, capacity, ck, dd_pallas, batch: int = 1):
        """The fused one-dispatch program for this search's geometry
        (shared by ``run`` and ``run_batch``; lru-cached by shape)."""
        cfg = self.config
        return build_fused_search(
            self.mesh,
            nbits=self.fil.header.nbits,
            nchans=self.fil.nchans,
            nsamps=self.fil.nsamps,
            out_nsamps=self.out_nsamps,
            size=self.size,
            bin_width=self.bin_width,
            tsamp=float(self.fil.tsamp),
            nharms=cfg.nharmonics,
            bounds=self.bounds,
            capacity=capacity,
            min_snr=cfg.min_snr,
            b5=cfg.boundary_5_freq,
            b25=cfg.boundary_25_freq,
            use_zap=bool(len(self.birdies)),
            use_killmask=self.killmask is not None,
            compact_k=ck,
            max_shift=self.max_shift,
            block=self.resample_block,
            dedisp_pallas=(
                dd_pallas["params"] if dd_pallas is not None else None
            ),
            lattice=self.lattice,
            use_jerks=self._legacy_jerks(),
            peaks_methods=self.peaks_methods_for(capacity),
            compact_method=self.compact_method_for(ck),
            batch=batch,
        )

    def _spawn(self, fil, cfg):
        return MeshPulsarSearch(fil, cfg, mesh=self.mesh)

    def _pack_raw(self, fil) -> np.ndarray:
        if fil.header.nbits == 32:  # float data: nothing to pack
            return np.ascontiguousarray(fil.data, np.float32).ravel()
        return pack_bits(fil.data.ravel(), fil.header.nbits)

    def run_batch(self, fils, configs=None) -> list:
        """ONE fused dispatch over B same-bucket observations.

        The per-dispatch fixed costs (compile lookup + two ~0.1 s
        host<->device round trips) dominate fused-search wall-clock, so
        stacking B beams into one ``(B, ...)`` program is a near-linear
        ``jobs_per_hour`` multiplier for survey drains (ROADMAP open
        item 2).  Per-beam semantics are preserved exactly: the batched
        program unrolls the B=1 body per beam (bit-identical HLO),
        decode/rerun/distill/checkpoint/finalise run per beam, and a
        beam whose post-processing fails returns its exception in its
        result slot without touching its batch-mates.  Falls back to
        the sequential base implementation when the bounded-HBM
        chunked plan is active or every beam is a checkpoint resume.
        """
        import time

        from ..obs.metrics import install_compile_hook

        B = len(fils)
        configs = ([self.config] * B if configs is None
                   else list(configs))
        if B == 1:
            return super().run_batch(fils, configs)
        self._assert_batch_compatible(fils)
        install_compile_hook()
        cfg = self.config
        ndm = len(self.dm_list)
        acc_lists = [
            self.acc_plan.generate_accel_list(dm) for dm in self.dm_list
        ]
        # combined (accel, jerk) trial lists, as in run()
        acc_lists, jerk_lists = self._trial_lists(acc_lists)
        namax = max(len(a) for a in acc_lists)
        n_trials_total = sum(len(a) for a in acc_lists)
        plan = self._plan_chunking(namax)
        if plan is not None:
            # production-scale chunked path has no batch axis (its HBM
            # budget is already saturated by ONE observation): run the
            # beams sequentially rather than refuse
            warn_event(
                "batch_fallback",
                "bounded-HBM chunked plan active: batched dispatch "
                "falls back to sequential per-beam runs",
                batch=B, path="chunked",
            )
            return super().run_batch(fils, configs)
        # per-beam checkpoints: complete resumes skip decode/distill
        # for that beam (mirrors run()'s all-done short-circuit)
        ckpts, resumed = [], {}
        for b in range(B):
            ck_b, done_b = self._make_checkpoint(fils[b], configs[b])
            ckpts.append(ck_b)
            if ck_b and len(done_b) == ndm:
                resumed[b] = done_b
        live = [b for b in range(B) if b not in resumed]
        if not live:
            # nothing left to search; sequential resumes also handle
            # the npdmp>0 re-dedisperse correctly
            return super().run_batch(fils, configs)

        timers: dict[str, float] = {}
        t_total = time.time()
        self._span_cursor0 = span_cursor()  # duty-cycle ledger origin
        METRICS.gauge("search.n_dm_trials", ndm)
        METRICS.gauge("search.fft_size", self.size)
        METRICS.gauge("search.n_devices", self.ndev)
        METRICS.gauge("search.batch", B)
        ndm_p = self._padded_trial_count()
        ndev = self.ndev
        nlevels = cfg.nharmonics + 1
        from ..obs.costmodel import record_run_costs

        run_costs = record_run_costs(self, acc_lists, batch=B)["stages"]
        dd_pallas = self._plan_fused_pallas_dedisp()
        if dd_pallas is not None:
            ndm_p = dd_pallas["ndm_p"]
        ndm_local = ndm_p // ndev
        from ..search.tuning import load_tuning, round_up, save_tuning

        # capacity/compaction tuning is per BEAM (every beam compacts
        # its own buffer), so the B=1 hints and sidecar cells apply
        # unchanged — see search/tuning.py "Batch axis" note
        if cfg.tune_file and getattr(self, "_cap_hint", None) is None:
            tune = load_tuning(cfg.tune_file,
                               self._tune_scoped_key("fused"))
            if tune is not None:
                self._cap_hint = round_up(tune["cap_hw"] + 32, 64, 64,
                                          cfg.peak_capacity)
                self._ck_hint = round_up(int(tune["ck_hw"] * 1.1), 4096,
                                         4096, cfg.compact_capacity)
        cap = min(cfg.peak_capacity,
                  getattr(self, "_cap_hint", cfg.peak_capacity))
        compact_k = min(
            cfg.compact_capacity, ndm_local * namax * nlevels * cap,
            getattr(self, "_ck_hint", cfg.compact_capacity),
        )
        t0 = time.time()
        inputs = self._device_inputs(acc_lists, ndm_p, namax, jerk_lists)
        raw_B = np.stack([self._pack_raw(f) for f in fils])
        inputs = (put_global(raw_B, NamedSharding(self.mesh, P())),
                  ) + tuple(inputs[1:])
        self.record_peaks_selection(cap)
        METRICS.inc("runs.mesh_fused")
        METRICS.inc("runs.mesh_fused_batched")
        beam_fail: dict[int, BaseException] = {}
        decoded: dict[int, tuple] = {}
        while True:
            program = self._fused_program(cap, compact_k, dd_pallas,
                                          batch=B)
            fused_gflops = sum(
                run_costs[s].flops
                for s in ("dedisperse", "spectrum", "harmonics", "peaks")
            ) / 1e9
            with span("Fused-Search", metric="fused_search",
                      batch=B, n_dm_trials=ndm,
                      n_trials=int(n_trials_total),
                      dm_lo=float(self.dm_list[0]),
                      dm_hi=float(self.dm_list[-1]),
                      capacity=int(cap), compact_k=int(compact_k),
                      hbm_budget_bytes=float(cfg.hbm_budget_gb * 1e9),
                      gflops=round(fused_gflops, 3),
                      ) as sp:
                packed, trials = program(*inputs)
                if getattr(cfg, "pipeline_depth", 2) > 1:
                    start_fetch(packed)  # d2h overlaps host-side prep
                tf = time.time()
                # (B, ndev*blk_len): row b IS the B=1 packed layout
                packed = finish_fetch(packed)
                sp.add_device_time(time.time() - tf)
            beam_fail, decoded = {}, {}
            with span("Peak-Decode", metric="peak_decode", batch=B):
                for b in live:
                    try:
                        decoded[b] = self._decode_packed(
                            packed[b], ndm_local, namax, nlevels, cap,
                            compact_k,
                        )
                    except Exception as exc:  # beam-fatal, mates live on
                        beam_fail[b] = exc
            if not decoded:
                break
            mx_count = max(d[1] for d in decoded.values())
            mx_valid = max(d[2] for d in decoded.values())
            n_trunc = max(len(d[5]) for d in decoded.values())
            nxt = self._escalated(
                cap, compact_k, mx_count, mx_valid,
                ndm_local * namax * nlevels * cap, n_trunc, ndm,
            )
            if nxt is None:
                break
            if lineage.enabled():
                # escalated re-dispatch discards every live beam's
                # decode; aggregate supersession per beam, attributed
                # to that beam's run id (see run()'s fused-path note)
                with lineage.marking():
                    for b in decoded:
                        n_disc = sum(
                            len(g[0])
                            for ii, g in decoded[b][0].items() if ii < ndm)
                        if n_disc:
                            lineage.mark(
                                "superseded",
                                run=getattr(configs[b], "lineage_run", ""),
                                n=n_disc, stage="redispatch")
            cap, compact_k = nxt
        # per-beam clipped-row re-searches on that beam's trials
        reruns: dict[int, dict] = {}
        for b in list(decoded):
            try:
                _g, _mc, _mv, counts_b, clipped_b, _t = decoded[b]
                trials_b = trials[b]
                # host-path re-search marks (decoded/absorbed) must
                # carry THIS beam's run id, not the driver config's
                self._lineage_run_override = getattr(
                    configs[b], "lineage_run", "")
                try:
                    reruns[b] = self._rerun_clipped_rows(
                        clipped_b, counts_b,
                        lambda rows, _t=trials_b: (
                            _t, {ii: ii for ii in rows}),
                    )
                finally:
                    self._lineage_run_override = ""
                if reruns[b] and lineage.enabled():
                    lrun_b = getattr(configs[b], "lineage_run", "")
                    with lineage.marking():
                        for ii in sorted(reruns[b]):
                            grp = decoded[b][0].get(ii)
                            if grp is not None and len(grp[0]):
                                lineage.mark(
                                    "superseded", run=lrun_b,
                                    n=int(len(grp[0])),
                                    stage="clip_rerun", dm_idx=int(ii))
            except Exception as exc:
                beam_fail[b] = exc
                decoded.pop(b)
        if decoded:
            # observed high-waters tighten the NEXT dispatch's buffers;
            # max over beams — a per-beam quantity, so B=1 and batched
            # runs feed the same hints/sidecar cells (B-invariance)
            mx_count = max(d[1] for d in decoded.values())
            mx_valid = max(d[2] for d in decoded.values())
            self._cap_hint = round_up(mx_count + 32, 64, 64,
                                      cfg.peak_capacity)
            ck_hint = round_up(int(mx_valid * 1.1), 4096, 4096,
                               cfg.compact_capacity)
            if ck_hint < getattr(self, "_ck_hint", 1 << 62):
                self._ck_hint = ck_hint
            if cfg.tune_file:
                hw_valid = max(
                    int(d[3].reshape(self.ndev, -1).sum(axis=1).max())
                    for d in decoded.values()
                )
                save_tuning(cfg.tune_file,
                            self._tune_scoped_key("fused"),
                            mx_count, hw_valid)
                from ..search.tuning import record_run_calibration

                record_run_calibration(cfg.tune_file)
        timers["dedispersion"] = 0.0  # fused into the search program
        timers["searching_device"] = time.time() - t0
        # ONE segmented distill across every live beam: (beam, dm) keys
        # keep the segments per-beam, so cross-beam absorption is
        # structurally impossible
        with span("Distill", metric="distillation",
                  n_dm_trials=ndm * max(len(decoded), 1), batch=B,
                  stage="dm_rows", native=self._segmented_distill()):
            distilled = self._distill_rows_batch(
                (((b, ii), decoded[b][0].get(ii), acc_lists[ii],
                  None if jerk_lists is None else jerk_lists[ii])
                 for b in decoded for ii in range(ndm)
                 if ii not in reruns[b]),
                dm_of=lambda k: k[1],
                run_of=lambda k: getattr(
                    configs[k[0]], "lineage_run", ""),
            )
        timers["searching"] = time.time() - t0
        # fan results back out per beam; a beam that fails here keeps
        # its exception in its own slot (checkpoints of batch-mates are
        # untouched — each beam has its own checkpoint file/key)
        results: list = [None] * B
        for b in range(B):
            if b in beam_fail:
                results[b] = beam_fail[b]
                continue
            try:
                dm_cands = CandidateCollection()
                ckpt_done = {}
                if b in resumed:
                    for ii in range(ndm):
                        dm_cands.append(resumed[b][ii])
                else:
                    rerun_b = reruns[b]
                    for ii in range(ndm):
                        cands_ii = (rerun_b[ii] if ii in rerun_b
                                    else distilled[(b, ii)])
                        ckpt_done[ii] = cands_ii
                        dm_cands.append(cands_ii)
                    if ckpts[b]:
                        ckpts[b].save(ckpt_done)
                # folding inputs are per-beam: never share the cache
                self._fold_input_cache = FoldInputCache()
                results[b] = self._finalise(
                    dm_cands, trials[b], dict(timers), t_total,
                    config=configs[b],
                )
                if ckpts[b]:
                    ckpts[b].remove()
            except Exception as exc:  # per-beam failure isolation
                results[b] = exc
        self.last_dispatch_batched = True
        return results
