"""Plain reference search: the candidate table the program should
write, worked out from the observation alone.

Straightforward ``jax.numpy`` at float32 (matmuls at ``highest``), no
kernels or chunking, and nothing imported from the program.  Each DM
row's work runs on one device, its acceleration trials in blocks; only
a block's above-threshold bins come back to the host, which merges and
distils them.  It follows peasoup's published semantics stage by stage:

* dedispersion: ``tim[t] = sum_c x[t + delay_c, c]`` with dedisp's
  integer delays (its float32 table, ``synth.delay_table``), the
  first ``size`` samples (``size`` the largest power of two strictly
  below the sample count; a shorter trial is padded with its mean);
* whitening: rfft, amplitude spectrum, running median (median of 5,
  25 and 125 bins, stretched linearly and spliced at 0.05 and 0.5 Hz),
  division of the Fourier series by it with bins 0-4 zeroed, birdies
  zapped to 1, the interbinned spectrum's mean and standard deviation,
  irfft;
* acceleration: the search resamples with ``rint(i + i*af*(i - n))``,
  the fold with ``rint(i + af*((i - n/2)^2 - (n/2)^2))``,
  ``af = a*tsamp/(2c)``, indices worked out in float64 on the host
  (the search's as shifts ``index - i``, selected on the device);
* spectrum: rfft, interbinning ``sqrt(max(|X_k|^2, |X_k - X_{k-1}|^2/2))``,
  normalised by the whitening statistics; harmonic level ``k`` adds the
  spectrum at ``(i*m + 2^(k-1)) >> k`` for the odd ``m < 2^k`` and
  scales by ``2^(-k/2)``;
* peaks: bins above ``min_snr`` in each level's searched range, merged
  when within 30 bins of the best so far;
* distillation: harmonic within each acceleration trial, acceleration
  across them, then DM and harmonic across DM trials;
* fold: 16 sub-integrations x 64 phase bins, each bin divided by its
  count plus one, optimised over 64 linear phase drifts and 63 boxcar
  widths, S/N from on/off-pulse statistics.

``dtype`` is the precision the stages are carried in.  ``bfloat16`` is
the control: every stage's output (and the dedispersion sum) is held in
bfloat16; the FFTs, which have no bfloat16 form, run at float32 on
bfloat16 inputs and round their outputs back.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from perfbench.synth import delay_table

SPEED_OF_LIGHT = 299792458.0
NBINS, NINTS = 64, 16


def generate_dm_list(dm_start, dm_end, tsamp, pulse_width_us, fch1, foff,
                     nchans, tol) -> np.ndarray:
    """dedisp's tolerance-stepped DM grid (float64 steps, float32 values)."""
    dt_us = tsamp * 1e6
    f_ghz = (fch1 + (nchans / 2 - 0.5) * foff) * 1e-3
    tol2 = tol * tol
    a2 = (8.3 * foff / f_ghz ** 3) ** 2
    b2 = a2 * float(nchans) ** 2 / 16.0
    c = (dt_us * dt_us + pulse_width_us ** 2) * (tol2 - 1.0)
    dms = [np.float32(dm_start)]
    while dms[-1] < dm_end:
        p = float(dms[-1])
        k = c + tol2 * a2 * p * p
        dms.append(np.float32(
            (b2 * p + np.sqrt(-a2 * b2 * p * p + (b2 + a2) * k)) / (b2 + a2)))
    return np.array(dms, np.float32)


def _q(x, dtype):
    """Hold ``x`` in ``dtype`` (complex: each part), returned at f32."""
    if dtype == jnp.float32:
        return x
    if jnp.iscomplexobj(x):
        return jax.lax.complex(_q(x.real, dtype), _q(x.imag, dtype))
    return x.astype(dtype).astype(jnp.float32)


@partial(jax.jit, static_argnames=("size", "nbits", "dtype"))
def dedisperse(rows, delays, *, size, nbits, dtype=jnp.float32):
    """One DM trial.  ``rows`` is the payload transposed to
    (bytes per sample, nsamps): row ``c // spb`` holds channel ``c`` at
    bit offset ``nbits * (c % spb)``."""
    spb = 8 // nbits
    mask = (1 << nbits) - 1

    def add(c, acc):
        row = jax.lax.dynamic_index_in_dim(rows, c // spb, keepdims=False)
        seg = jax.lax.dynamic_slice(row, (delays[c],), (size,))
        shift = (nbits * (c % spb)).astype(jnp.uint8)
        val = ((seg >> shift) & mask).astype(dtype)
        return (acc + val).astype(dtype)

    acc = jax.lax.fori_loop(0, delays.shape[0], add, jnp.zeros(size, dtype))
    return acc.astype(jnp.float32)


def _median5(x):
    n5 = x.shape[0] // 5
    return jnp.sort(x[: n5 * 5].reshape(n5, 5), axis=1)[:, 2]


def _stretch(x, n_out):
    step = jnp.float32(x.shape[0] - 1) / jnp.float32(n_out - 1)
    xi = jnp.arange(n_out, dtype=jnp.float32) * step
    j = xi.astype(jnp.int32)
    frac = xi - j.astype(jnp.float32)
    base = x[j]
    nxt = jnp.concatenate([x[1:], x[-1:]])[j]
    return jnp.where(frac > 1e-5, base + frac * (nxt - base), base)


def _interbin(fs):
    prev = jnp.concatenate([jnp.zeros((1,), fs.dtype), fs[:-1]])
    a = fs.real ** 2 + fs.imag ** 2
    d = 0.5 * ((fs.real - prev.real) ** 2 + (fs.imag - prev.imag) ** 2)
    return jnp.sqrt(jnp.maximum(a, d))


@partial(jax.jit, static_argnames=("bin_width", "dtype"))
def whiten(tim, zap, *, bin_width, dtype=jnp.float32):
    """Whitened series and the interbinned spectrum's mean and std;
    the bins ``zap`` marks are set to 1 after dereddening."""
    n = tim.shape[0]
    fs = _q(jnp.fft.rfft(tim), dtype)
    amp = _q(jnp.sqrt(fs.real ** 2 + fs.imag ** 2), dtype)
    m5 = _median5(amp)
    m25 = _median5(m5)
    m125 = _median5(m25)
    k = jnp.arange(amp.shape[0])
    med = jnp.where(k < int(0.05 / bin_width), _stretch(m5, amp.shape[0]),
                    jnp.where(k < int(0.5 / bin_width),
                              _stretch(m25, amp.shape[0]),
                              _stretch(m125, amp.shape[0])))
    fs = _q(jnp.where(k < 5, 0.0, fs / _q(med, dtype)), dtype)
    fs = jnp.where(zap, jnp.complex64(1.0), fs)
    spec = _q(_interbin(fs), dtype)
    mean = jnp.sum(spec) / spec.shape[0]
    rms = jnp.sqrt(jnp.sum(spec * spec) / spec.shape[0])
    std = jnp.sqrt(rms * rms - mean * mean)
    return _q(jnp.fft.irfft(fs, n=n), dtype), mean, std


def resample(tim, shift, width: int):
    """``tim[i + shift[i]]`` for ``|shift| <= width``, by selecting among
    the ``2 width + 1`` shifted copies (exact, and no gather)."""
    n = tim.shape[0]
    pad = jnp.pad(tim, width)
    out = jnp.zeros_like(tim)
    for s in range(-width, width + 1):
        out = jnp.where(shift == s, pad[width + s: width + s + n], out)
    return out


LANES = 128


def _harmonic_read(k: int, m: int):
    """Level ``k``'s read of numerator ``m`` in the (rows, 128) view.

    Output ``i = 128 R + l`` reads ``(i m + 2^(k-1)) >> k``.  With
    ``P = 2^k``, ``S = 128 m / P`` (whole for ``k <= 7``) and
    ``R = P t + r`` that is ``128 (m t + q_r) + b_r + c_l``, where
    ``q_r, b_r = divmod(S r, 128)``, ``c_l = (l m + 2^(k-1)) >> k`` and
    ``b_r + c_l < 256``.  So output rows ``P t + r`` are rows
    ``m t + q_r`` of the view of adjacent row pairs (256 lanes), with
    the fixed lane selection ``sel[r]``.  Returns ``q`` and ``sel``."""
    p, half = 1 << k, 1 << (k - 1)
    step = LANES * m // p
    lanes = np.arange(LANES)
    c = (lanes * m + half) >> k
    sel = np.zeros((p, 2 * LANES, LANES), np.float32)
    for r in range(p):
        sel[r, step * r % LANES + c, lanes] = 1.0
    return [step * r // LANES for r in range(p)], sel


def harmonic_levels(spec, nh: int, dtype=jnp.float32):
    """Levels ``0..nh`` of the incremental harmonic sum, (nh + 1, size)
    in ``dtype``: level ``k`` adds the spectrum at
    ``min((i*m + 2^(k-1)) >> k, size - 1)`` for the odd ``m < 2^k``, in
    that order, to level ``k - 1``'s unscaled sum, and is scaled by
    ``2^(-k/2)``.  Each stretched read is a strided row slice and a 0/1
    lane selection (``_harmonic_read``; an einsum at ``highest``, which is
    exact), not a gather; the spectrum's last value stands past its
    end, as the clip says."""
    size = spec.shape[0]
    pmax = 1 << nh
    rows = -(-size // (LANES * pmax)) * pmax
    # reads reach row m t + q_r + 1 < rows + pmax + 1
    x = jnp.pad(spec.astype(dtype), (0, (rows + pmax + 2) * LANES - size),
                mode="edge")
    tiles = x.reshape(-1, LANES)
    pairs = jnp.concatenate([tiles[:-1], tiles[1:]], axis=1).astype(jnp.float32)
    val = tiles[:rows]
    out = [x[:size]]
    for k in range(1, nh + 1):
        p = 1 << k
        for m in range(1, p, 2):
            q, sel = _harmonic_read(k, m)
            src = jnp.stack([pairs[q[r]::m][: rows // p] for r in range(p)])
            got = jnp.einsum("ptc,pcl->tpl", src, sel,
                             precision=jax.lax.Precision.HIGHEST)
            val = (val + got.reshape(rows, LANES).astype(dtype)).astype(dtype)
        scale = jnp.asarray(2.0 ** (-k / 2), dtype)
        out.append((val.reshape(-1)[:size] * scale).astype(dtype))
    return jnp.stack(out)


@partial(jax.jit, static_argnames=("nh", "width", "capacity", "dtype"))
def peaks(tim_w, shifts, mean, std, bounds, thresh, *, nh, width, capacity,
          dtype=jnp.float32):
    """The above-threshold bins of a block of one DM row's acceleration
    trials.  Per trial (a row of ``shifts``): the normalised interbinned
    spectrum of the resampled series, its harmonic levels, and the bins
    of each level ``k`` in ``bounds[k]`` above ``thresh``.  Returns how
    many there are, and the first ``capacity`` of them in (trial, level,
    bin) order: their flat positions and S/N."""

    def trial(shift):
        fs = _q(jnp.fft.rfft(resample(tim_w, shift, width)), dtype)
        spec = _q((_q(_interbin(fs), dtype) - mean) / std, dtype)
        return harmonic_levels(spec, nh, dtype)

    lv = jax.vmap(trial)(shifts)
    b = jnp.arange(lv.shape[-1])
    hit = ((lv > thresh.astype(dtype)) & (b >= bounds[:, :1])
           & (b < bounds[:, 1:])).reshape(-1)
    csum = jnp.cumsum(hit, dtype=jnp.int32)
    # the j-th hit is where the running count first reaches j
    pos = jnp.searchsorted(csum, jnp.arange(1, capacity + 1, dtype=jnp.int32))
    pos = jnp.minimum(pos, hit.shape[0] - 1)
    return csum[-1], pos, lv.reshape(-1)[pos].astype(jnp.float32)


def accel_list(cfg: dict, obs: dict, dm: float, size: int) -> np.ndarray:
    """peasoup's acceleration trials at ``dm``, float32 as there.  With
    ``acc_step``, the serial driver's fixed grid (``pipeline.cpp:287``):
    ``acc_start`` in steps below ``acc_end``.  Otherwise the tolerance
    grid (``utils.hpp:140-193``): zero first where neither end is zero,
    then ``acc_start`` in steps of the smearing-limited ``alt_a`` below
    ``acc_end``, then ``acc_end``; the pulse width is given in ns
    (divided by 1e3), the DM smear uses one channel's width."""
    f32 = np.float32
    lo, hi = f32(cfg.get("acc_start", 0.0)), f32(cfg.get("acc_end", 0.0))
    if lo == hi:
        return np.zeros(1, np.float32)
    if cfg.get("acc_step"):
        out, acc = [], lo
        while acc < hi:
            out.append(acc)
            acc = f32(acc + f32(cfg["acc_step"]))
        return np.array(out, np.float32)
    nch, foff = obs["nchans"], obs["foff"]
    cfreq = obs["fch1"] + (foff * nch / 2.0 if foff < 0 else -foff * nch / 2.0)
    bw, tsamp = f32(abs(foff)), f32(obs["tsamp"])
    tol = f32(cfg.get("acc_tol", 1.1))
    pw = f32(cfg.get("acc_pulse_width", 64.0)) / f32(1.0e3)
    tobs = f32(size) * tsamp
    tdm = f32((8.3 * float(bw) / float(f32(cfreq)) ** 3 * float(dm)) ** 2)
    w_us = f32(np.sqrt(f32(tdm + pw * pw + tsamp * tsamp)))
    alt_a = f32(2.0 * float(w_us) * 1.0e-6 * 24.0 * SPEED_OF_LIGHT
                / float(tobs) / float(tobs)
                * np.sqrt(float(tol) * float(tol) - 1.0))
    out = [f32(0.0)] if (hi != 0 and lo != 0) else []
    acc = lo
    while acc < hi:
        out.append(acc)
        acc = f32(acc + alt_a)
    out.append(hi)
    return np.array(out, np.float32)


def search_bounds(size: int, tsamp: float, nh: int, min_freq: float,
                  max_freq: float) -> tuple[int, int, float]:
    """Searched bins ``[start, stop)`` of level ``nh`` and the factor
    from a bin to the fundamental frequency (``peakfinder.hpp:77-94``);
    ``size`` is the transform length."""
    nb = size // 2 + 1
    bin_width = 1.0 / (size * tsamp)
    nyquist = bin_width * nb
    start = int(2.0 * (nb - 1.0) * (min_freq / nyquist) * 2.0 ** nh)
    stop = min(nb, int((max_freq / bin_width) * 2.0 ** nh))
    return start, stop, 1.0 / nb * nyquist / 2.0 ** nh


def unique_peaks(idx: np.ndarray, snr: np.ndarray, min_gap: int = 30):
    """peasoup's peak merge (``peakfinder.hpp:27-56``): walking the
    above-threshold bins in order, a peak keeps the bins that follow
    within ``min_gap`` of its best bin so far, and reports the best."""
    out_i, out_s = [], []
    k, n = 0, len(idx)
    while k < n:
        best, best_i = snr[k], idx[k]
        last = idx[k]
        k += 1
        while k < n and idx[k] - last < min_gap:
            if snr[k] > best:
                best, best_i, last = snr[k], idx[k], idx[k]
            k += 1
        out_i.append(int(best_i))
        out_s.append(float(best))
    return out_i, out_s


def distill(cands: list[dict], kind: str, tol: float, *, max_harm: int = 16,
            fractional: bool = True, tobs: float = 0.0) -> list[dict]:
    """peasoup's greedy distillers (``distiller.hpp``): sorted by S/N
    (stable), each surviving candidate absorbs the later ones it
    matches.  ``harmonic``: a frequency ratio ``k f / (j f0)`` within
    ``tol`` of 1, ``j <= max_harm``, ``k <= 2^nh`` of the later one
    (``k = 1`` unless ``fractional``); ``accel``: a frequency inside the
    fundamental's Doppler window for the two accelerations, widened by
    ``tol``; ``dm``: a frequency ratio within ``tol`` of 1."""
    cands = sorted(cands, key=lambda c: -c["snr"])
    n = len(cands)
    f = np.array([c["freq"] for c in cands], np.float64)
    acc = np.array([c["acc"] for c in cands], np.float64)
    den = np.array([2 ** c["nh"] if fractional else 1 for c in cands])
    jj = np.arange(1, max_harm + 1, dtype=np.float64)
    kk = np.arange(1, int(den.max(initial=1)) + 1, dtype=np.float64)
    keep = np.ones(n, bool)
    for i in range(n):
        if not keep[i]:
            continue
        fl = f[i + 1:]
        if kind == "harmonic":
            r = kk[None, :, None] * fl[:, None, None] / (jj[None, None, :] * f[i])
            ok = (r > 1 - tol) & (r < 1 + tol)
            ok &= kk[None, :, None] <= den[i + 1:, None, None]
            hit = ok.any(axis=(1, 2))
        elif kind == "accel":
            shifted = f[i] + (acc[i] - acc[i + 1:]) * f[i] * (tobs / SPEED_OF_LIGHT)
            edge = f[i] * tol
            hit = ((fl > np.minimum(shifted, f[i]) - edge)
                   & (fl < np.maximum(shifted, f[i]) + edge))
        else:
            r = fl / f[i]
            hit = (r > 1 - tol) & (r < 1 + tol)
        keep[i + 1:] &= ~hit
    return [c for c, k in zip(cands, keep) if k]


def search_index(n: int, accel: float, tsamp: float) -> np.ndarray:
    af = np.float64(np.float32(accel)) * tsamp / (2.0 * SPEED_OF_LIGHT)
    i = np.arange(n, dtype=np.float64)
    return np.clip(np.rint(i + i * af * (i - n)), 0, n - 1).astype(np.int32)


def fold_index(n: int, accel: float, tsamp: float) -> np.ndarray:
    af = np.float64(np.float32(accel)) * tsamp / (2.0 * SPEED_OF_LIGHT)
    i = np.arange(n, dtype=np.float64)
    h = n / 2.0
    return np.clip(np.rint(i + af * ((i - h) ** 2 - h * h)), 0,
                   n - 1).astype(np.int32)


def phase_bins(n: int, period: float, tsamp: float) -> np.ndarray:
    nper = n // NINTS
    tbp = np.float64(tsamp) / np.float64(np.float32(period))
    ph = np.arange(nper * NINTS, dtype=np.float64) * tbp
    b = np.floor((ph - np.floor(ph)) * NBINS).astype(np.int32)
    return b + (np.arange(b.size) // nper).astype(np.int32) * NBINS


@partial(jax.jit, static_argnames=("dtype",))
def _fold(tim_w, idx, flat, *, dtype=jnp.float32):
    x = tim_w[idx][: flat.shape[0]]
    sums = jax.ops.segment_sum(x, flat, num_segments=NINTS * NBINS)
    counts = jax.ops.segment_sum(jnp.ones_like(x), flat,
                                 num_segments=NINTS * NBINS)
    prof = _q(sums / (counts + 1.0), dtype).reshape(NINTS, NBINS)
    fsub = _q(jnp.fft.fft(prof.astype(jnp.complex64), axis=1), dtype)
    b = jnp.arange(NBINS, dtype=jnp.float32)
    ramp = b * (2.0 * np.float32(np.pi)) / NBINS
    ramp = jnp.where(b > NBINS // 2, ramp - 2.0 * np.float32(np.pi), ramp)
    shifts = jnp.arange(NBINS, dtype=jnp.float32) - NBINS // 2
    m = jnp.arange(NINTS, dtype=jnp.float32)
    amount = (m[None, :] / NINTS) * shifts[:, None]  # (shift, subint)
    phase = -ramp[None, None, :] * amount[:, :, None]
    prof_s = _q(jnp.sum(fsub[None] * jnp.exp(1j * phase), axis=1), dtype)
    w = jnp.arange(NBINS - 1)
    tmpl = jnp.fft.fft((b[None, :] <= w[:, None]).astype(jnp.complex64), axis=1)
    final = (prof_s[None] * tmpl[:, None, :]
             / jnp.sqrt(w.astype(jnp.float32) + 1.0)[:, None, None])
    final = final.at[:, :, 0].set(0.0)
    best = jnp.argmax(jnp.abs(jnp.fft.ifft(final, axis=2)).reshape(-1))
    shift = (best // NBINS) % NBINS
    return best, _q(jnp.real(jnp.fft.ifft(prof_s[shift])), dtype)


def _on_off_snr(prof: np.ndarray, bin_: int, width: int) -> float:
    prof = prof.astype(np.float64)
    edge = int(width * 0.3 + 0.5)
    half = int(width / 2.0 + 0.5)
    r = np.roll(prof, NBINS // 2 - bin_)
    sel = np.arange(NBINS)
    centre = NBINS // 2 - 1
    hi, lo = centre + half + edge, centre - half - edge
    on, off = r[(sel <= hi) & (sel >= lo)], r[(sel > hi) | (sel < lo)]
    std = np.sqrt(((off - off.mean()) ** 2).mean())
    with np.errstate(divide="ignore", invalid="ignore"):
        sn1 = (on.mean() - off.mean()) * np.sqrt(width) / std
        sn2 = ((r - off.mean()) / std).sum() / np.sqrt(width)
    sn = [s if np.isfinite(s) and s <= 99999 else 0.0 for s in (sn1, sn2)]
    return float(max(sn))


def folded_snr(tim_w, accel, period, tsamp, dtype=jnp.float32) -> float:
    n = tim_w.shape[0]
    best, prof = _fold(tim_w, jnp.asarray(fold_index(n, accel, tsamp)),
                       jnp.asarray(phase_bins(n, period, tsamp)), dtype=dtype)
    best = int(best)
    width = best // (NBINS * NBINS)
    return _on_off_snr(np.asarray(prof), best % NBINS - width // 2, width)


#: resampled samples a block of trials holds at most
BLOCK_SAMPLES = 1 << 24
#: hits a block returns before it is redone at a larger capacity
CAPACITY = 1 << 14


class Reference:
    """The reference over one observation, on the devices.

    ``payload`` is the observation's packed samples, (nsamps, bytes per
    sample); ``max_dm`` the top of the DM grid, which sets the trial
    length ``nsamps - max_delay``; ``birdies`` the (frequency, width)
    pairs zapped.  A trial shorter than the FFT is padded with its
    mean; the fold takes the power of two strictly below the trial
    length.  Whitened series are kept per (DM, length, device), so each
    is worked out once.

    ``devices`` (the first device if not given) each hold a copy of the
    payload, and DM row ``i`` is searched on device ``i mod n``; folds
    run on the first.  ``capacity`` is how many hits a block of trials
    returns before it is redone at a larger capacity."""

    def __init__(self, obs: dict, payload: np.ndarray, max_dm: float,
                 dtype=jnp.float32, birdies=(), devices=None,
                 capacity: int = CAPACITY):
        self.obs, self.dtype = obs, dtype
        self.npdt = np.dtype(dtype).type  # numpy's (ml_dtypes') scalar type
        self.size = _prev_pow2(obs["nsamps"])
        self.table = delay_table(obs["nchans"], obs["tsamp"], obs["fch1"],
                                 obs["foff"])
        max_delay = int(np.float32(max_dm) * self.table[-1] + 0.5)
        self.out = obs["nsamps"] - max_delay
        self.fold_n = _prev_pow2(self.out)
        self.devices = list(devices or jax.devices()[:1])
        self.rows = {d: jax.device_put(payload, d).T for d in self.devices}
        self.birdies = np.asarray(birdies, np.float32).reshape(-1, 2)
        self.capacity = capacity
        self._white, self._folded = {}, {}
        self._resample, self._shifts = {}, {}

    def zap_mask(self, n: int) -> np.ndarray:
        """Bins ``[floor((f - w)/bw), ceil((f + w)/bw))`` of each birdie,
        float32 as ``birdiezapper.hpp``, clamped to the spectrum."""
        nb = n // 2 + 1
        bw = np.float32(1.0 / (n * self.obs["tsamp"]))
        mask = np.zeros(nb, bool)
        for f, w in self.birdies:
            lo = int(np.floor((f - w) / bw))
            hi = min(int(np.ceil((f + w) / bw)), nb - 1)
            mask[max(lo, 0):hi] = True
        return mask

    def whitened(self, dm: float, n: int, zap: bool = True, device=None):
        """Whitened first ``n`` samples of the trial (padded to ``n``
        with its mean where the trial is shorter), on ``device`` (the
        first if not given); the fold's whitening zaps nothing
        (``folder.hpp:382-389``)."""
        device = device or self.devices[0]
        key = (np.float32(dm), n, zap, device)
        if key not in self._white:
            delays = np.floor(key[0] * self.table + np.float32(0.5)).astype(np.int32)
            tim = dedisperse(self.rows[device], jax.device_put(delays, device),
                             size=min(n, self.out), nbits=self.obs["nbits"],
                             dtype=self.dtype)
            if n > self.out:
                tim = jnp.concatenate(
                    [tim, jnp.full(n - self.out, jnp.mean(tim), tim.dtype)])
            mask = self.zap_mask(n) if zap else np.zeros(n // 2 + 1, bool)
            self._white[key] = whiten(tim, jax.device_put(mask, device),
                                      bin_width=1.0 / (n * self.obs["tsamp"]),
                                      dtype=self.dtype)
        return self._white[key]

    def shifts(self, accs, device):
        """The search's resampling of the trials ``accs`` as shifts
        ``index - i``, (len(accs), size) on ``device``, and the widest."""
        key = tuple(np.float32(a) for a in accs)
        if key not in self._resample:
            i = np.arange(self.size)
            d = np.stack([search_index(self.size, float(a), self.obs["tsamp"])
                          - i for a in accs])
            width = int(np.abs(d).max())
            self._resample[key] = (d.astype(np.min_scalar_type(-width - 1)),
                                   width)
        d, width = self._resample[key]
        if (key, device) not in self._shifts:
            self._shifts[key, device] = jax.device_put(d, device)
        return self._shifts[key, device], width

    def search(self, dms, cfg: dict) -> list[dict]:
        """The distilled candidates of the whole search, strongest first,
        at most ``limit``: per DM and acceleration the harmonic levels'
        peaks above ``min_snr``, merged; harmonic distillation within
        each acceleration trial, acceleration distillation across them;
        then DM and (whole-number) harmonic distillation across DMs.
        Every row's device work is dispatched before any is fetched."""
        n, tsamp = self.size, self.obs["tsamp"]
        tol, nh = cfg.get("freq_tol", 1e-4), cfg.get("nharmonics", 4)
        max_harm = cfg.get("max_harm", 16)
        bounds = [search_bounds(n, tsamp, k, cfg.get("min_freq", 0.1),
                                cfg.get("max_freq", 1100.0))
                  for k in range(nh + 1)]
        lims = np.array([b[:2] for b in bounds], np.int32)
        thresh = np.float32(self.npdt(cfg.get("min_snr", 9.0)))
        per = max(1, BLOCK_SAMPLES // n)
        rows = []
        for ii, dm in enumerate(np.asarray(dms, np.float32)):
            device = self.devices[ii % len(self.devices)]
            tim_w, mean, std = self.whitened(dm, n, device=device)
            accs = accel_list(cfg, self.obs, float(dm), n)
            blocks = []
            for j in range(0, len(accs), per):
                shifts, width = self.shifts(accs[j: j + per], device)
                call = partial(peaks, tim_w, shifts, mean, std, lims, thresh,
                               nh=nh, width=width, dtype=self.dtype)
                blocks.append((accs[j: j + per], call,
                               call(capacity=self.capacity)))
            rows.append((ii, dm, blocks))
        found = []
        for ii, dm, blocks in rows:
            row = []
            for accs, call, out in blocks:
                for acc, levels in zip(accs, self._hits(call, out, len(accs),
                                                        nh + 1, n // 2 + 1)):
                    cands = []
                    for k, (hit, snr) in enumerate(levels):
                        for b, sn in zip(*unique_peaks(hit, snr)):
                            cands.append(dict(dm=float(dm), dm_idx=ii,
                                              acc=float(acc), nh=k, snr=sn,
                                              freq=b * bounds[k][2]))
                    row += distill(cands, "harmonic", tol, max_harm=max_harm)
            found += distill(row, "accel", tol, tobs=n * tsamp)
        out = distill(found, "dm", tol)
        out = distill(out, "harmonic", tol, max_harm=max_harm,
                      fractional=False)
        return out[: cfg.get("limit", 1000)]

    def _hits(self, call, out, ntrials: int, nlevels: int, nbins: int):
        """A block's hits, fetched, as (bins, S/N) per trial and level;
        the block is redone where its hits overflow the capacity."""
        count = int(out[0])
        if count > self.capacity:
            out = call(capacity=1 << (count - 1).bit_length())
        pos = np.asarray(out[1])[:count]
        snr = np.asarray(out[2])[:count]
        # hits come in (trial, level, bin) order: cut them per spectrum
        cuts = np.searchsorted(pos // nbins, np.arange(ntrials * nlevels + 1))
        spans = list(zip(cuts[:-1], cuts[1:]))
        return [[(pos[a:b] % nbins, snr[a:b])
                 for a, b in spans[t * nlevels: (t + 1) * nlevels]]
                for t in range(ntrials)]

    def folded_snr(self, dm, accel, period) -> float:
        key = (np.float32(dm), np.float32(accel), period)
        if key not in self._folded:
            tim_w, _, _ = self.whitened(dm, self.fold_n, zap=False)
            self._folded[key] = folded_snr(tim_w, accel, period,
                                           self.obs["tsamp"], self.dtype)
        return self._folded[key]


def _prev_pow2(n: int) -> int:
    p = 1
    while 2 * p < n:
        p *= 2
    return p
