"""Candidate distillation (de-duplication) passes.

Host-side greedy SNR-sorted dedup, exact semantics of
`include/transforms/distiller.hpp:16-197`:

* ``BaseDistiller.distill``: sort by SNR descending; walk the survivors
  in order, letting each "fundamental" absorb (mark non-unique, and
  optionally append to its ``assoc`` list) everything its match
  predicate hits further down the list.  Like the reference,
  already-absorbed candidates are still tested (and may be appended to
  several fundamentals' ``assoc`` lists).
* ``HarmonicDistiller``: absorbs candidates whose frequency is a
  (fractional, up to 2^nh denominators) harmonic ratio of the
  fundamental within tolerance.
* ``AccelerationDistiller``: absorbs candidates whose frequency lies
  within the Doppler drift window f*da*tobs/c of the fundamental.
* ``DMDistiller``: absorbs candidates with matching frequency ratio
  regardless of DM.

The O(n^2) pair predicates are vectorised over the trailing candidates
(the reference's inner loops, `distiller.hpp:69-197`, are per-pair).

Distillers are strictly per-observation: every pass runs over ONE
SearchResult's candidates.  Batched multi-observation dispatch
(ISSUE 9) preserves this — the driver keys its batched distillation
rows by ``(beam, dm_idx)`` so each beam's candidates flow through
separate native segments, and a fundamental in one beam can never
absorb a harmonic from a batch-mate.  Cross-OBSERVATION matching is a
different operation with different semantics (position/epoch aware)
and lives in the survey layer (``serve/store.py``'s coincidence
queries), not here.
"""

from __future__ import annotations

import numpy as np

from ..data.candidates import Candidate
from ..obs import lineage

SPEED_OF_LIGHT = 299792458.0


def _native_lib():
    try:
        from ..native import lib
    except Exception:
        return None
    return lib


# -- tolerance-margin helpers (candidate lineage, ISSUE 19) ----------------
# How far inside its distiller's acceptance window an absorbed
# candidate sat (>= 0; ~0 means a borderline absorption a slightly
# tighter tolerance would have kept).  Shared by the per-object
# ``pair_margin`` methods below and the mesh driver's segmented batch
# path, so both report identical margins for identical pairs.

def harmonic_margin(f_fund: float, f_abs: float, max_denom: int,
                    tol: float, max_harm: int) -> float:
    """tol minus the closest |k*f_abs/(j*f_fund) - 1| over the (j, k)
    ratio grid the harmonic predicate searched."""
    jj = np.arange(1, max(int(max_harm), 1) + 1, dtype=np.float64)
    kk = np.arange(1, max(int(max_denom), 1) + 1, dtype=np.float64)
    ratio = kk[:, None] * float(f_abs) / (jj[None, :] * float(f_fund))
    return float(tol - np.abs(ratio - 1.0).min())


def drift_margin(f_fund: float, f_abs: float, drift: float,
                 tol: float) -> float:
    """Distance of ``f_abs`` from the nearer edge of the drift window
    [min(f, f+drift*f) - tol*f, max(f, f+drift*f) + tol*f], as a
    fraction of ``f_fund`` (the accel/jerk window shape)."""
    f0 = float(f_fund)
    shifted = f0 + float(drift) * f0
    edge = f0 * float(tol)
    lo = min(shifted, f0) - edge
    hi = max(shifted, f0) + edge
    return float(min(float(f_abs) - lo, hi - float(f_abs)) / f0)


def dm_margin(f_fund: float, f_abs: float, tol: float) -> float:
    """tol minus |f_abs/f_fund - 1| (the DM distiller's freq-ratio
    window)."""
    return float(tol - abs(float(f_abs) / float(f_fund) - 1.0))


class BaseDistiller:
    #: native predicate id for distill_greedy, or None (numpy path only)
    native_type: int | None = None

    #: lineage rule name stamped on absorption decisions (ISSUE 19)
    rule = "distill"

    def __init__(self, keep_related: bool):
        self.keep_related = keep_related

    def matches(self, idx: int) -> np.ndarray:
        """Bool array over candidates idx+1.. that this fundamental
        absorbs."""
        raise NotImplementedError

    def pair_margin(self, fi: int, ai: int) -> float:
        """Tolerance margin of the (fundamental ``fi``, absorbed
        ``ai``) pair — how far inside the acceptance window the
        absorption sat.  Valid after :meth:`setup`; indices are into
        the SNR-sorted candidate order."""
        return 0.0

    def match_counts(self, idx: int) -> np.ndarray:
        """Int array over candidates idx+1..: how many times each is
        absorbed (the reference appends one assoc entry per matching
        predicate combination — only >1 for the harmonic distiller's
        (j,k) grid, `distiller.hpp:91-100`)."""
        return self.matches(idx).astype(np.int64)

    def setup(self, cands: list[Candidate]) -> None:
        self.freqs = np.array([c.freq for c in cands], np.float64)

    def native_args(self) -> tuple:
        """(aux_array, max_harm, tobs_over_c) for distill_greedy."""
        raise NotImplementedError

    def distill(self, cands: list[Candidate],
                on_decision=None) -> list[Candidate]:
        """Greedy SNR-sorted dedup; survivors in sorted order.

        ``on_decision(fundamental, absorbed, margin)`` — the lineage
        callback (ISSUE 19) — fires once per absorbed candidate, for
        its FIRST (highest-SNR) absorber, with the pair's tolerance
        margin, after the walk and in walk order, all under one
        ``Lineage`` span.  Purely observational: candidate output
        (uniqueness AND assoc lists) is bit-identical with or without
        it.
        """
        size = len(cands)
        # std::sort with snr-greater comparator; stable for determinism
        cands = sorted(cands, key=lambda c: -c.snr)
        self.setup(cands)
        native = _native_lib() if self.native_type is not None else None
        if native is not None:
            aux, max_harm, tobs_over_c = self.native_args()
            # pair recording only feeds assoc/lineage; uniqueness is
            # independent of the flag (native/distill.c), so asking
            # for pairs never changes the survivors
            record = self.keep_related or on_decision is not None
            unique, pf, pa = native.distill_greedy(
                self.native_type, self.freqs, aux, self.tolerance,
                max_harm, tobs_over_c, record,
            )
            if self.keep_related:
                for fi, ai in zip(pf, pa):
                    cands[fi].append(cands[ai])
            pairs = zip(pf, pa)
        else:
            unique = np.ones(size, dtype=bool)
            pairs = []
            for idx in range(size):
                if not unique[idx]:
                    continue
                counts = self.match_counts(idx)
                hit = np.nonzero(counts)[0] + idx + 1
                if self.keep_related:
                    for ii in hit:
                        for _ in range(int(counts[ii - idx - 1])):
                            cands[idx].append(cands[ii])
                if on_decision is not None:
                    pairs.extend((idx, ii) for ii in hit if unique[ii])
                unique[hit] = False
        if on_decision is not None:
            with lineage.marking():
                seen: set[int] = set()  # pairs are in walk order:
                for fi, ai in pairs:  # first absorber wins
                    if ai not in seen:
                        seen.add(ai)
                        on_decision(cands[fi], cands[ai],
                                    self.pair_margin(int(fi), int(ai)))
        return [cands[i] for i in range(size) if unique[i]]


class HarmonicDistiller(BaseDistiller):
    native_type = 0
    rule = "harmonic"

    def __init__(self, tol: float, max_harm: int, keep_related: bool,
                 fractional_harms: bool = True):
        super().__init__(keep_related)
        self.tolerance = tol
        self.max_harm = int(max_harm)
        self.fractional_harms = fractional_harms

    def native_args(self):
        return self.max_denoms.astype(np.float64), self.max_harm, 0.0

    def setup(self, cands):
        super().setup(cands)
        if self.fractional_harms:
            self.max_denoms = np.array(
                [int(2.0 ** c.nh) for c in cands], np.int64
            )
            kmax = int(self.max_denoms.max(initial=1))
        else:
            self.max_denoms = np.ones(len(cands), np.int64)
            kmax = 1
        self.jj = np.arange(1, self.max_harm + 1, dtype=np.float64)
        self.kk = np.arange(1, kmax + 1, dtype=np.float64)

    def _ok_grid(self, idx):
        fundi_freq = self.freqs[idx]
        freqs = self.freqs[idx + 1 :]
        # ratio[i, k, j] = kk[k] * f_i / (jj[j] * f0)
        ratio = (
            self.kk[None, :, None]
            * freqs[:, None, None]
            / (self.jj[None, None, :] * fundi_freq)
        )
        ok = (ratio > 1 - self.tolerance) & (ratio < 1 + self.tolerance)
        ok &= self.kk[None, :, None] <= self.max_denoms[idx + 1 :, None, None]
        return ok

    def matches(self, idx):
        return self._ok_grid(idx).any(axis=(1, 2))

    def match_counts(self, idx):
        # one absorption per matching (j,k), like distiller.hpp:91-100
        return self._ok_grid(idx).sum(axis=(1, 2))

    def pair_margin(self, fi, ai):
        return harmonic_margin(self.freqs[fi], self.freqs[ai],
                               int(self.max_denoms[ai]),
                               self.tolerance, self.max_harm)


class AccelerationDistiller(BaseDistiller):
    native_type = 1
    rule = "accel"

    def __init__(self, tobs: float, tolerance: float, keep_related: bool):
        super().__init__(keep_related)
        self.tobs = tobs
        self.tobs_over_c = tobs / SPEED_OF_LIGHT
        self.tolerance = tolerance

    def native_args(self):
        return self.accs, 0, self.tobs_over_c

    def setup(self, cands):
        super().setup(cands)
        self.accs = np.array([c.acc for c in cands], np.float64)

    def matches(self, idx):
        fundi_freq = self.freqs[idx]
        freqs = self.freqs[idx + 1 :]
        delta_acc = self.accs[idx] - self.accs[idx + 1 :]
        acc_freq = fundi_freq + delta_acc * fundi_freq * self.tobs_over_c
        edge = fundi_freq * self.tolerance
        lo = np.minimum(acc_freq, fundi_freq) - edge
        hi = np.maximum(acc_freq, fundi_freq) + edge
        return (freqs > lo) & (freqs < hi)

    def pair_margin(self, fi, ai):
        drift = (self.accs[fi] - self.accs[ai]) * self.tobs_over_c
        return drift_margin(self.freqs[fi], self.freqs[ai], drift,
                            self.tolerance)


class JerkDistiller(BaseDistiller):
    """Jerk-adjacent de-dup (ISSUE 13): the jerk-axis analogue of
    :class:`AccelerationDistiller`.  A jerk mismatch dj smears a
    signal's apparent frequency by up to f*|dj|*tobs^2/(6c) over the
    observation (the cubic resample term's peak fractional shift), so
    a fundamental absorbs candidates whose frequency sits inside that
    drift window plus the usual tolerance edge.  Runs only when the
    search has >1 jerk trial — accel-only runs never construct it, so
    their distillation chain is untouched.  Python-vectorised only
    (no native predicate id; jerk grids are small)."""

    native_type = None
    rule = "jerk"

    def __init__(self, tobs: float, tolerance: float, keep_related: bool):
        super().__init__(keep_related)
        self.tobs = tobs
        self.tobs2_over_6c = tobs * tobs / (6.0 * SPEED_OF_LIGHT)
        self.tolerance = tolerance

    def setup(self, cands):
        super().setup(cands)
        self.jerks = np.array([c.jerk for c in cands], np.float64)

    def matches(self, idx):
        fundi_freq = self.freqs[idx]
        freqs = self.freqs[idx + 1 :]
        delta_jerk = self.jerks[idx] - self.jerks[idx + 1 :]
        jerk_freq = (fundi_freq
                     + delta_jerk * fundi_freq * self.tobs2_over_6c)
        edge = fundi_freq * self.tolerance
        lo = np.minimum(jerk_freq, fundi_freq) - edge
        hi = np.maximum(jerk_freq, fundi_freq) + edge
        return (freqs > lo) & (freqs < hi)

    def pair_margin(self, fi, ai):
        drift = (self.jerks[fi] - self.jerks[ai]) * self.tobs2_over_6c
        return drift_margin(self.freqs[fi], self.freqs[ai], drift,
                            self.tolerance)


class DMDistiller(BaseDistiller):
    native_type = 2
    rule = "dm"

    def __init__(self, tolerance: float, keep_related: bool):
        super().__init__(keep_related)
        self.tolerance = tolerance

    def native_args(self):
        return np.zeros_like(self.freqs), 0, 0.0

    def matches(self, idx):
        ratio = self.freqs[idx + 1 :] / self.freqs[idx]
        return (ratio > 1 - self.tolerance) & (ratio < 1 + self.tolerance)

    def pair_margin(self, fi, ai):
        return dm_margin(self.freqs[fi], self.freqs[ai],
                         self.tolerance)
