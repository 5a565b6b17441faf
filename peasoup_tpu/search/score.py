"""Candidate scoring.

Exact semantics of `include/transforms/scorer.hpp:8-87`:

* ``is_physical``: period longer than the per-channel DM smear delay
  (note the reference evaluates ``8300 * foff / cfreq^3`` with the raw,
  typically negative, ``foff`` — reproduced faithfully);
* ``is_adjacent``: an associated detection exists in a neighbouring DM
  trial, or all associations share this DM trial;
* ``ddm_count_ratio`` / ``ddm_snr_ratio``: fraction of associated
  detections (and their SNR) within the expected DM width of the
  candidate.
"""

from __future__ import annotations

from ..data.candidates import Candidate


class CandidateScorer:
    def __init__(self, tsamp: float, cfreq: float, foff: float, bw: float):
        self.tsamp = tsamp
        self.cfreq = cfreq
        self.foff = foff
        ftop = cfreq + bw / 2.0
        fbottom = cfreq - bw / 2.0
        self.tdm_chan_partial = 8300.0 * foff / cfreq ** 3
        self.tdm_band_partial = 4150.0 * (1.0 / fbottom ** 2 - 1.0 / ftop ** 2)

    def _has_physical_period(self, cand: Candidate) -> bool:
        return 1.0 / cand.freq > cand.dm * self.tdm_chan_partial

    def _has_adjacency(self, cand: Candidate) -> bool:
        idx = cand.dm_idx
        adjacent = False
        unique = True
        for a in cand.assoc:
            if a.dm_idx != idx:
                unique = False
            if a.dm_idx in (idx + 1, idx - 1):
                adjacent = True
                break
        return adjacent or unique

    def _delta_dm_ratio(self, cand: Candidate) -> None:
        inside_count = total_count = 1
        inside_snr = total_snr = cand.snr
        ddm = 1.0 / (cand.freq * self.tdm_band_partial)
        for a in cand.assoc:
            total_count += 1
            total_snr += a.snr
            if abs(cand.dm - a.dm) <= ddm:
                inside_count += 1
                inside_snr += a.snr
        cand.ddm_count_ratio = inside_count / total_count
        # C float semantics (`scorer.hpp:62`): 0/0 is a quiet NaN, not
        # a crash — an all-zero-snr family scores nan like the
        # reference would
        cand.ddm_snr_ratio = (
            inside_snr / total_snr if total_snr != 0.0 else float("nan")
        )

    def score(self, cand: Candidate) -> None:
        cand.is_physical = self._has_physical_period(cand)
        cand.is_adjacent = self._has_adjacency(cand)
        self._delta_dm_ratio(cand)

    def score_all(self, cands: list[Candidate]) -> None:
        """Score every candidate in place."""
        for c in cands:
            self.score(c)

    @staticmethod
    def flags(cand: Candidate) -> dict:
        """A scored candidate's verdict, as its lineage ``scored`` mark
        carries it, so the provenance ledger records why a `why` query
        shows the folds/limit treating it the way they did.  Scoring annotates only (`scorer.hpp` never drops
        candidates): the marks are annotations, not terminal states."""
        return {
            "is_physical": bool(cand.is_physical),
            "is_adjacent": bool(cand.is_adjacent),
            "ddm_count_ratio": round(float(cand.ddm_count_ratio), 6),
            "ddm_snr_ratio": round(float(cand.ddm_snr_ratio), 6),
        }
