"""The comparison that decides ``correct`` for constrained beam search.

Three numbers, each against a limit of its own:

``violations``
    Over every request finished in the window: served beams that are not
    in the constraint set, carry no finite score, or repeat another beam
    of the same request.  Exact: the limit is 0.
``score_gap``
    Over a sample of those requests: the largest distance, in nats, between
    a served beam's score and the reference's sum of log-probs along that
    beam.  Covers the decoder's forward (prefill and every decode level)
    and the log-softmax the constraint step scores with.
``select_gap``
    Over the same sample: how far beam selection strayed from the
    reference.  At each level, the candidates in view are the valid
    one-token extensions of the served prefixes of that length.  Beam
    search keeps the best M of a superset of them, so every served prefix
    one token longer must score, by the reference, at least the M-th best
    candidate in view.  The number is the widest shortfall.  For the
    control, the prefixes are those its own scores rank first among the
    same candidates.
"""
from __future__ import annotations

import numpy as np

NEG_INF_SCORE = -1e29  # the program marks dead beams with -1e30


class ConstraintSet:
    """The constraint SIDs, sorted, for membership and child lookups."""

    def __init__(self, sids: np.ndarray):
        self.L = sids.shape[1]
        self._rows = np.sort(self._keys(sids))
        self._sids = self._decode(self._rows)

    @staticmethod
    def _keys(rows: np.ndarray) -> np.ndarray:
        # big-endian bytes order as the tokens do, so a byte-wise sort is a
        # lexicographic sort of the SIDs
        a = np.ascontiguousarray(np.asarray(rows).astype(">u2"))
        return a.view(f"V{2 * a.shape[-1]}").reshape(a.shape[:-1])

    def _decode(self, keys: np.ndarray) -> np.ndarray:
        return keys.view(">u2").reshape(-1, self.L).astype(np.int64)

    def contains(self, rows: np.ndarray) -> np.ndarray:
        k = self._keys(rows.reshape(-1, self.L))
        i = np.minimum(np.searchsorted(self._rows, k), len(self._rows) - 1)
        return (self._rows[i] == k).reshape(rows.shape[:-1])

    def children(self, prefix) -> np.ndarray:
        """Distinct next tokens of the SIDs that start with ``prefix``."""
        n = len(prefix)
        lo = np.array(list(prefix) + [0] * (self.L - n))
        hi = np.array(list(prefix) + [0xFFFF] * (self.L - n))
        a = np.searchsorted(self._rows, self._keys(lo[None]))[0]
        b = np.searchsorted(self._rows, self._keys(hi[None]),
                            side="right")[0]
        return np.unique(self._sids[a:b, n])


def violations(cs: ConstraintSet, beams: np.ndarray,
               scores: np.ndarray) -> int:
    """Bad beams over requests (R, M, L) with scores (R, M)."""
    beams = np.asarray(beams)
    scores = np.asarray(scores, np.float64)
    bad = ~cs.contains(beams)
    bad |= ~np.isfinite(scores) | (scores <= NEG_INF_SCORE)
    R, M, L = beams.shape
    for r in range(R):
        _, first = np.unique(beams[r], axis=0, return_index=True)
        dup = np.ones(M, bool)
        dup[first] = False
        bad[r] |= dup
    return int(bad.sum())


def _beam_scores(beams, lp):
    """Sum over levels of lp (N, M, L, V) at the beams' tokens (N, M, L)."""
    at = np.take_along_axis(lp, beams[..., None], axis=-1)[..., 0]
    return (at.astype(np.float64).sum(-1),
            np.cumsum(at, axis=-1, dtype=np.float64))


def score_gap(beams, scores, lp_ref) -> float:
    """Widest |served score - reference score| over the sample."""
    ref, _ = _beam_scores(np.asarray(beams), lp_ref)
    return float(np.max(np.abs(np.asarray(scores, np.float64) - ref)))


def select_gap(cs: ConstraintSet, beams, lp_ref, M: int,
               lp_pick=None) -> float:
    """Widest shortfall of a kept prefix below the reference's M-th best
    candidate in view (module docstring).  The kept prefixes are the served
    ones, or, with ``lp_pick``, the top M in view by ``lp_pick``."""
    beams = np.asarray(beams)
    N, _, L = beams.shape
    _, ref_cum = _beam_scores(beams, lp_ref)
    if lp_pick is not None:
        _, pick_cum = _beam_scores(beams, lp_pick)
    worst = 0.0
    for n in range(N):
        for level in range(L):
            # one representative beam per distinct served prefix
            prefixes = {}
            for m in range(beams.shape[1]):
                prefixes.setdefault(tuple(beams[n, m, :level].tolist()), m)
            cand_ref, cand_pick, cand_key = [], [], []
            for p, m in prefixes.items():
                kids = cs.children(p)
                base = ref_cum[n, m, level - 1] if level else 0.0
                cand_ref.append(base + lp_ref[n, m, level, kids])
                if lp_pick is not None:
                    pb = pick_cum[n, m, level - 1] if level else 0.0
                    cand_pick.append(pb + lp_pick[n, m, level, kids])
                cand_key.extend(p + (int(t),) for t in kids)
            cand_ref = np.concatenate(cand_ref)
            if len(cand_ref) < M:
                continue  # every candidate in view is kept
            mth = np.sort(cand_ref)[::-1][M - 1]
            if lp_pick is None:
                kept = {tuple(beams[n, m, :level + 1].tolist())
                        for m in range(beams.shape[1])}
                index = {k: i for i, k in enumerate(cand_key)}
                got = cand_ref[[index[k] for k in kept if k in index]]
            else:
                order = np.argsort(-np.concatenate(cand_pick), kind="stable")
                got = cand_ref[order[:M]]
            worst = max(worst, float(np.max(mth - got, initial=0.0)))
    return worst


def judge(numbers: dict, limits: dict) -> bool:
    """True when every number is at or under its limit."""
    return all(numbers[k] <= limits[k] for k in limits)
