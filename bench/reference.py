"""The plain reference and the comparison that decides ``correct``.

The reference scores every document exactly: a float64 sparse-dense
product over the uncompressed CSR corpus that ``bench/corpus.py``
drew, with no codec, row layout, kernel or engine in the way. It
imports nothing of the program and takes nothing the program made.

``compare`` reads the served answers against it. Every number it
returns is "worse when larger", so each is held to an upper limit
(``<=``):

* ``bad_ids`` — answers that are no valid top-k list: an id outside
  the corpus, an id twice in one list, a score that is not finite, or
  scores out of descending order (exact comparison, limit 0);
* ``score_err`` — the widest gap between a served score and the
  reference score of the document it names, over the query's
  reference top-1 score. A wrong value, a lossy decode, or an answer
  that reached the wrong ticket all show here;
* ``rank_gap`` — the widest gap by which the j-th served document's
  reference score lies below the reference's j-th best, over the
  top-1 score: what an exact engine must keep at rounding level;
* ``missed_share`` — one minus recall@k against the reference top-k
  (an id counts as found when its reference score reaches the k-th
  best score, so tied documents are interchangeable): what an
  approximate engine gives up.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp

#: reference scores are computed for this many queries at a time
QUERY_BLOCK = 64


def csr_matrix(corpus) -> sp.csr_matrix:
    """The corpus as a float64 [n_docs, dim] CSR matrix."""
    return sp.csr_matrix(
        (corpus.values.astype(np.float64), corpus.components.astype(np.int64),
         corpus.offsets),
        shape=(corpus.n_docs, corpus.dim),
    )


def exact_scores(A: sp.csr_matrix, Q: np.ndarray) -> np.ndarray:
    """float64 [m, n_docs]: every document's inner product with each query."""
    return np.asarray(A @ np.asarray(Q, np.float64).T).T


def compare(corpus, Q: np.ndarray, ids: np.ndarray, scores: np.ndarray, k: int) -> dict:
    """Served answers (``ids``/``scores`` [m, k] for the dense queries
    ``Q`` [m, dim]) against the exact reference → the numbers above,
    plus ``recall_at_k``, the mean recall over the ``m`` answers."""
    A = csr_matrix(corpus)
    ids = np.asarray(ids)
    scores = np.asarray(scores, np.float64)
    n = corpus.n_docs
    bad = score_err = rank_gap = 0.0
    found = 0
    for lo in range(0, len(Q), QUERY_BLOCK):
        R = exact_scores(A, Q[lo : lo + QUERY_BLOCK])
        for r, full in enumerate(R):
            got, s = ids[lo + r], scores[lo + r]
            best = np.sort(full)[::-1][:k]
            top1 = max(best[0], np.finfo(np.float64).tiny)
            valid = (got >= 0) & (got < n)
            ok = (valid.all() and len(np.unique(got)) == k
                  and np.isfinite(s).all() and (np.diff(s) <= 0).all())
            bad += 0 if ok else 1
            ref = np.where(valid, full[np.clip(got, 0, n - 1)], -np.inf)
            sound = valid & np.isfinite(s)
            gap = np.full(k, np.inf)
            gap[sound] = np.abs(s[sound] - ref[sound]) / top1
            score_err = max(score_err, float(gap.max()))
            rank_gap = max(rank_gap, float(np.max(best - ref) / top1))
            hit = valid & (ref >= best[-1])
            found += len(np.unique(got[hit]))
    recall = found / (k * len(Q))
    return {
        "bad_ids": bad,
        "score_err": score_err,
        "rank_gap": max(rank_gap, 0.0),
        "missed_share": 1.0 - recall,
        "recall_at_k": recall,
    }
