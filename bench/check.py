"""The comparison that decides ``correct``: served beams against the plain
reference, on a seeded sample of the window's requests.

For one request the program served BW items (ND tokens each) with their
log-probs.  The reference reruns the prompt, then each served prefix, in
float32 at the highest matmul precision, and gives the log-softmax over
the vocabulary at every position a served token was chosen at.  Two
numbers come out:

``lp_gap``  the widest gap, in nats, between a served item's log-prob and
            the reference's log-prob of the same item.  It covers the
            backbone's prefill, beam attention over the paged prefix (and
            over pages adopted from the prefix cache) and the logits.
``select_gap``  the widest shortfall of a served prefix below the
            reference's cut-off at that phase: at phase d the candidates
            are the children (per the catalog) of the served prefixes of
            phase d - 1, each beam keeping its top-K; the cut-off is the
            BW-th best candidate by the reference's score.  A served prefix
            scoring above the cut-off reads 0.  Because only the prefixes
            that survive to the end are visible, the candidate pool is a
            subset of the one the program ranked, so a sound selection
            reads 0 up to rounding, and a wrong token reads its whole
            shortfall.  It covers trie-constrained beam select.

The control is the reference in a lower precision put in the program's
place: its ``lp_gap`` is the gap between its log-probs of the served items
and the reference's, and its ``select_gap`` is read for the prefixes it
would itself pick, over the same pools.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np


class Catalog:
    """Valid continuations of every prefix of the item catalog."""

    def __init__(self, items: np.ndarray):
        items = np.asarray(items, np.int64)
        self.nd = items.shape[1]
        self.items = {tuple(r) for r in items.tolist()}
        self.children: List[Dict[tuple, np.ndarray]] = []
        for d in range(self.nd):
            groups: Dict[tuple, list] = {}
            for row in items.tolist():
                groups.setdefault(tuple(row[:d]), set()).add(row[d])
            self.children.append({k: np.fromiter(sorted(v), np.int64)
                                  for k, v in groups.items()})


def bad_output(items: np.ndarray, lps: np.ndarray, bw: int, nd: int,
               catalog: Catalog) -> Optional[str]:
    """Why a served answer is malformed, or None."""
    items, lps = np.asarray(items), np.asarray(lps)
    if items.shape != (bw, nd) or lps.shape != (bw,):
        return f"shapes {items.shape} / {lps.shape}"
    if not np.all(np.isfinite(lps)):
        return "non-finite log-probs"
    if np.any(np.diff(lps) > 0):
        return "log-probs not descending"
    bad = [t for t in map(tuple, items.tolist()) if t not in catalog.items]
    if bad:
        return f"{len(bad)} items not in the catalog, e.g. {bad[0]}"
    if len(set(map(tuple, items.tolist()))) != bw:
        return "duplicate items"
    return None


def _kth_best(scores: np.ndarray, k: int) -> float:
    if len(scores) < k:
        return -np.inf
    return float(np.partition(scores, len(scores) - k)[len(scores) - k])


def _pool(prefixes: List[tuple], base: Dict[tuple, float],
          rows: Dict[tuple, np.ndarray], children: Dict[tuple, np.ndarray],
          top_k: int) -> Tuple[List[tuple], np.ndarray]:
    """Candidates of phase d: each prefix's children, scored base + row,
    each prefix keeping its top-K."""
    cands, scores = [], []
    for pre in prefixes:
        ch = children.get(pre, np.zeros(0, np.int64))
        sc = base[pre] + rows[pre][ch]
        if len(ch) > top_k:
            keep = np.argsort(-sc, kind="stable")[:top_k]
            ch, sc = ch[keep], sc[keep]
        cands.extend(pre + (int(t),) for t in ch)
        scores.append(sc)
    return cands, (np.concatenate(scores) if scores else np.zeros(0))


def readings(items: np.ndarray, lps: np.ndarray, ref: Tuple, catalog: Catalog,
             bw: int, top_k: int, ctrl: Optional[Tuple] = None
             ) -> Dict[str, float]:
    """``lp_gap`` and ``select_gap`` of one served request against the
    reference's (lp0, lp1, lp2) — and the control's, when given — where
    lp1/lp2 rows follow the sorted distinct (t0, t1) prefixes of the
    served items (see :func:`beam_rows`)."""
    items = np.asarray(items, np.int64)
    nd = items.shape[1]
    served = [tuple(r) for r in items.tolist()]
    out = {}
    sides = [("", ref)] + ([("ctrl_", ctrl)] if ctrl is not None else [])
    pre2 = sorted({s[:2] for s in served})
    for tag, (lp0, lp1, lp2) in sides:
        # per-position log-softmax rows keyed by the prefix they follow
        rows = [{(): lp0},
                {p[:1]: lp1[i] for i, p in enumerate(pre2)},
                {p: lp2[i] for i, p in enumerate(pre2)}][:nd]
        out[tag] = rows
    ref_rows = out[""]

    def score(rows, seq):
        return sum(rows[d][seq[:d]][seq[d]] for d in range(len(seq)))

    res = {"lp_gap": float(max(abs(score(ref_rows, s) - float(lp))
                               for s, lp in zip(served, lps)))}
    if ctrl is not None:
        res["ctrl_lp_gap"] = float(max(
            abs(score(ref_rows, s) - score(out["ctrl_"], s)) for s in served))
    gap = {"": 0.0, "ctrl_": 0.0}
    prefixes = [()]
    for d in range(nd):
        base = {p: score(ref_rows, p) for p in prefixes}
        cands, sc = _pool(prefixes, base, ref_rows[d],
                          catalog.children[d], top_k)
        cut = _kth_best(sc, bw)
        picked = {s[:d + 1] for s in served}
        gap[""] = max(gap[""], max(cut - score(ref_rows, p) for p in picked))
        if ctrl is not None:
            cbase = {p: score(out["ctrl_"], p) for p in prefixes}
            ccands, csc = _pool(prefixes, cbase, out["ctrl_"][d],
                                catalog.children[d], top_k)
            mine = [ccands[i] for i in np.argsort(-csc, kind="stable")[:bw]]
            gap["ctrl_"] = max(gap["ctrl_"], max(
                (cut - score(ref_rows, p) for p in mine), default=0.0))
        prefixes = sorted(picked)
    res["select_gap"] = max(0.0, float(gap[""]))
    if ctrl is not None:
        res["ctrl_select_gap"] = max(0.0, float(gap["ctrl_"]))
    return res


def beam_rows(items: np.ndarray) -> np.ndarray:
    """(b, 2) the sorted distinct (t0, t1) prefixes of served items: the
    rows the reference runs after the prompt."""
    pre = sorted({tuple(r[:2]) for r in np.asarray(items).tolist()})
    return np.asarray(pre, np.int32).reshape(-1, 2)
