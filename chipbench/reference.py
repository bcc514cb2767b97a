"""The plain reference and the comparison that decides `correct`.

The reference is the fixed-window limiter written the obvious way: a
dict from (key, window number) to the hits seen, a hit answered OK while
hits <= limit (always OK under a shadow rule), `limit_remaining` =
max(0, limit - hits).  It imports nothing of the program and takes
nothing the program made: keys, limits and units come from the
configuration file through deploy.Deployment.

The server stamps a request with its own whole unix second somewhere
between the client's send and the client's receipt.  A hit whose send
and receipt lie in the same window of its rule has a known window; one
that straddles a boundary does not, and the (key, window) pairs it
could have landed in are set aside ("unsure") for the exact
comparisons, never for the upper bound.

Two comparisons, every number exact (limit 0):

  (a) replay: an ordered sequence on one connection, answer for answer
      (code, limit_remaining, limit) against the reference carried
      forward from everything sent before;
  (b) log: over every hit of the run (load, warm-up, window, replay),
      per key and window: OK answers never above the limit; where the
      window is known for all of the key's hits, OK answers ==
      min(limit, hits) (== hits under a shadow rule).

`limit_offset` raises every limit of the REFERENCE by that much: the
must-fail probe (run.py --control reference).
"""

from __future__ import annotations

import numpy as np

from .deploy import Deployment

OK, OVER_LIMIT = 1, 2  # envoy RateLimitResponse.Code


class Ledger:
    def __init__(self, dep: Deployment, limit_offset: int = 0):
        self.dep = dep
        self.limit_offset = int(limit_offset)
        self.hits: dict = {}  # (gid, window) -> hits known to lie there
        self.admitted: dict = {}  # (gid, window) -> OK answers among them
        self.unsure: set = set()  # (gid, window) some hit may or may not be in

    # -- properties of keys ----------------------------------------------

    def _props(self, gid: np.ndarray):
        d, k = np.divmod(gid, self.dep.kpd)
        fam = self.dep.family_of(k)
        limit = self.dep.limits_of(d, k) + self.limit_offset
        return self.dep.unit_s_by_family[fam], limit, self.dep.shadow_by_family[fam]

    # -- unordered hits (load, warm-up, window) ---------------------------

    def add(self, gid, t_send, t_recv, ok, answered) -> None:
        """Hits whose order is unknown.  All arguments are arrays of
        one length: key, wall-clock send and receipt, answered OK,
        answered at all."""
        gid = np.asarray(gid, dtype=np.int64)
        unit_s, _, _ = self._props(gid)
        w_lo = np.floor(np.asarray(t_send) / unit_s).astype(np.int64)
        w_hi = np.floor(np.asarray(t_recv) / unit_s).astype(np.int64)
        known = (w_lo == w_hi) & np.asarray(answered, dtype=bool)
        for g, lo, hi in zip(gid[~known].tolist(), w_lo[~known].tolist(), w_hi[~known].tolist()):
            for w in range(lo, hi + 1):
                self.unsure.add((g, w))
        pairs = np.stack([gid[known], w_lo[known]], axis=1)
        if not len(pairs):
            return
        uniq, inverse = np.unique(pairs, axis=0, return_inverse=True)
        inverse = inverse.reshape(-1)
        n_hits = np.bincount(inverse, minlength=len(uniq))
        n_ok = np.bincount(inverse, weights=np.asarray(ok)[known].astype(float), minlength=len(uniq))
        for (g, w), h, a in zip(uniq.tolist(), n_hits.tolist(), n_ok.tolist()):
            key = (g, w)
            self.hits[key] = self.hits.get(key, 0) + h
            self.admitted[key] = self.admitted.get(key, 0) + int(a)

    # -- ordered hits (replay) --------------------------------------------

    def expect(self, gids, t_send: float, t_recv: float, got) -> tuple:
        """One replayed request: `got` is [(code, remaining, limit)] as
        answered.  Returns (compared, mismatches, first mismatch or
        None) and carries the reference forward."""
        gids = np.asarray(gids, dtype=np.int64)
        unit_s, limit, shadow = self._props(gids)
        compared = mismatches = 0
        first = None
        for i, g in enumerate(gids.tolist()):
            u, lim = int(unit_s[i]), int(limit[i])
            lo, hi = int(t_send // u), int(t_recv // u)
            if lo != hi:
                self.unsure.update(((g, lo), (g, hi)))
                continue
            key = (g, lo)
            after = self.hits.get(key, 0) + 1
            self.hits[key] = after
            self.admitted[key] = self.admitted.get(key, 0) + (got[i][0] == OK)
            if key in self.unsure:
                continue
            over = after > lim
            want = (OK if (not over or shadow[i]) else OVER_LIMIT, max(0, lim - after), lim)
            compared += 1
            if tuple(got[i]) != want:
                mismatches += 1
                first = first or f"key {g} hit {after}: answered {tuple(got[i])}, reference {want}"
        return compared, mismatches, first

    # -- the log comparison -------------------------------------------------

    def check_log(self) -> dict:
        keys = list(self.hits)
        if not keys:
            return {"windows": 0, "exact_windows": 0, "over_admitted": 0, "miscounted": 0, "example": None}
        arr = np.array(keys, dtype=np.int64)
        _, limit, shadow = self._props(arr[:, 0])
        hits = np.array([self.hits[k] for k in keys])
        adm = np.array([self.admitted[k] for k in keys])
        sure = np.array([k not in self.unsure for k in keys])
        over = (adm > limit) & ~shadow
        want = np.where(shadow, hits, np.minimum(limit, hits))
        wrong = sure & (adm != want)
        example = None
        bad = np.flatnonzero(over | wrong)
        if len(bad):
            i = int(bad[0])
            example = (
                f"key {keys[i][0]} window {keys[i][1]}: {int(hits[i])} hits, "
                f"{int(adm[i])} answered OK, limit {int(limit[i])}"
            )
        return {
            "windows": len(keys),
            "exact_windows": int(sure.sum()),
            "over_admitted": int(over.sum()),
            "miscounted": int(wrong.sum()),
            "example": example,
        }
