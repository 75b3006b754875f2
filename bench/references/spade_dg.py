"""Plain reference of the served Spade engine under DG semantics.

What ``SpadeService(DG, EngineSpec(...)).run(stream)`` must answer, written
from the algorithm's statement and not from the program (nothing here
imports it):

* DG weighting: every transaction weighs 1, accounts weigh 0 (Charikar).
* Set-up: a bulk peel of the base graph to convergence.  A round peels
  every active account whose weight is at most ``2 (1 + eps) g(S)``, or,
  where none is, those of least weight; it records the round as the
  account's level, and keeps the best density seen and its round.
* Each streamed tick: the Def 4.1 benign count against the state before
  the tick; on a full window, the oldest tick expires; the affected
  suffix starts at ``r0``, the least level of an endpoint of an expired
  or inserted edge; the suffix is re-peeled for ``max_rounds`` rounds
  from the best density the old community keeps after the expiry; the
  levels of the suffix are rebased onto ``r0`` (an account left unpeeled
  takes the round count), the community moves to the new best set where
  the re-peel beat the old density, and the windowed service also keeps
  every account it ever reported.

Densities are quotients of integer masses in the stated precision,
float32, rounded to nearest as IEEE says; masses and weights are exact
integers while they stay below 2**24.  ``precision="bfloat16"`` computes
every weight, mass, density and threshold in bfloat16 instead: that is
the control, which the comparison has to reject.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field

import numpy as np

__all__ = ["SpadeDG", "RefResult"]

LEVEL_NEW = 2**31 - 1  # the level an untouched suffix bound starts from
LEVEL_CAP = 2**30


@dataclass
class RefResult:
    final_g: float
    live_edges: int
    n_ticks: int
    n_expired_edges: int
    detected: set = field(default_factory=set)
    benign: int = 0
    max_suffix_edges: int = 0


def _rounder(precision: str):
    if precision == "float32":
        return lambda x: np.asarray(x, np.float32)
    if precision == "bfloat16":
        import ml_dtypes

        return lambda x: np.asarray(x, np.float32).astype(
            ml_dtypes.bfloat16).astype(np.float32)
    raise ValueError(f"unknown precision {precision!r}")


class SpadeDG:
    """The reference engine; feed it the stream tick by tick."""

    def __init__(self, n_vertices: int, base_src, base_dst, *, eps: float,
                 max_rounds: int, window_ticks: int = 0,
                 precision: str = "float32"):
        self.n = int(n_vertices)
        self.q = _rounder(precision)
        self.two_eps = self.q(np.float32(2.0 * (1.0 + eps)))
        self.max_rounds = int(max_rounds)
        self.window_ticks = int(window_ticks)
        self.base_src = np.asarray(base_src, np.int32)
        self.base_dst = np.asarray(base_dst, np.int32)
        self.ticks: deque = deque()  # resident streamed ticks, oldest first
        self.n_ticks = 0
        self.n_expired = 0
        self.benign = 0
        self.max_suffix_edges = 0
        # the full graph's peel weights, for the benign test
        self.w0 = self.q(self._degrees(self.base_src, self.base_dst))
        live = np.ones(self.n, bool)
        level, best_g, best_level, _ = self._peel(
            live, self.base_src, self.base_dst, np.float32(-np.inf), None)
        self.level = level
        self.best_g = best_g
        self.community = level >= best_level
        self.detected = np.zeros(self.n, bool)

    # -- arithmetic in the stated precision -----------------------------

    def _div(self, a, b):
        return self.q(np.float32(a) / np.float32(b))

    def _degrees(self, src, dst) -> np.ndarray:
        return (np.bincount(src, minlength=self.n)
                + np.bincount(dst, minlength=self.n)).astype(np.float32)

    def _peel(self, live, es, ed, best_g, max_rounds):
        """Bulk peel of the subgraph that ``live`` induces (``es, ed`` are
        its edges); ``max_rounds=None`` runs to convergence."""
        q = self.q
        w = q(self._degrees(es, ed))
        active = live.copy()
        n_act = int(active.sum())
        level = np.full(self.n, -1, np.int64)
        best_level = 0
        rnd = 0
        while n_act > 0 and (max_rounds is None or rnd < max_rounds):
            g = self._div(q(np.float32(es.shape[0])), max(n_act, 1))
            if g > best_g:
                best_g, best_level = g, rnd
            thresh = q(self.two_eps * g)
            peel = active & (w <= thresh)
            if not peel.any():
                peel = active & (w <= w[active].min())
            ps, pd = peel[es], peel[ed]
            dw = q(self._degrees_one_sided(es, ed, ps, pd))
            w = q(w - dw)
            alive = ~(ps | pd)
            es, ed = es[alive], ed[alive]
            active &= ~peel
            level[peel] = rnd
            n_act -= int(peel.sum())
            rnd += 1
        n_rounds = rnd if max_rounds is None else max_rounds
        return level, q(best_g), best_level, n_rounds

    def _degrees_one_sided(self, es, ed, ps, pd) -> np.ndarray:
        """Weight each survivor loses: its edges to peeled accounts."""
        return (np.bincount(ed[ps & ~pd], minlength=self.n)
                + np.bincount(es[pd & ~ps], minlength=self.n)
                ).astype(np.float32)

    # -- one streamed tick ------------------------------------------------

    def tick(self, src, dst) -> None:
        """Serve one tick of valid edges ``src -> dst``."""
        q = self.q
        src = np.asarray(src, np.int32)
        dst = np.asarray(dst, np.int32)
        one = np.float32(1.0)
        urgent = ((q(self.w0[src] + one) >= self.best_g)
                  | (q(self.w0[dst] + one) >= self.best_g))
        self.benign += int((~urgent).sum())

        lvl = LEVEL_NEW
        comm_loss = 0
        dropped = None
        if self.window_ticks and len(self.ticks) >= self.window_ticks:
            dropped = self.ticks.popleft()
            ds, dd = dropped
            if ds.size:
                lvl = min(lvl, int(self.level[ds].min()),
                          int(self.level[dd].min()))
            comm_loss = int((self.community[ds] & self.community[dd]).sum())
            self.n_expired += ds.size
        if src.size:
            lvl = min(lvl, int(self.level[src].min()),
                      int(self.level[dst].min()))
        n_del = 0 if dropped is None else dropped[0].size
        r0 = lvl if (n_del or src.size) else LEVEL_NEW
        r0 = min(r0, LEVEL_CAP)
        n_comm = int(self.community.sum())
        if n_comm:
            prior_g = q(self.best_g - self._div(q(np.float32(comm_loss)),
                                                n_comm))
        else:
            prior_g = np.float32(-np.inf)

        self.ticks.append((src, dst))
        if dropped is not None:
            ds, dd = dropped
            self.w0 = q(self.w0 - self._degrees(ds, dd))
        self.w0 = q(self.w0 + self._degrees(src, dst))

        keep = self.level >= r0
        es, ed = self._resident()
        if not keep.all():
            both = keep[es] & keep[ed]
            es, ed = es[both], ed[both]
        self.max_suffix_edges = max(self.max_suffix_edges, es.shape[0])
        res_level, res_g, res_best, n_rounds = self._peel(
            keep, es, ed, prior_g, self.max_rounds)

        suffix = np.where(res_level >= 0, res_level, n_rounds)
        self.level = np.where(keep, r0 + suffix, self.level)
        if res_g > prior_g:
            self.community = (res_level >= res_best) & keep
        self.best_g = max(res_g, prior_g)
        if self.window_ticks:
            self.detected |= self.community
        self.n_ticks += 1

    def _resident(self):
        if not self.ticks:
            return self.base_src, self.base_dst
        return (np.concatenate([self.base_src, *(t[0] for t in self.ticks)]),
                np.concatenate([self.base_dst, *(t[1] for t in self.ticks)]))

    def result(self) -> RefResult:
        resident = sum(t[0].size for t in self.ticks)
        return RefResult(
            final_g=float(self.best_g),
            live_edges=self.base_src.size + resident,
            n_ticks=self.n_ticks,
            n_expired_edges=self.n_expired,
            detected=set(np.flatnonzero(self.community | self.detected)
                         .tolist()),
            benign=self.benign,
            max_suffix_edges=self.max_suffix_edges,
        )
