"""The comparison that decides ``correct``.

Every number compared is the gap between what the timed ``run()``
reported and what the plain reference answers for the same stream, each
with its limit.  All are exact: DG masses are integers, so the final
density is the same float32 on any correct engine, and the counts are
counts.  The limits are therefore 0 (PERF.md gives the readings).

* ``final_g``: relative gap of the final best density (every tick's
  re-peel, merge and the window's re-seeded tracker feed it);
* ``live_edges``, ``expired``, ``ticks``: the structure phase A keeps;
* ``detected``: planted accounts in the reported community (with every
  account a windowed run ever reported);
* ``benign``: Def 4.1 benign transactions, each judged against the state
  before its tick, so every tick's best density and weights count;
* ``suffix_edges`` (workset engines): the largest affected suffix, in
  edges, that phase A counted for the workset/fallback choice;
* ``tick_paths`` (workset engines): ticks that took neither the workset
  nor the fallback.
"""

from __future__ import annotations

from dataclasses import dataclass

__all__ = ["Check", "compare"]


@dataclass(frozen=True)
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def compare(report, ref, fraud_accounts, n_streamed: int,
            workset: bool) -> list[Check]:
    """``report`` is the program's ``DeviceServiceReport`` (or a report of
    the same fields); ``ref`` the reference's ``RefResult``."""
    fraud = set(int(x) for x in fraud_accounts)
    hits_ref = len(fraud & ref.detected)
    hits = round(report.fraud_recall * len(fraud))
    benign = round(report.benign_fraction * n_streamed)
    g_ref = ref.final_g
    checks = [
        Check("final_g", abs(report.final_g - g_ref) / max(abs(g_ref), 1e-30),
              0.0),
        Check("live_edges", abs(report.live_edges - ref.live_edges), 0),
        Check("expired", abs(report.n_expired_edges - ref.n_expired_edges),
              0),
        Check("ticks", abs(report.n_ticks - ref.n_ticks), 0),
        Check("detected", abs(hits - hits_ref), 0),
        Check("benign", abs(benign - ref.benign), 0),
    ]
    if workset:
        checks += [
            Check("suffix_edges",
                  abs(report.max_suffix_edges - ref.max_suffix_edges), 0),
            Check("tick_paths", abs(report.n_workset_ticks
                                    + report.n_fallback_ticks - ref.n_ticks),
                  0),
        ]
    return checks
