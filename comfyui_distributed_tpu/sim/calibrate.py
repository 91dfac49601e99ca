"""Calibration gate: the sim vs the measured records.

A simulator that cannot reproduce the runs it claims to model is a
random-number generator with extra steps.  This module scores a sim
summary against a committed measured record
(``benchmarks/scenarios/*.measured.json``: loopback runs of the ``tiny``
family on the CPU, kept as the sim's calibration target and as nothing
else) two ways:

- **quantities** — relative error on the numbers the bench measured
  (per-class admitted/shed counts, per-class p95, completion rate for
  the overload bench; completion for the multimaster kill arm).  The
  headline ``calibration_error`` is the mean relative error, floored at
  1e-4 so it stays positive even on a perfect run.
- **hard bars** — the *orderings* the bench proves (paid sheds zero,
  shedding is batch-first, per-class p95 orders paid < free < batch,
  the kill arm completes 1.0 with exactly one takeover by the measured
  ring successor).  A failed bar adds 1.0 to the error: orderings are
  the point of the policies, so a sim that inverts one must fail the
  gate no matter how close the raw numbers land.

``tests/test_sim.py::TestCalibration`` runs both fixtures under
``benchmarks/scenarios/`` and gates on
``calibration_error <= C.SIM_CALIBRATION_MAX_ERR``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from comfyui_distributed_tpu.utils import constants as C

# floor keeps the headline metric positive on a perfect run
_ERR_FLOOR = 1e-4


def rel_err(sim: float, ref: float) -> float:
    """|sim - ref| / |ref| (a ref of 0 demands an exact 0)."""
    if ref == 0:
        return 0.0 if sim == 0 else 1.0
    return abs(float(sim) - float(ref)) / abs(float(ref))


def _cls(summary: Dict[str, Any], cls: str) -> Dict[str, Any]:
    return dict((summary.get("per_class") or {}).get(cls) or {})


def _score(quantities: List[Tuple[str, float, float]],
           bars: List[Tuple[str, bool]]) -> Dict[str, Any]:
    errors = {name: round(rel_err(sim, ref), 4)
              for name, sim, ref in quantities}
    mean = (sum(errors.values()) / len(errors)) if errors else 0.0
    failed = [name for name, ok in bars if not ok]
    return {
        "quantities": {name: {"sim": sim, "ref": ref,
                              "rel_err": errors[name]}
                       for name, sim, ref in quantities},
        "mean_rel_err": round(mean, 4),
        "bars": {name: ok for name, ok in bars},
        "bars_failed": failed,
        "calibration_error": round(
            max(mean + 1.0 * len(failed), _ERR_FLOOR), 4),
    }


def score_overload(summary: Dict[str, Any],
                   artifact: Dict[str, Any]) -> Dict[str, Any]:
    """Score a sim run of the overload fixture against
    ``benchmarks/scenarios/overload_r09.measured.json`` (the measured
    elastic-fleet run)."""
    ref = artifact.get("per_class") or {}
    quantities: List[Tuple[str, float, float]] = []
    for cls in C.TENANT_CLASSES:
        s, r = _cls(summary, cls), dict(ref.get(cls) or {})
        quantities.append((f"{cls}_admitted",
                           s.get("admitted", 0), r.get("admitted", 0)))
        quantities.append((f"{cls}_p95_s",
                           s.get("p95_s", 0.0), r.get("p95_s", 0.0)))
        if r.get("shed", 0):
            quantities.append((f"{cls}_shed",
                               s.get("shed_overload", 0)
                               + s.get("shed_rate", 0),
                               r.get("shed", 0)))
    quantities.append(("completion_rate",
                       summary.get("completion_rate", 0.0),
                       artifact.get("completion_rate", 1.0)))
    paid, free, batch = (_cls(summary, c) for c in
                         ("paid", "free", "batch"))
    free_shed = free.get("shed_overload", 0) + free.get("shed_rate", 0)
    batch_shed = batch.get("shed_overload", 0) \
        + batch.get("shed_rate", 0)
    bars = [
        ("paid_shed_zero", paid.get("shed_overload", 0)
         + paid.get("shed_rate", 0) == 0),
        ("shed_batch_first", batch_shed >= free_shed > 0),
        ("p95_class_order", paid.get("p95_s", 0.0)
         < free.get("p95_s", 0.0) < batch.get("p95_s", 0.0)),
        ("paid_completion", paid.get("completed", 0)
         == paid.get("admitted", -1)),
        ("drained", bool(summary.get("drained"))),
    ]
    fan = summary.get("fanout")
    if fan is not None:
        # the churn act's fan-out jobs must all survive the mid-window
        # worker kill, like the measured fanout_completed == fanout_jobs
        bars.append(("fanout_completion",
                     fan.get("completed") == fan.get("jobs")))
    return _score(quantities, bars)


def score_multimaster(summary: Dict[str, Any],
                      artifact: Dict[str, Any]) -> Dict[str, Any]:
    """Score a sim run of the multimaster kill fixture against
    ``benchmarks/scenarios/multimaster_r14.measured.json`` (the measured
    sharded control-plane run)."""
    ref_kill = artifact.get("kill") or {}
    ref_tk = artifact.get("takeover") or {}
    tk = summary.get("takeover") or {}
    quantities = [
        ("completed", summary.get("completed_total", 0),
         ref_kill.get("completed", 0)),
        ("completion_rate", summary.get("completion_rate", 0.0),
         artifact.get("kill_completion_rate", 1.0)),
    ]
    bars = [
        ("one_takeover", tk.get("takeovers") == ref_tk.get("takeovers")),
        ("ring_successor", tk.get("successor")
         == ref_tk.get("successor")),
        ("owned_shards", list(tk.get("owned") or [])
         == list(ref_tk.get("owned") or [])),
        ("ring_epoch", tk.get("ring_epoch")
         == ref_tk.get("ring_epoch")),
        ("kill_completion", summary.get("completion_rate") == 1.0),
        ("drained", bool(summary.get("drained"))),
    ]
    return _score(quantities, bars)


SCORERS = {"overload": score_overload, "multimaster": score_multimaster}


def combine(scores: Dict[str, Dict[str, Any]]) -> Dict[str, Any]:
    """One headline number over the per-fixture scores: the mean of
    their calibration errors (each already bar-inflated)."""
    errs = [s["calibration_error"] for s in scores.values()]
    mean = sum(errs) / len(errs) if errs else _ERR_FLOOR
    return {
        "calibration_error": round(max(mean, _ERR_FLOOR), 4),
        "max_allowed": C.SIM_CALIBRATION_MAX_ERR,
        "ok": all(not s["bars_failed"] and
                  s["mean_rel_err"] <= C.SIM_CALIBRATION_MAX_ERR
                  for s in scores.values()),
        "fixtures": scores,
    }
