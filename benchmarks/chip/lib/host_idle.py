"""What the readers of the idle time BETWEEN programs share: the rows the
program's own trace summary (``lib/profile.py`` `summary`) keeps since PR
51 beside ``idle`` and ``idle_under``.  ``idle_by_executor_class`` gives
every idle second between two executions (and at the slice's two edges)
ONE owner, by the interval the EXECUTOR thread stood in at that instant
on the program's host timeline: ``host`` (its own code), ``wake`` (a
hand-over from another thread on its way), ``wait_request``
(``exec_idle``), ``wait_device`` (it blocked for the device), ``gc`` (a
collector pause on any thread), ``unowned`` (none of its intervals).
The rows add up to ``idle_between_s`` by construction.

A reader gets None, never 0, where the run has no device trace or the
summary has no such rows (a program from before PR 51, a trace with no
timeline beside it, clock markers that disagree).
"""

from __future__ import annotations

from .profile import stage_total_s, summary

CLASSES = ("host", "wake", "wait_device", "wait_request", "gc", "unowned")
_said = False


def rows(ctx) -> dict | None:
    """``idle_by_executor_class`` of the summary, the identity it holds
    printed once a run."""
    global _said
    prof = summary(ctx)
    if prof is None or "idle_by_executor_class" not in prof:
        return None
    by_class = prof["idle_by_executor_class"]
    if not _said:
        _said = True
        window = prof["window_s"]
        idle = window - prof["busy_s"]
        total = sum(by_class[c] for c in CLASSES)
        print("[chipbench] idle between programs: "
              + " + ".join(f"{c} {by_class[c]:.6f}" for c in CLASSES)
              + f" = {total:.6f} s against the summary's between-program "
              f"idle {prof['idle_between_s']:.6f} s (device idle "
              f"{idle:.6f} s = {100.0 * idle / window:.3f}% of "
              f"{window:.6f} s, less gaps_in_programs "
              f"{prof['gaps_in_programs_s']:.6f} = "
              f"{idle - prof['gaps_in_programs_s']:.6f}; the summary "
              f"counts the slice's edges by its clock markers); "
              f"clock_drift_ns {prof.get('clock_drift_ns')}, the timeline "
              f"{prof.get('host_timeline')}, by owner "
              + ", ".join(f"{k} {v:.6f}" for k, v in sorted(
                  prof["idle_by_executor"].items(), key=lambda kv: -kv[1]))
              + "; under (any depth) " + ", ".join(
                  f"{k} {v:.6f}" for k, v in sorted(
                      prof.get("idle_under_executor", {}).items(),
                      key=lambda kv: -kv[1])[:6])
              + "; longest: " + "; ".join(
                  f"{r['s']:.6f} s x{r['n']} {r['owner']} between "
                  f"{r['before']} and {r['after']}"
                  for r in prof.get("top_idle_between", [])[:4]),
              flush=True)
    return by_class


def class_pct(ctx, cls: str) -> float | None:
    """One row of the owner table as a share of the traced window."""
    by_class = rows(ctx)
    if by_class is None:
        return None
    return 100.0 * by_class[cls] / summary(ctx)["window_s"]


def stage_ms_per_request(ctx, name: str) -> float | None:
    """A stage's milliseconds of the window per completed request; None
    where the program records no such stage."""
    total = stage_total_s(ctx.metrics_window, name)
    if total is None or not ctx.completed():
        return None
    return 1e3 * total / len(ctx.completed())
