"""What the span and kernel-class readers share: the program's own summary
of its device trace (``GET /distributed/metrics`` -> ``profile``, written
by ``POST /distributed/profile/stop``), and its stages and counters.

A device_trace reader gets None where the run has no device trace (the
CPU rehearsal) or the program writes no summary (a checkout from before
it did); a summary in which no operation carries a path (``names_found``
false) is an error, never a 0.
"""

from __future__ import annotations

import re

from .flops import request_shape
from .server import BenchFailure


def summary(ctx) -> dict | None:
    if ctx.trace is None:
        return None
    prof = ctx.metrics_window.get("profile")
    if not prof or not prof.get("chips"):
        return None
    if not prof.get("names_found"):
        raise BenchFailure(
            "the program's trace summary found no op_name path on any "
            "device operation, so no kernel class can be read "
            "(statistics seen on an operation: "
            f"{prof.get('op_stat_names')})")
    return prof


def denoise_classes(ctx) -> dict | None:
    """Device seconds per whole denoise execution by kernel class (with
    ``other`` and ``gaps``), mean over chips."""
    prof = summary(ctx)
    if prof is None:
        return None
    pattern = re.compile(ctx.config["programs"]["denoise"])
    rows = [p for name, p in prof["programs"].items()
            if pattern.search(name)]
    if len(rows) != 1:
        raise BenchFailure(
            f"{len(rows)} programs of the summary match the denoise "
            f"pattern {pattern.pattern!r}; it has "
            f"{sorted(prof['programs'])}")
    return rows[0]["classes"]


def class_s_per_image(ctx, *classes: str) -> float | None:
    per_class = denoise_classes(ctx)
    if per_class is None:
        return None
    batch = request_shape(ctx.config["graph"])["batch_size"]
    return sum(per_class.get(c, 0.0) for c in classes) / batch


def idle_under(ctx, span: str) -> float | None:
    """Idle seconds between programs under a ``dtpu/<span>`` host span.
    None where no such span lies wholly inside the slice (the profiler
    drops an annotation that began before it started or ends after it
    stopped): the share is then unknown, not 0."""
    prof = summary(ctx)
    if prof is None or span not in prof.get("host_spans", []):
        return None
    return prof["idle_under"].get(span, 0.0)


def stage_total_s(metrics: dict, name: str) -> float | None:
    """Total seconds of one ``pipeline.stages`` entry, None where the
    program records no such stage."""
    row = metrics["pipeline"]["stages"].get(name)
    return None if row is None else float(row["total_s"])
