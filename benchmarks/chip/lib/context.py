"""What a metric reader is given.  A reader is one file with
``read(ctx) -> float | None``; it returns None where its source does not
exist in this run (no device trace on the CPU), and raises BenchFailure
where the source exists and holds nothing it can read."""

from __future__ import annotations

import dataclasses

from .flops import request_shape
from .server import BenchFailure


@dataclasses.dataclass
class Context:
    cell: dict                  # the workloads entry
    config: dict                # the configuration file
    mix: dict                   # the traffic file
    seconds: float              # the window's length
    images_per_request: int     # data axis x batch_size
    setup_s: float              # process start to window start
    records: list               # one per attempted request of the window
    window: dict                # the loader's facts about the window
    metrics_setup: dict         # /distributed/metrics at window start
    metrics_window: dict        # the same after the window; aggregates were
                                # reset at window start, so these are its own
    compiles_in_window: int     # retraces.compiles, after minus before
    resource: dict              # /distributed/resource after the window
    device: dict                # platform, kind, count as the server says
    peaks: dict | None          # the peak table's row for device.kind
    trace: dict | None          # xplane.reduce(), or None with no trace

    def completed(self) -> list:
        return [r for r in self.records if r["done"] is not None
                and (r["entry"] or {}).get("status") == "success"]

    def latencies(self) -> list:
        """Seconds from due-to-send to the id on /history, over every
        attempted request that completed."""
        return [r["done"] - r["due"] for r in self.completed()]

    def stage(self, name: str) -> dict | None:
        """One of the program's ``pipeline.stages`` in the window."""
        return self.metrics_window["pipeline"]["stages"].get(name)

    def program(self, key: str) -> dict | None:
        """Executions and device seconds of a layer's jitted programs in
        the trace slice, mean over the chips.  None with no trace; a
        trace in which the configuration's pattern matches no program is
        an error, never a 0."""
        if self.trace is None:
            return None
        if key not in self.config["programs"]:
            raise BenchFailure(f"configuration {self.config['name']!r} "
                               f"names no program pattern {key!r}")
        rows = [c["programs"][key] for c in self.trace["chips"]]
        if not rows or min(r["count"] for r in rows) == 0:
            seen = sorted({m for c in self.trace["chips"]
                           for m in c["modules"]})
            raise BenchFailure(
                f"pattern {self.config['programs'][key]!r} ({key}) matches "
                f"no program on some chip; the trace has {seen}")
        n = len(rows)
        return {"count": sum(r["count"] for r in rows) / n,
                "total_s": sum(r["total_s"] for r in rows) / n}

    def program_s_per_image(self, key: str) -> float | None:
        """Mean device seconds of one whole execution of a layer's
        program, over the images it handles on a chip (the request's
        batch_size)."""
        prog = self.program(key)
        if prog is None:
            return None
        batch = request_shape(self.config["graph"])["batch_size"]
        return prog["total_s"] / prog["count"] / batch
