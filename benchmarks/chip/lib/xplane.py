"""From a profiler trace to numbers: the benchmark's own reduction.

Two stages, so that the second can be checked on a recorded slice
without JAX (``testdata/``):

  read_xplane(path)   .xplane.pb -> events (needs ``jax.profiler``; call
                      it only after the server child has exited, with
                      JAX_PLATFORMS=cpu in the environment)
  reduce(events, ..)  events -> busy seconds, per-program device time,
                      top operations, idle gaps with what the host did

What the trace of a TPU holds (read by hand, PERF.md section 5): one
plane per chip, ``/device:TPU:<n>``; on it the line ``XLA Modules`` has
one event per execution of a jitted program, named ``jit_<fn>(<id>)``,
and the line ``XLA Ops`` one event per HLO operation, named by the whole
HLO instruction and nested where an operation (a ``while``) contains
others.  ``/host:CPU`` has one line per host thread, and goes on for
seconds after the device lines end.  So:

* the *window* is the device lines' own range, first start to last end,
  or the time the profiler was on by the caller's clock where that is
  longer: a slice that starts or ends in an idle gap (a server below
  capacity waits up to 0.6 s for a request) holds no device event there,
  and the range alone would drop that idle time;
* device *busy* is the union of the ``XLA Ops`` events that contain no
  other event: a ``while`` that spans a scan's twenty steps would
  otherwise hide every gap inside it;
* a program execution cut by the window's edge is recorded with the part
  inside; per-program times leave out the events that touch an edge of
  their own chip's lines (chips start and stop tracing a little apart).
"""

from __future__ import annotations

import gzip
import json
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
MIN_HOST_EVENT_NS = 100_000     # host events shorter than 0.1 ms explain no gap
GAPS_EXPLAINED = 200            # the longest gaps of a chip that get a host label
EDGE_NS = 10_000                # an event this close to the window's edge is cut
EDGE_GAP = "(slice edge: before the first or after the last device operation)"
_MODULE_ID = re.compile(r"\(\d+\)$")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def short_op(name: str) -> str:
    """An HLO instruction as the trace prints it, without layouts and
    cut to 100 characters: the name, the output shapes, the opcode."""
    return _LAYOUT.sub("", name).lstrip("%")[:100]


def read_xplane(path: str) -> dict:
    """Every event of the device planes' module and operation lines and
    of the host threads, as plain lists."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        if not is_device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if is_device and line.name not in (MODULES_LINE, OPS_LINE):
                continue
            names: dict[str, int] = {}
            idx, start, dur = [], [], []
            for ev in line.events:
                d = int(ev.duration_ns)
                if not is_device and d < MIN_HOST_EVENT_NS:
                    continue
                idx.append(names.setdefault(ev.name, len(names)))
                start.append(int(ev.start_ns))
                dur.append(d)
            lines.append({"name": line.name, "names": list(names),
                          "name_idx": idx, "start_ns": start,
                          "dur_ns": dur})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def dump_events(events: dict, path: str) -> None:
    with gzip.open(path, "wt", encoding="utf-8") as f:
        json.dump(events, f, separators=(",", ":"))


def load_events(path: str) -> dict:
    with gzip.open(path, "rt", encoding="utf-8") as f:
        return json.load(f)


def cut(events: dict, t0_ns: int, t1_ns: int) -> dict:
    """The events that lie wholly inside ``[t0_ns, t1_ns]`` (for cutting
    a small slice out of a recorded trace)."""
    out = []
    for plane in events["planes"]:
        lines = []
        for line in plane["lines"]:
            keep = [i for i, (s, d) in enumerate(
                zip(line["start_ns"], line["dur_ns"]))
                if s >= t0_ns and s + d <= t1_ns]
            used = sorted({line["name_idx"][i] for i in keep})
            remap = {old: new for new, old in enumerate(used)}
            lines.append({
                "name": line["name"],
                "names": [line["names"][i] for i in used],
                "name_idx": [remap[line["name_idx"][i]] for i in keep],
                "start_ns": [line["start_ns"][i] for i in keep],
                "dur_ns": [line["dur_ns"][i] for i in keep]})
        out.append({"name": plane["name"], "lines": lines})
    return {"planes": out}


def _arrays(line: dict):
    return (np.asarray(line["start_ns"], np.int64),
            np.asarray(line["dur_ns"], np.int64),
            np.asarray(line["name_idx"], np.int64))


def _leaves(start, dur):
    """Mask of events that contain no other event of their line."""
    order = np.lexsort((-dur, start))
    s, e = start[order], start[order] + dur[order]
    holds_next = np.zeros(len(s), bool)
    holds_next[:-1] = s[1:] < e[:-1]
    # an event that merely overlaps the next one's start by sharing an
    # edge is no container; one of zero length holds nothing
    holds_next &= dur[order] > 0
    mask = np.ones(len(s), bool)
    mask[order] = ~holds_next
    return mask


def _union(start, end):
    """Merged intervals of (start, end) pairs, as two arrays."""
    if len(start) == 0:
        return start, end
    order = np.argsort(start, kind="stable")
    s, e = start[order], end[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    firsts = np.flatnonzero(new)
    lasts = np.append(firsts[1:] - 1, len(s) - 1)
    return s[firsts], reach[lasts]


def _sum_by_name(names, idx, dur, mask=None):
    if mask is not None:
        idx, dur = idx[mask], dur[mask]
    totals = np.bincount(idx, weights=dur, minlength=len(names))
    counts = np.bincount(idx, minlength=len(names))
    return {names[i]: (int(counts[i]), float(totals[i]) / 1e9)
            for i in np.flatnonzero(counts)}


def _host_events(events: dict):
    label, start, end = [], [], []
    for plane in events["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            thread = line["name"].split("/")[0]
            for i, s, d in zip(line["name_idx"], line["start_ns"],
                               line["dur_ns"]):
                label.append(f"{thread}: {line['names'][i]}"[:120])
                start.append(s)
                end.append(s + d)
    return label, np.asarray(start, np.int64), np.asarray(end, np.int64)


def _explain_gaps(gap_s, gap_e, host):
    """For each gap the host event that covers most of it, the shortest
    such event where several cover it equally: seconds by that label."""
    label, hs, he = host
    out: dict[str, float] = {}
    for gs, ge in zip(gap_s.tolist(), gap_e.tolist()):
        name = "(no host event)"
        if len(hs):
            over = np.minimum(he, ge) - np.maximum(hs, gs)
            best = over.max()
            if best > 0:
                cand = np.flatnonzero(over >= 0.99 * best)
                name = label[int(cand[np.argmin((he - hs)[cand])])]
        out[name] = out.get(name, 0.0) + (ge - gs) / 1e9
    return out


def _top(table: dict, n: int = 10):
    rows = sorted(table.items(), key=lambda kv: -kv[1])[:n]
    return [[name, seconds] for name, seconds in rows]


def reduce(events: dict, programs: dict[str, str],
           traced_s: float = 0.0) -> dict:
    """The numbers per-layer readers take from a trace.

    ``programs`` maps a layer's key to the regular expression that picks
    its jitted programs out of the module names; ``traced_s`` is how long
    the profiler was on by the caller's clock (from ``profile/start``'s
    answer to the call of ``profile/stop``).  Returns, besides the
    per-chip facts, ``window_s`` (first start to last end over the device
    lines, or ``traced_s`` where that is longer), ``busy_s`` (mean over
    chips) and the two ``breakdown`` lists.
    """
    patterns = {k: re.compile(v) for k, v in programs.items()}
    t0 = t1 = None
    for plane in events["planes"]:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            if line["start_ns"]:
                s, d, _ = _arrays(line)
                lo, hi = int(s.min()), int((s + d).max())
                t0 = lo if t0 is None else min(t0, lo)
                t1 = hi if t1 is None else max(t1, hi)
    if t0 is None:
        return {"window_s": 0.0, "chips": [], "busy_s": 0.0,
                "device_ops": [], "idle_gaps": []}
    host = _host_events(events)
    chips = []
    op_totals: dict[str, float] = {}
    for plane in events["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        if not m:
            continue
        lines = {ln["name"]: ln for ln in plane["lines"]}
        chip = {"chip": int(m.group(1)), "busy_s": 0.0, "ops": 0,
                "container_ops": 0, "modules": {}, "programs": {}}
        if OPS_LINE in lines and lines[OPS_LINE]["start_ns"]:
            ln = lines[OPS_LINE]
            s, d, idx = _arrays(ln)
            leaf = _leaves(s, d)
            bs, be = _union(s[leaf], (s + d)[leaf])
            chip["busy_s"] = float((be - bs).sum()) / 1e9
            chip["ops"] = int(leaf.sum())
            chip["container_ops"] = int((~leaf).sum())
            for name, (_, sec) in _sum_by_name(
                    ln["names"], idx, d, leaf).items():
                op_totals[name] = op_totals.get(name, 0.0) + sec
            gs = np.concatenate(([t0], be))
            ge = np.concatenate((bs, [t1]))
            longest = np.argsort(ge - gs)[::-1][:GAPS_EXPLAINED]
            longest = longest[(ge - gs)[longest] > 0]
            chip["idle_gaps"] = _explain_gaps(gs[longest], ge[longest],
                                              host)
            chip["longest_gap_s"] = float((ge - gs).max()) / 1e9
        if MODULES_LINE in lines and lines[MODULES_LINE]["start_ns"]:
            ln = lines[MODULES_LINE]
            s, d, idx = _arrays(ln)
            # the edges are this chip's own: chips start tracing apart
            lo = min(min(v["start_ns"]) for v in lines.values()
                     if v["start_ns"])
            hi = max(max(a + b for a, b in zip(v["start_ns"], v["dur_ns"]))
                     for v in lines.values() if v["start_ns"])
            whole = (s > lo + EDGE_NS) & (s + d < hi - EDGE_NS)
            by_name: dict[str, list] = {}
            for name, (count, sec) in _sum_by_name(
                    ln["names"], idx, d, whole).items():
                row = by_name.setdefault(_MODULE_ID.sub("", name), [0, 0.0])
                row[0] += count
                row[1] += sec
            chip["modules"] = {k: {"count": c, "total_s": t}
                               for k, (c, t) in by_name.items()}
            for key, pat in patterns.items():
                hit = [v for k, v in chip["modules"].items()
                       if pat.search(k)]
                chip["programs"][key] = {
                    "count": sum(v["count"] for v in hit),
                    "total_s": sum(v["total_s"] for v in hit)}
        chips.append(chip)
    chips.sort(key=lambda c: c["chip"])
    n = len(chips)
    window_s = max((t1 - t0) / 1e9, float(traced_s))
    gaps: dict[str, float] = {}
    if n:
        least_busy = min(chips, key=lambda c: c["busy_s"])
        gaps = dict(least_busy.get("idle_gaps", {}))
        if window_s > (t1 - t0) / 1e9:
            gaps[EDGE_GAP] = window_s - (t1 - t0) / 1e9
    return {
        "window_s": window_s,
        "chips": chips,
        "busy_s": sum(c["busy_s"] for c in chips) / n if n else 0.0,
        "device_ops": _top({short_op(k): v / n
                            for k, v in op_totals.items()}) if n else [],
        "idle_gaps": _top(gaps),
    }
