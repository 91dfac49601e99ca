"""The program's own reduction of a device trace: where the device time
went, by name, without TensorBoard.

``trace.stop_device_trace()`` runs this module as a child process
(``python -m comfyui_distributed_tpu.utils.trace_summary <xplane.pb>
<summary.json> <traced seconds>``, ``JAX_PLATFORMS=cpu``) on the
``.xplane.pb`` the profiler just wrote.  Two stages, so the second can be
tested on made-up and recorded events without JAX:

  read_events(path)   .xplane.pb -> plain lists (needs ``jax.profiler``)
  summarize(events)   events -> the summary

What a TPU trace holds: one plane per chip (``/device:TPU:<n>``) whose
line ``XLA Modules`` has one event per execution of a jitted program and
whose line ``XLA Ops`` one per HLO operation, nested where an operation
(a ``while``) contains others; ``/host:CPU`` has one line per host
thread, on which ``trace.stage()`` / ``span()`` leave ``dtpu/<name>``
annotations.  The summary holds, per chip and as a mean over chips:

* ``programs``: for each jitted program (module name without its id) the
  whole executions seen, their mean seconds, and per class of
  ``trace.KERNEL_CLASSES`` the leaf-operation seconds per execution (the
  class of the path the operation's metadata carries, ``other`` for an
  operation under none, ``top_other`` naming the costliest of those),
  ``gaps`` for the time inside the execution in which no operation ran.
  The rows add up to the execution's seconds.  Where a program's paths
  carry a phase (``trace.PHASES``: a scope right under the model's),
  ``phases`` has the same leaf-operation seconds by phase (without the
  gaps, and without what lies under no phase); a program without such a
  scope has no ``phases`` key.  Beside them ``account``, in which every
  nanosecond of a whole execution has exactly ONE owner.  (``classes``
  has not: it takes an operation for a container wherever another event
  begins inside it, so one that overlaps the next in part, or has the
  zero-duration custom call that marks a prefetch at its own start, is
  left out whole and its seconds read as ``gaps``.)  Its operations are
  the events of a duration that wholly contain no other (`_operations`).
  ``by_class``: the EXCLUSIVE seconds per execution by class (where two
  overlap the instant is the one's that began first, a tie the
  longer's), with ``other``, and with ``idle`` for the time in which
  nothing ran, so the rows add up to the execution's seconds by
  construction; ``overlap_s``: what a sum of those operations' durations
  counts twice; ``dropped_s``: the durations of those ``classes`` leaves
  out (``classes["gaps"] + overlap_s - dropped_s == by_class["idle"]``);
  ``by_phase`` (only where the paths carry a phase): the same seconds as
  ``{phase: {class: s}}`` with ``none`` for what lies under no phase, an
  idle stretch under the phase of the operation that ENDS it (the device
  was waiting to start that; the stretch after the last operation is
  ``none``'s), so a phase's rows add up to its wall seconds and the
  phases to the execution's; ``top_idle``: the idle stretches that cost
  most, by the two operations on either side (``before``, ``after``:
  paths, else HLO names; stretches between the same two are one row),
  ``n`` stretches and ``s`` seconds an execution;
* ``idle``: the seconds *between* program executions in which no
  operation ran, by the innermost ``dtpu/`` host span over each gap
  (``none`` under none), and ``idle_under``: the same seconds under each
  span name at any depth; ``gaps_in_programs_s``: the idle seconds
  inside executions, which no host span can answer for;
* where the trace has a host timeline beside it (``host_timeline.json``,
  written by ``trace.stop_device_trace`` since PR 51, and the two
  ``dtpu/clock_sync`` annotations that say where its clock stands on the
  trace's: ``clock_drift_ns`` is what the second disagrees by, and past
  MAX_DRIFT_NS the summary carries ``timeline_error`` and none of the
  following): ``idle_by_executor``, the same seconds between executions
  and the slice's two edges with them (``idle_between_s``), by the ONE
  thread whose next enqueue the device waits for: cut at the edges of the
  ``executor`` role's intervals, each piece is the executor's innermost
  interval's at that instant (an interval the slice's edge cuts counts,
  clipped), a collector pause on any thread wins over it, ``unowned``
  where the executor has none, so the rows add up to ``idle_between_s``
  by construction; ``idle_by_executor_class``, the same seconds in the
  six rows of EXEC_CLASSES; ``idle_under_executor``, the same seconds
  under each of the executor's intervals at ANY depth (``dispatch`` is
  never the innermost: a node's span always lies in it), as
  ``idle_under`` is to ``idle``; ``top_idle_between``, the stretches that
  cost most by their owner and the programs on either side (``s``
  seconds, ``n`` stretches, ``owner``, ``before``, ``after``);
* ``programs_per_denoise`` (PR 52): how many programs the host enqueues
  an image batch: the executions of ANY program that begin from the
  first whole execution of the denoise (``jit_core``) to the last one's
  start, by the denoises that begin there, summed over the chips, so the
  slice's edges cut nothing.  Each is an enqueue from Python, and the
  device is dry while the next is being made unless a longer one covers
  it.  Absent where no chip saw two whole denoises;
* ``names_found``: whether any operation carried a path at all.
"""

from __future__ import annotations

import json
import os
import re
import sys
from typing import Any, Dict, List, Optional

import numpy as np

from comfyui_distributed_tpu.utils.trace import CLOCK_SYNC, EXECUTOR, \
    GC_PAUSE, HOST_PREFIX, OTHER, TIMELINE_FILE, WAKE_PREFIX, classify, \
    phase_of

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
EDGE_NS = 10_000        # an execution this close to the slice's edge is cut
# the denoise scan's program: ``DiffusionPipeline.sample`` jits a function
# named ``core`` (the benchmark's configurations name the same pattern)
DENOISE_PROGRAM = "jit_core"
_MODULE_ID = re.compile(r"\(\d+\)$")
GAPS, NONE, IDLE = "gaps", "none", "idle"
OP_NAME = "tf_op"       # the event-metadata statistic that holds op_name
TOP_OTHER = 8           # the costliest unclassed operations a program lists
TOP_IDLE = 8            # and the costliest idle stretches inside it
STARTS, ENDS = "(the execution starts)", "(the execution ends)"
SLICE_STARTS, SLICE_ENDS = "(the slice starts)", "(the slice ends)"
MAX_DRIFT_NS = 100_000  # the two clock markers may disagree by this much
UNOWNED = "unowned"
# who the idle time between programs waited for, by the executor's
# interval over it: its own code, a hand-over from another thread
# (``wake_*``), a request, the device, the collector, nothing it timed
EXEC_CLASSES = ("host", "wake", "wait_request", "wait_device", "gc", UNOWNED)
WAIT_REQUEST = ("exec_idle",)
WAIT_DEVICE = ("device_wait", "lm_drain_wait")


# --- the event metadata, straight from the protobuf ---------------------------
#
# ``jax.profiler.ProfileData`` hands out an event's own statistics (its
# device offset and duration) but not those of its metadata entry, and
# that is where a TPU trace keeps what an operation *is*: the name stack
# it was traced under, its category, its source line.  The few fields
# needed are read from the wire format directly (tsl/profiler/protobuf/
# xplane.proto: XSpace.planes=1; XPlane.name=2, .lines=3,
# .event_metadata=4, .stat_metadata=5; XEventMetadata.id=1, .name=2,
# .stats=5; XStat.metadata_id=1, .str_value=5, .bytes_value=6,
# .ref_value=7; XStatMetadata.id=1, .name=2).  The lines, which are
# nearly all of the file, are skipped whole.

def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, i, end):
    """(field number, wire type, value) of one message; a length-delimited
    value is its (start, end) in ``buf``."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 1:
            value, i = None, i + 8
        elif wire == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, wire, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def read_op_metadata(path: str) -> Dict[str, Dict[str, Dict[str, str]]]:
    """Per device plane: event-metadata name (the HLO instruction as the
    trace prints it) -> its string statistics by name."""
    import mmap
    out: Dict[str, Dict[str, Dict[str, str]]] = {}
    with open(path, "rb") as f, \
            mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ) as buf:
        for no, wire, plane in _fields(buf, 0, len(buf)):
            if no != 1 or wire != 2:
                continue
            name, entries, stat_names = "", [], {}
            for pno, pwire, val in _fields(buf, *plane):
                if pno == 2:
                    name = _text(buf, val)
                    if not DEVICE_PLANE.match(name):
                        break
                elif pno == 4:
                    entries.append(val)
                elif pno == 5:
                    sid, sname = 0, ""
                    for eno, _, ev in _fields(buf, *val):
                        if eno == 2:
                            for mno, _, mv in _fields(buf, *ev):
                                if mno == 1:
                                    sid = mv
                                elif mno == 2:
                                    sname = _text(buf, mv)
                    stat_names[sid] = sname
            if not DEVICE_PLANE.match(name):
                continue
            table: Dict[str, Dict[str, str]] = {}
            for entry in entries:
                for eno, _, ev in _fields(buf, *entry):
                    if eno != 2:
                        continue
                    ename, stats = "", {}
                    for mno, mwire, mv in _fields(buf, *ev):
                        if mno == 2:
                            ename = _text(buf, mv)
                        elif mno == 5:
                            key, text = 0, None
                            for sno, swire, sv in _fields(buf, *mv):
                                if sno == 1:
                                    key = sv
                                elif sno in (5, 6):
                                    text = _text(buf, sv)
                                elif sno == 7:
                                    text = stat_names.get(sv, "")
                            if text is not None:
                                stats[stat_names.get(key, str(key))] = text
                    table[ename] = stats
            out[name] = table
    return out


def read_events(path: str) -> Dict[str, Any]:
    """The device planes' module and operation events (an operation with
    the path its metadata names) and the host's ``dtpu/`` annotations."""
    from jax.profiler import ProfileData
    metadata = read_op_metadata(path)
    data = ProfileData.from_file(path)
    planes = []
    stat_names: set = set()
    clock_sync = []
    for plane in data.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        if not is_device and plane.name != HOST_PLANE:
            continue
        table = metadata.get(plane.name, {})
        lines = []
        for line in plane.lines:
            if is_device and line.name not in (MODULES_LINE, OPS_LINE):
                continue
            ops = is_device and line.name == OPS_LINE
            names: Dict[str, int] = {}
            idx, start, dur = [], [], []
            for ev in line.events:
                name = ev.name
                if not is_device and not name.startswith(HOST_PREFIX):
                    continue
                if not is_device and name == HOST_PREFIX + CLOCK_SYNC:
                    # no span of the program's: where the timeline's
                    # clock stood when this annotation began
                    perf_ns = dict(ev.stats).get("perf_counter_ns")
                    if perf_ns is not None:
                        clock_sync.append([int(ev.start_ns), int(perf_ns)])
                    continue
                i = names.get(name)
                if i is None:
                    i = names[name] = len(names)
                idx.append(i)
                start.append(int(ev.start_ns))
                dur.append(int(ev.duration_ns))
            row = {"name": line.name, "names": list(names),
                   "name_idx": idx, "start_ns": start, "dur_ns": dur}
            if ops:
                # libtpu keeps the HLO op_name in the statistic "tf_op"
                # ("jit(core)/while/body/closed_call/UNet/mid_attn/
                # blocks_0/attn1/to_q/dot_general:"); a fusion carries
                # its root's
                row["paths"] = [table.get(n, {}).get(OP_NAME, "")
                                for n in row["names"]]
                for n in row["names"][:50]:
                    stat_names.update(table.get(n, {}))
            lines.append(row)
        planes.append({"name": plane.name, "lines": lines})
    events = {"planes": planes, "op_stat_names": sorted(stat_names)}
    beside = os.path.join(os.path.dirname(path), TIMELINE_FILE)
    if clock_sync and os.path.isfile(beside):
        with open(beside, encoding="utf-8") as f:
            events.update(clock_sync=sorted(clock_sync),
                          timeline=json.load(f))
        events["timeline"]["bytes"] = os.path.getsize(beside)
    return events


def _arrays(line: dict):
    return (np.asarray(line["start_ns"], np.int64),
            np.asarray(line["dur_ns"], np.int64),
            np.asarray(line["name_idx"], np.int64))


def _leaves(start, dur):
    """Mask of the events that contain no other event of their line."""
    order = np.lexsort((-dur, start))
    s, e = start[order], start[order] + dur[order]
    holds_next = np.zeros(len(s), bool)
    holds_next[:-1] = s[1:] < e[:-1]
    holds_next &= dur[order] > 0
    mask = np.ones(len(s), bool)
    mask[order] = ~holds_next
    return mask


def _union(start, end):
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


def _host_spans(events: dict):
    name, start, end = [], [], []
    for plane in events["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            for i, s, d in zip(line["name_idx"], line["start_ns"],
                               line["dur_ns"]):
                label = line["names"][i]
                if label.startswith(HOST_PREFIX) and d > 0:
                    name.append(label[len(HOST_PREFIX):])
                    start.append(s)
                    end.append(s + d)
    return name, np.asarray(start, np.int64), np.asarray(end, np.int64)


def _cut_at(gap_s, gap_e, edges):
    """The gaps cut wherever one of ``edges`` falls inside them, sorted:
    each piece then lies wholly inside or outside whatever the edges
    bound."""
    cuts = np.unique(edges)
    lo = np.searchsorted(cuts, gap_s, "right")
    hi = np.searchsorted(cuts, gap_e, "left")
    ps, pe = [gap_s[hi <= lo]], [gap_e[hi <= lo]]
    for g in np.flatnonzero(hi > lo):
        pieces = np.concatenate(([gap_s[g]], cuts[lo[g]:hi[g]],
                                 [gap_e[g]]))
        ps.append(pieces[:-1])
        pe.append(pieces[1:])
    ps, pe = np.concatenate(ps), np.concatenate(pe)
    order = np.argsort(ps, kind="stable")
    return ps[order], pe[order]


def _idle_by_span(gap_s, gap_e, spans):
    """Seconds of the gaps by the innermost (shortest) host span over
    each, and under each span name at any depth (the gaps cut at the
    spans' edges first)."""
    names, hs, he = spans
    total_ns = int((gap_e - gap_s).sum())
    if not len(hs) or not len(gap_s):
        return ({NONE: total_ns / 1e9} if total_ns else {}), {}
    ps, pe = _cut_at(gap_s, gap_e, np.concatenate((hs, he)))
    mid, dur = (ps + pe) // 2, pe - ps
    distinct = sorted(set(names))
    code = {n: i for i, n in enumerate(distinct)}
    inner = np.full(len(ps), -1, np.int64)
    under = np.zeros((len(distinct), len(ps)), bool)
    first = np.searchsorted(mid, hs, "left")
    last = np.searchsorted(mid, he, "left")
    # longest first, so that a shorter span over the same piece wins
    for k in np.argsort(-(he - hs), kind="stable"):
        if last[k] > first[k]:
            inner[first[k]:last[k]] = code[names[k]]
            under[code[names[k]], first[k]:last[k]] = True
    by_inner = {NONE: float(dur[inner < 0].sum()) / 1e9}
    for n, i in code.items():
        sec = float(dur[inner == i].sum()) / 1e9
        if sec:
            by_inner[n] = sec
    by_under = {n: float(dur[under[i]].sum()) / 1e9
                for n, i in code.items() if under[i].any()}
    return by_inner, by_under


def timeline_on_trace(events: dict) -> Optional[Dict[str, Any]]:
    """The host timeline on the trace's nanoseconds, by the first clock
    marker: the slice's bounds, the ``executor`` role's intervals that are
    of its stack (names, starts, ends, depths) and every thread's
    collector pauses; ``clock_drift_ns``, what the second marker
    disagrees by.  None without a timeline or the marker it names;
    ``{"error": ...}`` where the clocks drifted past MAX_DRIFT_NS."""
    timeline = events.get("timeline")
    on_trace = {perf: at for at, perf in events.get("clock_sync") or []}
    if not timeline or timeline["start_ns"] not in on_trace:
        return None
    t0 = on_trace[timeline["start_ns"]]
    t1 = t0 + timeline["stop_ns"] - timeline["start_ns"]
    drift = on_trace.get(timeline["stop_ns"], t1) - t1
    if abs(drift) > MAX_DRIFT_NS:
        return {"error": f"the clock markers disagree by {drift} ns over "
                         f"{t1 - t0} ns, more than {MAX_DRIFT_NS}: the "
                         f"timeline cannot be laid on this trace"}
    roles = [lane["role"] for lane in timeline["lanes"]]
    names = timeline["names"]
    executor, pauses = [], []
    for lane, name, start, end, depth in timeline["intervals"]:
        if names[name] == GC_PAUSE:
            pauses.append((t0 + start, t0 + end))
        elif roles[lane] == EXECUTOR and depth >= 0:
            executor.append((names[name], t0 + start, t0 + end, depth))
    cols = list(zip(*executor)) or [(), (), (), ()]
    gc_cols = list(zip(*pauses)) or [(), ()]
    return {"t0": t0, "t1": t1, "clock_drift_ns": int(drift),
            "intervals": len(timeline["intervals"]),
            "dropped": int(timeline.get("dropped", 0)),
            "bytes": int(timeline.get("bytes", 0)),
            "names": list(cols[0]),
            "start": np.asarray(cols[1], np.int64),
            "end": np.asarray(cols[2], np.int64),
            "depth": np.asarray(cols[3], np.int64),
            "gc_start": np.asarray(gc_cols[0], np.int64),
            "gc_end": np.asarray(gc_cols[1], np.int64)}


def owner_class(owner: str) -> str:
    """The row of EXEC_CLASSES an owner of ``idle_by_executor`` falls in."""
    if owner == GC_PAUSE:
        return "gc"
    if owner == UNOWNED:
        return UNOWNED
    if owner.startswith(WAKE_PREFIX):
        return "wake"
    if owner in WAIT_REQUEST:
        return "wait_request"
    return "wait_device" if owner in WAIT_DEVICE else "host"


def _idle_by_executor(gap_s, gap_e, before, after, timeline):
    """The gaps (sorted, apart; ``before`` / ``after``: what ran on
    either side of each) by the executor's innermost interval over each
    instant: seconds by owner, seconds under each of its intervals at any
    depth, and the stretches that cost most.  One owner an instant: the
    gaps are cut at every interval's edges, the deepest interval of the
    executor's stack over a piece has it, a collector pause of any thread
    before that, UNOWNED under none."""
    names = timeline["names"]
    start, end = timeline["start"], timeline["end"]
    gcs, gce = timeline["gc_start"], timeline["gc_end"]
    ps, pe = _cut_at(gap_s, gap_e, np.concatenate((start, end, gcs, gce)))
    mid, dur = (ps + pe) // 2, pe - ps
    owners = sorted(set(names)) + [GC_PAUSE, UNOWNED]
    code = {n: i for i, n in enumerate(owners)}
    owner = np.full(len(ps), code[UNOWNED], np.int64)
    under = np.zeros((len(owners), len(ps)), bool)
    first = np.searchsorted(mid, start, "left")
    last = np.searchsorted(mid, end, "left")
    # the shallower first, so that what it holds wins over it
    for k in np.lexsort((start, timeline["depth"])):
        if last[k] > first[k]:
            owner[first[k]:last[k]] = code[names[k]]
            under[code[names[k]], first[k]:last[k]] = True
    for a, b in zip(np.searchsorted(mid, gcs, "left"),
                    np.searchsorted(mid, gce, "left")):
        owner[a:b] = code[GC_PAUSE]
    ns = np.bincount(owner, weights=dur, minlength=len(owners))
    by_owner = {n: float(ns[i]) / 1e9 for n, i in code.items() if ns[i]}
    by_under = {n: float(dur[under[i]].sum()) / 1e9
                for n, i in code.items() if under[i].any()}
    # the stretches between the same two programs under one owner are a row
    gap = np.searchsorted(gap_s, mid, "right") - 1
    rows: Dict[tuple, list] = {}
    for g, o, d in zip(gap.tolist(), owner.tolist(), dur.tolist()):
        row = rows.setdefault((before[g], after[g], owners[o]), [0, set()])
        row[0] += d
        row[1].add(g)
    top = sorted(rows.items(), key=lambda kv: -kv[1][0])[:TOP_IDLE]
    return by_owner, by_under, [
        {"s": total / 1e9, "n": len(gaps), "owner": who, "before": b,
         "after": a} for (b, a, who), (total, gaps) in top]


def _owners(start, end, floor):
    """One owner an instant.  For operations sorted by (start, the longer
    first), each with the start of the execution it lies in (``floor``):
    the nanoseconds each OWNS (from where every operation that began
    before it has ended: the instant is the one's that began first), the
    idle nanoseconds in front of it inside its execution, the position of
    the operation that idle stretch follows (-1: the execution's start),
    and how far the operations up to each reach."""
    reach = np.maximum.accumulate(end)
    prev = np.concatenate(([np.iinfo(np.int64).min], reach[:-1]))
    own = np.maximum(end - np.maximum(start, prev), 0)
    wait = np.maximum(start - np.maximum(prev, floor), 0)
    holder = np.maximum.accumulate(
        np.where(end >= reach, np.arange(len(end)), 0))
    before = np.concatenate(([-1], holder[:-1]))
    before[prev <= floor] = -1
    return own, wait, before, reach


def _operations(start, dur):
    """Mask of the events that themselves run on the device: of a duration,
    and wholly containing no other such event (a ``while`` contains its
    body's).  `_leaves` asks less: there an operation with a zero-duration
    event at its own start (the custom call that marks a prefetch) reads
    as a container, and two that overlap in part as one holding the
    other; here both are operations, and `_owners` says whose an instant
    is."""
    real = np.flatnonzero(dur > 0)
    order = real[np.lexsort((-dur[real], start[real]))]
    s, e = start[order], start[order] + dur[order]
    holds_next = np.zeros(len(s), bool)
    holds_next[:-1] = (s[1:] < e[:-1]) & (e[1:] <= e[:-1])
    mask = np.zeros(len(start), bool)
    mask[order] = ~holds_next
    return mask


def _accounts(ops, labels, classes, phases, leaf, modules, programs):
    """``account`` of every program of ``programs`` (module docstring):
    the operations of the whole executions swept once in the order they
    began, then summed by program, class and phase.  ``labels``,
    ``classes`` and ``phases`` are per operation NAME; ``leaf`` is
    `_leaves`' mask, which ``classes`` sums: what it leaves out of the
    operations here is ``dropped_s``."""
    s, d, idx = ops
    ms, md, whole, prog_of_module = modules
    o = np.flatnonzero(_operations(s, d))
    o = o[np.lexsort((-d[o], s[o]))]
    ex = np.searchsorted(ms, s[o], "right") - 1
    keep = (ex >= 0) & (s[o] < (ms + md)[np.maximum(ex, 0)]) \
        & whole[np.maximum(ex, 0)]
    o, ex = o[keep], ex[keep]
    name, dur = idx[o], d[o]
    floor, roof = ms[ex], (ms + md)[ex]
    own, wait, before, reach = _owners(s[o], np.minimum(s[o] + dur, roof),
                                       floor)
    # the stretch after an execution's last operation: the whole of an
    # execution that holds none
    last = np.flatnonzero(np.diff(ex, append=len(ms)) != 0)
    tail = md.copy()
    tail[ex[last]] = roof[last] - reach[last]
    cls_names, cls_code = np.unique(classes, return_inverse=True)
    # "" (no phase) first, whether or not an operation lies under none
    ph_names, ph_code = np.unique(np.append(phases, ""), return_inverse=True)
    cc, ph = cls_code[name], ph_code[name]
    prog_names, prog_code = np.unique(prog_of_module, return_inverse=True)
    pc = prog_code[ex]
    labels = list(labels) + [ENDS, STARTS]    # [-1] is STARTS
    width = len(labels)
    for code, prog in enumerate(prog_names):
        row = programs.get(str(prog))
        if row is None:
            continue
        mine = pc == code
        runs = whole & (prog_code == code)
        per = 1e9 * row["count"]
        tail_ns = tail[runs].sum()
        by_class = np.bincount(cc[mine], weights=own[mine],
                               minlength=len(cls_names))
        account: Dict[str, Any] = {"by_class": {
            str(c): float(ns) / per
            for c, ns in zip(cls_names, by_class) if ns}}
        account["by_class"][IDLE] = float(wait[mine].sum() + tail_ns) / per
        account["overlap_s"] = float(dur[mine].sum() - own[mine].sum()) / per
        account["dropped_s"] = float(dur[mine & ~leaf[o]].sum()) / per
        if ph[mine].any():
            grid = np.bincount(ph[mine] * len(cls_names) + cc[mine],
                               weights=own[mine],
                               minlength=len(ph_names) * len(cls_names)
                               ).reshape(len(ph_names), len(cls_names))
            waits = np.bincount(ph[mine], weights=wait[mine],
                                minlength=len(ph_names))
            waits[0] += tail_ns
            account["by_phase"] = {}
            for phase, sums, idle_ns in zip(ph_names, grid, waits):
                rows = {str(c): float(ns) / per
                        for c, ns in zip(cls_names, sums) if ns}
                if idle_ns:
                    rows[IDLE] = float(idle_ns) / per
                if rows:
                    account["by_phase"][str(phase) or NONE] = rows
        # the idle stretches, by the two operations on either side
        front = np.flatnonzero(mine & (wait > 0))
        ends = last[mine[last] & (tail[ex[last]] > 0)]
        # an execution that holds no operation is one stretch, end to end
        empty = runs.copy()
        empty[ex[mine]] = False
        pair = np.concatenate((
            np.where(before[front] < 0, -1, name[before[front]]) * width
            + name[front],
            name[ends] * width + width - 2,
            np.full(int(empty.sum()), -2)))
        ns = np.concatenate((wait[front], tail[ex[ends]], tail[empty]))
        pairs, inv = np.unique(pair, return_inverse=True)
        sec = np.bincount(inv, weights=ns, minlength=len(pairs))
        n = np.bincount(inv, minlength=len(pairs))
        account["top_idle"] = [
            {"s": float(sec[i]) / per, "n": float(n[i]) / row["count"],
             "before": labels[pairs[i] // width],
             "after": labels[pairs[i] % width]}
            for i in np.argsort(-sec, kind="stable")[:TOP_IDLE]]
        row["account"] = account


def _chip(plane: dict, spans, timeline=None) -> Dict[str, Any]:
    lines = {ln["name"]: ln for ln in plane["lines"]}
    chip: Dict[str, Any] = {"busy_s": 0.0, "window_s": 0.0, "ops": 0,
                            "gaps_in_programs_s": 0.0, "programs": {},
                            "idle": {}, "idle_under": {},
                            "names_found": False}
    ops_ln, mod_ln = lines.get(OPS_LINE), lines.get(MODULES_LINE)
    if not ops_ln or not ops_ln["start_ns"]:
        return chip
    s, d, idx = _arrays(ops_ln)
    paths = ops_ln.get("paths") or [""] * len(ops_ln["names"])
    classes = np.asarray([classify(p) for p in paths])
    phases = np.asarray([phase_of(p) or "" for p in paths])
    leaf = _leaves(s, d)
    ls, ld, lidx = s[leaf], d[leaf], idx[leaf]
    lclass, lphase = classes[lidx], phases[lidx]
    chip["ops"] = int(leaf.sum())
    chip["names_found"] = bool(np.asarray([bool(p) for p in paths])[lidx]
                               .any())
    t0, t1 = int(s.min()), int((s + d).max())
    if mod_ln and mod_ln["start_ns"]:
        ms, md, midx = _arrays(mod_ln)
        t0, t1 = min(t0, int(ms.min())), max(t1, int((ms + md).max()))
    bs, be = _union(ls, ls + ld)
    chip["busy_s"] = float((be - bs).sum()) / 1e9
    chip["window_s"] = (t1 - t0) / 1e9
    gap_s = np.concatenate(([t0], be))
    gap_e = np.concatenate((bs, [t1]))
    keep = gap_e > gap_s
    gap_s, gap_e = gap_s[keep], gap_e[keep]
    if not mod_ln or not mod_ln["start_ns"]:
        chip["idle"], chip["idle_under"] = _idle_by_span(gap_s, gap_e,
                                                         spans)
        return chip
    order = np.argsort(ms, kind="stable")
    ms, md, midx = ms[order], md[order], midx[order]
    # a gap inside an execution (between two of its operations) is the
    # program's own and is counted with it; the host can only answer for
    # the gaps between executions
    gap_s, gap_e = _cut_at(gap_s, gap_e, np.concatenate((ms, ms + md)))
    mid = (gap_s + gap_e) // 2
    k = np.maximum(np.searchsorted(ms, mid, "right") - 1, 0)
    within = (mid >= ms[k]) & (mid < (ms + md)[k])
    chip["gaps_in_programs_s"] = float(
        (gap_e - gap_s)[within].sum()) / 1e9
    chip["idle"], chip["idle_under"] = _idle_by_span(
        gap_s[~within], gap_e[~within], spans)
    names_of = np.asarray([_MODULE_ID.sub("", n)
                           for n in mod_ln["names"]])
    if timeline is not None:
        # the same gaps, and the slice's two edges, which the timeline can
        # answer for: the profiler was on and no program had begun, or
        # none was left
        idle_s, idle_e = gap_s[~within], gap_e[~within]
        idle_s = np.concatenate(([min(timeline["t0"], t0)], idle_s, [t1]))
        idle_e = np.concatenate(([t0], idle_e, [max(timeline["t1"], t1)]))
        idle_s, idle_e = idle_s[idle_e > idle_s], idle_e[idle_e > idle_s]
        k = np.searchsorted(ms, (idle_s + idle_e) // 2, "right") - 1
        ran = np.concatenate((names_of[midx], [SLICE_ENDS, SLICE_STARTS]))
        chip["idle_between_s"] = float((idle_e - idle_s).sum()) / 1e9
        chip["idle_by_executor"], chip["idle_under_executor"], \
            chip["top_idle_between"] = _idle_by_executor(
                idle_s, idle_e, ran[k], ran[k + 1], timeline)
    whole = (ms > t0 + EDGE_NS) & (ms + md < t1 - EDGE_NS)
    cycles = ms[whole & (names_of[midx] == DENOISE_PROGRAM)]
    if cycles.size > 1:
        chip["denoise_cycles"] = int(cycles.size) - 1
        chip["programs_in_cycles"] = int(
            ((ms >= cycles[0]) & (ms < cycles[-1])).sum())
    # the execution each leaf operation started in
    k = np.searchsorted(ms, ls, "right") - 1
    inside = (k >= 0) & (ls < (ms + md)[np.maximum(k, 0)])
    programs: Dict[str, Dict[str, Any]] = {}
    for m in np.flatnonzero(whole):
        name = _MODULE_ID.sub("", mod_ln["names"][midx[m]])
        row = programs.setdefault(name, {"count": 0, "total_s": 0.0,
                                         "classes": {}})
        row["count"] += 1
        row["total_s"] += md[m] / 1e9
    # an operation's name: the path where there is one, else the HLO
    # instruction as the trace prints it
    labels = [p or n[:120] for p, n in zip(paths, ops_ln["names"])]
    sel = inside & whole[np.maximum(k, 0)]
    prog_of_op = names_of[midx[np.maximum(k, 0)]]
    for name, row in programs.items():
        mine = sel & (prog_of_op == name)
        per_class = {}
        for cl in np.unique(lclass[mine]):
            per_class[str(cl)] = float(
                ld[mine & (lclass == cl)].sum()) / 1e9 / row["count"]
        row["mean_s"] = row["total_s"] / row["count"]
        per_class[GAPS] = row["mean_s"] - sum(per_class.values())
        row["classes"] = per_class
        by_phase = {str(ph): float(ld[mine & (lphase == ph)].sum()) / 1e9
                    / row["count"] for ph in np.unique(lphase[mine]) if ph}
        if by_phase:
            row["phases"] = by_phase
        # what ``other`` holds, by name
        unclassed = mine & (lclass == OTHER)
        sec = np.bincount(lidx[unclassed], weights=ld[unclassed],
                          minlength=len(paths)) / 1e9 / row["count"]
        row["top_other"] = [
            {"op": labels[i], "s": float(sec[i])}
            for i in np.argsort(-sec)[:TOP_OTHER] if sec[i] > 0]
    _accounts((s, d, idx), labels, classes, phases, leaf,
              (ms, md, whole, names_of[midx]), programs)
    chip["programs"] = programs
    return chip


def _mean(rows: List[Dict[str, float]]) -> Dict[str, float]:
    keys = sorted({k for r in rows for k in r})
    return {k: sum(r.get(k, 0.0) for r in rows) / len(rows) for k in keys}


def summarize(events: dict, traced_s: float = 0.0) -> Dict[str, Any]:
    spans = _host_spans(events)
    timeline = timeline_on_trace(events)
    error = (timeline or {}).get("error")
    chips = []
    for plane in events["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        if m:
            chips.append({"chip": int(m.group(1)), **_chip(
                plane, spans, None if error else timeline)})
    chips.sort(key=lambda c: c["chip"])
    out: Dict[str, Any] = {
        "traced_s": float(traced_s), "chips": chips,
        "names_found": any(c["names_found"] for c in chips),
        "host_spans": sorted(set(spans[0])),
        "op_stat_names": events.get("op_stat_names", [])}
    if error:
        out["timeline_error"] = error
    elif timeline is not None:
        out["clock_drift_ns"] = timeline["clock_drift_ns"]
        out["host_timeline"] = {k: timeline[k]
                                for k in ("intervals", "dropped", "bytes")}
    if not chips:
        return out
    # a program counts where every chip saw it whole
    shared = set.intersection(*(set(c["programs"]) for c in chips))
    programs = {}
    for name in sorted(shared):
        rows = [c["programs"][name] for c in chips]
        programs[name] = {
            "count": sum(r["count"] for r in rows) / len(rows),
            "mean_s": sum(r["mean_s"] for r in rows) / len(rows),
            "classes": _mean([r["classes"] for r in rows]),
            "top_other": rows[0]["top_other"]}
        if all("phases" in r for r in rows):
            programs[name]["phases"] = _mean([r["phases"] for r in rows])
        accounts = [r["account"] for r in rows]
        programs[name]["account"] = account = {
            "by_class": _mean([a["by_class"] for a in accounts]),
            "overlap_s": sum(a["overlap_s"] for a in accounts) / len(rows),
            "dropped_s": sum(a["dropped_s"] for a in accounts) / len(rows),
            "top_idle": accounts[0]["top_idle"]}
        if all("by_phase" in a for a in accounts):
            account["by_phase"] = {
                phase: _mean([a["by_phase"].get(phase, {}) for a in accounts])
                for phase in sorted({p for a in accounts
                                     for p in a["by_phase"]})}
    # a slice that starts or ends in an idle gap holds no device event
    # there: the time the profiler was on still counts as idle
    window_s = max(max(c["window_s"] for c in chips), float(traced_s))
    idle = _mean([c["idle"] for c in chips])
    edge = window_s - sum(c["window_s"] for c in chips) / len(chips)
    if edge > 0:
        idle["slice_edge"] = edge
    out.update({
        "window_s": window_s,
        "busy_s": sum(c["busy_s"] for c in chips) / len(chips),
        "gaps_in_programs_s": sum(c["gaps_in_programs_s"]
                                  for c in chips) / len(chips),
        "programs": programs, "idle": idle,
        "idle_under": _mean([c["idle_under"] for c in chips])})
    cycles = sum(c.get("denoise_cycles", 0) for c in chips)
    if cycles:
        out["programs_per_denoise"] = sum(
            c.get("programs_in_cycles", 0) for c in chips) / cycles
    if all("idle_by_executor" in c for c in chips):
        by_owner = _mean([c["idle_by_executor"] for c in chips])
        by_class = dict.fromkeys(EXEC_CLASSES, 0.0)
        for owner, sec in by_owner.items():
            by_class[owner_class(owner)] += sec
        out.update({
            "idle_between_s": sum(c["idle_between_s"]
                                  for c in chips) / len(chips),
            "idle_by_executor": by_owner,
            "idle_by_executor_class": by_class,
            "idle_under_executor": _mean([c["idle_under_executor"]
                                          for c in chips]),
            "top_idle_between": chips[0]["top_idle_between"]})
    return out


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    path, out_path = argv[0], argv[1]
    traced_s = float(argv[2]) if len(argv) > 2 else 0.0
    summary = summarize(read_events(path), traced_s)
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump(summary, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
