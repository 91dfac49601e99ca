"""One weight stream for the prompt expander's waiting requests.

A decode step of the language model reads all its weights to make one
token; a second, third or fourth row through the same step costs that
row's cache and nothing else.  So when a request reaches its
``LanguageModelGenerate`` node, ``lm_generate`` runs ONCE over that
request (the leader) and over the requests still waiting in the server's
queue whose graphs hold a generate call of the same model and lengths
with literal inputs (the followers); where the leader's rows start from
the snapshot of its ``instructions`` (`LanguageModel.shared_prefix`),
only those that carry the same, so that the execution keeps the short
prefill the snapshot buys; the others run at their own turn.  A follower
stays where it is in the queue: its class, its place and `pop_fair_group` know nothing of
this.  Its words and its ``LM_OUTPUT`` row are kept here and handed over,
once, when its own graph reaches the node.

When the row set is closed.  Dispatch is asynchronous: the host reaches
the node while the device still owes the images of the groups enqueued
before it, and ``lm_generate`` cannot start until they are out.  A leader
whose set has room therefore waits for the device to have produced the
last of them (each group's ``device_ready``, counted by the server) and
closes the set with whoever is queued THEN: the callers whose images came
out meanwhile have posted again and ride along.  The wait has no limit of
its own and needs none: it ends with work that is already enqueued and
that the host does not feed, during which this execution could not have
begun, and every way such a group ends lets it go (`ServerState.
_image_settled`).  Where the device owes nothing (an empty server, no
overlap) or the set is full when the host arrives, nothing waits.  The
device is idle from the drain's end to the enqueue, so what can be done
before the wait is: the queued graphs are parsed and their rows encoded
then (`LanguageModel.prompt_ids` keeps a request's ids), and the second
look at the queue walks only a row that joined meanwhile.

What is kept is keyed on everything the result is a function of (model,
lengths, instructions, text, seed, temperature), read again from the values the
follower's node is really called with: a request that was edited, or
whose inputs the graph alone did not give away, finds nothing under its
key and runs on its own.  At most ``model.row_counts[-1] - 1`` results are
kept at a time; a follower that ends any other way (purged as abandoned,
cancelled by a drain, run by the step executor, failed before its node)
has its result dropped when it is finalized, or is never kept if it left
the queue while the execution ran.  An error in a shared execution is the
leader's: nothing is kept, and each follower runs alone at its turn.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

from comfyui_distributed_tpu.models.registry import LMRow
from comfyui_distributed_tpu.ops.base import get_op
from comfyui_distributed_tpu.utils import trace as trace_mod
from comfyui_distributed_tpu.workflow.graph import parse_workflow

NODE = "LanguageModelGenerate"


class Waiting(NamedTuple):
    """A queued request's generate call that an execution has room for."""
    pid: str
    row: LMRow
    span: Any           # the request's root span
    ids: Any            # `LanguageModel.prompt_ids` of the row


class GenerateHandover:
    """What a generate node sees of the server's queue (``OpContext.
    lm_handover``), and where the rows it ran for queued requests wait
    for them."""

    def __init__(self, state: Any):
        self._state = state
        self._lock = threading.Lock()
        # (prompt id, key, (words, LMOutput)) in the order they were made
        self._kept: List[Tuple[str, tuple, tuple]] = []  # guarded-by: self._lock

    def generate(self, model: Any, row: LMRow, max_new_tokens: int,
                 prompt_tokens: int) -> tuple:
        """``(words, LMOutput)`` for the request whose node this is: what
        an earlier execution kept for it, else one execution over it and
        over whoever waits behind it."""
        def key(of: LMRow) -> tuple:
            return (model.name, max_new_tokens, prompt_tokens, of)

        with self._lock:
            at = next((i for i, kept in enumerate(self._kept)
                       if kept[1] == key(row)), None)
            mine = self._kept.pop(at)[2] if at is not None else None
        bump = trace_mod.GLOBAL_COUNTERS.bump
        if mine is not None:
            bump("lm.followers_served")
            return mine
        # before the wait, so that only the look at the queue lies between
        # the drain and the enqueue: the queued graphs are parsed (`_calls`
        # keeps them), their rows encoded (the model keeps the ids) and a
        # leader that cannot be encoded is refused
        ids = model.prompt_ids(row.text, prompt_tokens, row.instructions)
        shared = None if model.shared_prefix([row], prompt_tokens, [ids]) \
            is None else row.instructions
        waiting, full = self._waiting(model, max_new_tokens, prompt_tokens,
                                      shared)
        if not full and self._drain_wait():
            here = {w.pid for w in waiting}
            waiting, _ = self._waiting(model, max_new_tokens, prompt_tokens,
                                       shared)
            bump("lm.drain_waits")
            bump("lm.rows_joined_in_drain",
                 sum(w.pid not in here for w in waiting))
        results = model.generate_rows(
            [row] + [w.row for w in waiting], max_new_tokens, prompt_tokens,
            spans=[w.span for w in waiting],
            ids=[ids] + [w.ids for w in waiting])
        made = [(w.pid, key(w.row), result)
                for w, result in zip(waiting, results[1:])]
        # a follower that left the queue while the execution ran (a drain
        # that timed out) was finalized before this was made, and nobody
        # would drop it later.  Under the lock `drop` takes: a purge lands
        # wholly before this or wholly after
        with self._lock:
            with self._state._queue_lock:
                queued = {item["id"] for item in self._state._queue}
            self._kept += [m for m in made if m[0] in queued]
        gone = sum(m[0] not in queued for m in made)
        if gone:
            bump("lm.followers_dropped", gone)
        return results[0]

    def drop(self, pid: str) -> None:
        """The request left the queue without reaching its node, or has
        run: nothing is kept for it any longer."""
        with self._lock:
            before = len(self._kept)
            self._kept = [k for k in self._kept if k[0] != pid]
            dropped = before - len(self._kept)
        if dropped:
            trace_mod.GLOBAL_COUNTERS.bump("lm.followers_dropped", dropped)

    def kept(self) -> int:
        with self._lock:
            return len(self._kept)

    def _drain_wait(self) -> bool:
        """Until the device has produced every image enqueued before this
        node was reached; False, at once, where it owes none.  The host
        meets the device here as it does in ``lm_generate``'s wait: not
        the host's own seconds of ``dispatch``."""
        state = self._state
        began_ns = trace_mod.now_ns()
        with state._queue_lock:
            if not state._owed:
                return False
        with trace_mod.stage("lm_drain_wait"), trace_mod.device_wait():
            with state._queue_lock:
                while state._owed:
                    state._drained.wait()
                drained_ns = state._drained_ns
            # from the LAST image's notify on the host pool's thread to
            # here: the set was not empty at `began_ns`, so one came since
            trace_mod.woke("drain", drained_ns, began_ns)
        return True

    def _waiting(self, model: Any, max_new_tokens: int, prompt_tokens: int,
                 shared: Optional[str] = None
                 ) -> Tuple[List[Waiting], bool]:
        """The queued requests' calls this execution has room for, in
        queue order, and whether they fill it.  ``shared``: the
        instructions whose snapshot the leader starts from; a call with
        others does not join."""
        state = self._state
        with state._queue_lock:
            queued = list(state._queue)
        with self._lock:
            served = {pid for pid, _, _ in self._kept}
        room = model.row_counts[-1] - 1 - len(served)
        found: List[Waiting] = []
        for item in queued:
            if item["id"] in served:
                continue
            for name, row, n, p in self._calls(item):
                if len(found) >= room:
                    return found, True
                if (name, n, p) != (model.name, max_new_tokens,
                                    prompt_tokens) \
                        or shared not in (None, row.instructions):
                    continue
                try:
                    ids = model.prompt_ids(row.text, prompt_tokens,
                                           row.instructions)
                except ValueError:
                    continue        # it fails at its own turn, alone
                found.append(Waiting(item["id"], row, item.get("span"), ids))
        return found, len(found) >= room

    def _calls(self, item: Dict[str, Any]) -> list:
        """The generate calls a queued graph holds with literal inputs,
        read once per request."""
        calls = item.get("lm_calls")
        if calls is None:
            calls = item["lm_calls"] = []
            try:
                graph = parse_workflow(item["prompt"])
            except Exception:  # noqa: BLE001 - it fails at its own turn
                return calls
            op = get_op(NODE) if graph.find_by_type(NODE) else None
            for nid in graph.find_by_type(NODE):
                call = op.literal_call(graph, graph.nodes[nid],
                                       self._state.is_worker)
                if call is not None:
                    calls.append(call)
        return calls
