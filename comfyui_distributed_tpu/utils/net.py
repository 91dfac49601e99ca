"""Network + async helpers.

Capability parity with reference ``utils/network.py:11-40`` (pooled client
session, error responder) and ``utils/async_helpers.py:9-50``
(sync->async bridge), plus the network-info / master-IP heuristics of
reference ``distributed.py:93-207``.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import socket
import threading
import time
from typing import Any, Dict, List, Optional

import aiohttp

from comfyui_distributed_tpu.utils.logging import debug_log, log

import weakref

_sessions: "weakref.WeakKeyDictionary[asyncio.AbstractEventLoop, aiohttp.ClientSession]" = (
    weakref.WeakKeyDictionary())
_session_lock = threading.Lock()


async def get_client_session() -> aiohttp.ClientSession:
    """Shared pooled session (reference ``utils/network.py:14-22``).

    One session per event loop, keyed weakly by the loop object itself: an
    aiohttp session is bound to the loop that created it, and id()-keying
    would alias a dead loop's session onto a new loop allocated at the same
    address."""
    loop = asyncio.get_running_loop()
    with _session_lock:
        sess = _sessions.get(loop)
        if sess is None or sess.closed:
            connector = aiohttp.TCPConnector(limit=100, limit_per_host=30)
            sess = aiohttp.ClientSession(connector=connector)
            _sessions[loop] = sess
        return sess


async def cleanup_client_session() -> None:
    loop = asyncio.get_running_loop()
    with _session_lock:
        sess = _sessions.pop(loop, None)
    if sess is not None and not sess.closed:
        await sess.close()


def handle_api_error(request, error: Exception, status: int = 500):
    """JSON error responder (reference ``utils/network.py:28-33``)."""
    from aiohttp import web
    log(f"API error on {getattr(request, 'path', '?')}: {error}")
    return web.json_response({"status": "error", "message": str(error)},
                             status=status)


def run_async_in_loop(coro, loop: asyncio.AbstractEventLoop,
                      timeout: Optional[float] = None):
    """Run a coroutine on a foreign event loop from sync code and block for the
    result (reference ``run_async_in_server_loop``,
    ``utils/async_helpers.py:9-50``).  Raises if called *on* that loop's
    thread, which would deadlock — the hazard SURVEY.md §5 flags."""
    try:
        running = asyncio.get_running_loop()
    except RuntimeError:
        running = None
    if running is loop:
        raise RuntimeError("run_async_in_loop called from the target loop; "
                           "await the coroutine instead")
    fut = asyncio.run_coroutine_threadsafe(coro, loop)
    try:
        return fut.result(timeout=timeout)
    except concurrent.futures.TimeoutError:
        fut.cancel()
        raise TimeoutError(f"coroutine timed out after {timeout}s")


def _retry_after_hint(headers) -> Optional[float]:
    """Parse a Retry-After header (delta-seconds form only — the HTTP
    date form isn't worth a date parser on this hot path) into a
    bounded sleep, or None."""
    from comfyui_distributed_tpu.utils import constants as C
    raw = (headers or {}).get("Retry-After")
    if raw is None:
        return None
    try:
        return min(max(float(raw), 0.0), C.RETRY_AFTER_CAP_S)
    except (TypeError, ValueError):
        return None


def backoff_delays(retries: int, rng=None) -> List[float]:
    """The jittered exponential backoff schedule ``post_form_with_retry``
    sleeps between attempts: ``min(base * 2^k, cap) * uniform[1-j, 1]``.

    Jitter exists for the fleet, not the caller: when one master restart
    fails every worker's in-flight send at the same instant, a fixed
    cadence re-synchronizes all their retries into periodic thundering
    herds — exactly the overload signature the chaos harness provokes.
    Pure function (injectable ``rng``) so the de-synchronization is
    testable."""
    import random as _random

    from comfyui_distributed_tpu.utils import constants as C
    rng = rng or _random
    out = []
    delay = C.SEND_BACKOFF_BASE
    for _ in range(max(retries - 1, 0)):
        out.append(delay * rng.uniform(1.0 - C.SEND_JITTER_FRACTION, 1.0))
        delay = min(delay * 2, C.SEND_BACKOFF_CAP)
    return out


async def post_form_with_retry(url: str, make_form, timeout: float,
                               max_retries: Optional[int] = None,
                               what: str = "upload",
                               headers: Optional[Dict[str, str]] = None
                               ) -> None:
    """POST a multipart form with jittered exponential backoff, retrying
    any error including 404 (the queue-not-ready race the reference's
    tile sender retries through, ``distributed_upscale.py:618-665``).
    ``make_form`` is a zero-arg factory — FormData payloads are
    single-use.  ``headers`` rides every attempt (the worker->master
    data-plane hop carries its traceparent here so the master can stitch
    the job's distributed trace together).

    Overload behavior (ISSUE 9): each attempt's wall clock is capped at
    ``SEND_ATTEMPT_TIMEOUT_CAP`` so one black-holed connection can't eat
    the whole retry budget; a ``Retry-After`` header on a 429/503
    response overrides the computed backoff (the server's drain-rate
    hint beats our exponential guess); and the chaos harness may drop or
    delay an attempt here — the client-side half of a flaky network."""
    from comfyui_distributed_tpu.utils import chaos as chaos_mod
    from comfyui_distributed_tpu.utils import constants as C
    retries = max_retries if max_retries is not None else C.SEND_MAX_RETRIES
    delays = backoff_delays(retries)
    attempt_timeout = min(timeout, C.SEND_ATTEMPT_TIMEOUT_CAP)
    for attempt in range(retries):
        retry_after = None
        try:
            cm = chaos_mod.get_chaos()
            if cm.active:
                extra = cm.client_edge(url, what=what)  # may raise (drop)
                if extra > 0:
                    await asyncio.sleep(extra)
            # re-acquire per attempt: a peer's cleanup can close the
            # shared session mid-retry (get_client_session then hands
            # out a fresh one) — holding one reference across the loop
            # would turn a transient close into N guaranteed failures
            session = await get_client_session()
            async with session.post(
                    url, data=make_form(), headers=headers or None,
                    timeout=aiohttp.ClientTimeout(
                        total=attempt_timeout)) as resp:
                if resp.status == 200:
                    return
                if resp.status in (429, 503):
                    retry_after = _retry_after_hint(resp.headers)
                body = await resp.text()
                raise RuntimeError(f"{what} {resp.status}: {body[:100]}")
        except Exception as e:  # noqa: BLE001 - retry transport + status
            if attempt == retries - 1:
                raise
            debug_log(f"{what} retry {attempt + 1}: {e}")
            # honor the server's shed hint when it's LONGER than our
            # backoff: a 429'd sender hammering at its own cadence is
            # the retry storm the hint exists to prevent
            await asyncio.sleep(max(delays[attempt], retry_after or 0.0))


# --- overlapped host-IO pool -------------------------------------------------

class HostIOPool:
    """Bounded encoder/uploader pool: device->host fetches, PNG/tensor
    encodes and disk writes move here so job N's host edge overlaps job
    N+1's device compute (JAX's async dispatch makes the overlap free
    once nothing synchronizes on the executor thread).

    Bounded on purpose: ``max_pending`` in-flight tasks, then ``submit``
    blocks the producer — device compute can outrun a slow disk/NIC
    without buffering unbounded decoded batches in host RAM."""

    def __init__(self, max_workers: Optional[int] = None,
                 max_pending: Optional[int] = None):
        import concurrent.futures
        import functools
        import os

        from comfyui_distributed_tpu.utils import constants as C
        from comfyui_distributed_tpu.utils import trace as trace_mod
        max_workers = max_workers or int(os.environ.get(
            C.HOSTIO_THREADS_ENV, C.HOSTIO_THREADS_DEFAULT))
        max_pending = max_pending or int(os.environ.get(
            C.HOSTIO_PENDING_ENV, C.HOSTIO_PENDING_DEFAULT))
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=max(1, max_workers),
            thread_name_prefix="dtpu-hostio",
            initializer=functools.partial(trace_mod.thread_role,
                                          trace_mod.HOST_POOL))
        self._slots = threading.BoundedSemaphore(max(1, max_pending))
        self._pending = 0  # guarded-by: self._idle
        self._idle = threading.Condition(threading.Lock())

    @property
    def pending(self) -> int:
        with self._idle:
            return self._pending

    def submit(self, fn, *args, stage: Optional[str] = None):
        """Schedule ``fn(*args)`` on the pool; returns a Future.

        The submitting thread's transfer attribution (workflow node +
        per-run sinks) AND its request-trace span context are captured and
        re-entered in the worker, so the deferred d2h still lands in the
        run's ledger and deferred stage spans still attach to the job's
        trace; ``stage`` times the task into the pipeline stage
        timeline."""
        from comfyui_distributed_tpu.utils import trace as trace_mod
        captured = trace_mod.capture_transfer_context()
        captured_span = trace_mod.capture_span_context()
        self._slots.acquire()
        with self._idle:
            self._pending += 1
        submitted_ns = trace_mod.now_ns()

        def run():
            trace_mod.woke("pool", submitted_ns)
            try:
                with trace_mod.transfer_context(captured), \
                        trace_mod.use_span(captured_span):
                    if stage:
                        with trace_mod.stage(stage):
                            return fn(*args)
                    return fn(*args)
            finally:
                with self._idle:
                    self._pending -= 1
                    if self._pending == 0:
                        self._idle.notify_all()
                self._slots.release()

        return self._pool.submit(run)

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Block until every submitted task finished; False on timeout."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._idle:
            while self._pending > 0:
                remaining = None if deadline is None \
                    else deadline - time.monotonic()
                if remaining is not None and remaining <= 0:
                    return False
                self._idle.wait(timeout=remaining)
        return True

    def shutdown(self, wait: bool = True) -> None:
        self._pool.shutdown(wait=wait, cancel_futures=not wait)


# --- wire-format negotiation -------------------------------------------------

# master_url -> (negotiated upload content type, tensor codec); one
# probe per master per process (a fallen-back master stays PNG until
# reset_wire_cache()).
_wire_formats: Dict[str, tuple] = {}
_wire_lock = threading.Lock()


def reset_wire_cache() -> None:
    with _wire_lock:
        _wire_formats.clear()


def wire_codec(master_url: str) -> str:
    """The tensor codec negotiated with ``master_url`` (after
    :func:`negotiate_wire_format` ran); zlib — the floor every build
    decodes — when nothing is cached."""
    with _wire_lock:
        return _wire_formats.get(master_url, ("", "zlib"))[1]


async def negotiate_wire_format(master_url: str) -> str:
    """The upload content type to use toward ``master_url``.

    Probes ``GET /distributed/wire_formats`` once with an ``Accept``
    header naming the raw-tensor type; a master that lists it back gets
    raw-tensor uploads in the best codec BOTH sides support (the
    response's ``tensor_codecs`` ∩ ours — a zstd-capable worker must
    never send zstd at a deflate-only master), anything else (404 from
    an older build, network error, ``DTPU_WIRE=png``) falls back to PNG
    — the always-compatible reference wire."""
    import os

    from comfyui_distributed_tpu.utils import constants as C
    from comfyui_distributed_tpu.utils.image import tensor_codecs
    if os.environ.get(C.WIRE_FORMAT_ENV, "").lower() in ("png", "0", "off"):
        return "image/png"
    with _wire_lock:
        cached = _wire_formats.get(master_url)
    if cached is not None:
        return cached[0]
    fmt, codec = "image/png", "zlib"
    try:
        session = await get_client_session()
        async with session.get(
                f"{master_url}/distributed/wire_formats",
                headers={"Accept": C.TENSOR_WIRE_CONTENT_TYPE},
                timeout=aiohttp.ClientTimeout(total=5)) as r:
            if r.status == 200:
                body = await r.json()
                if C.TENSOR_WIRE_CONTENT_TYPE in body.get("formats", []):
                    fmt = C.TENSOR_WIRE_CONTENT_TYPE
                    # peers predating codec negotiation decode zlib only
                    theirs = body.get("tensor_codecs", ["zlib"])
                    codec = next((c for c in tensor_codecs()
                                  if c in theirs), "zlib")
    except Exception as e:  # noqa: BLE001 - negotiation must never fail a job
        debug_log(f"wire negotiation with {master_url} failed ({e}); "
                  f"falling back to PNG")
    with _wire_lock:
        _wire_formats[master_url] = (fmt, codec)
    debug_log(f"wire format for {master_url}: {fmt} ({codec})")
    return fmt


# --- host IP discovery (reference distributed.py:93-207) --------------------

def get_network_ips() -> List[str]:
    """Enumerate candidate host IPs (reference ``get_network_ips``,
    ``distributed.py:98-152``): getaddrinfo + UDP-connect trick."""
    ips: List[str] = []
    try:
        for info in socket.getaddrinfo(socket.gethostname(), None,
                                       family=socket.AF_INET):
            ip = info[4][0]
            if ip not in ips:
                ips.append(ip)
    except socket.gaierror:
        pass
    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        try:
            s.connect(("10.255.255.255", 1))
            ip = s.getsockname()[0]
            if ip not in ips:
                ips.append(ip)
        finally:
            s.close()
    except OSError:
        pass
    if "127.0.0.1" not in ips:
        ips.append("127.0.0.1")
    return ips


def _private_rank(ip: str) -> int:
    """Private-range preference (reference ``get_recommended_ip``,
    ``distributed.py:154-207``): 192.168 > 10. > 172.16-31 > other > loopback."""
    if ip.startswith("192.168."):
        return 0
    if ip.startswith("10."):
        return 1
    if ip.startswith("172."):
        try:
            second = int(ip.split(".")[1])
            if 16 <= second <= 31:
                return 2
        except (IndexError, ValueError):
            pass
    if ip.startswith("127."):
        return 9
    return 5


def get_recommended_ip() -> str:
    ips = get_network_ips()
    return sorted(ips, key=_private_rank)[0]


def network_info() -> Dict[str, Any]:
    ips = get_network_ips()
    return {"ips": ips, "recommended_ip": get_recommended_ip(),
            "hostname": socket.gethostname()}


def find_free_port(host: str = "127.0.0.1") -> int:
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.bind((host, 0))
    port = s.getsockname()[1]
    s.close()
    return port
