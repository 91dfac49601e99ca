"""Worker process manager.

Capability parity with the reference's ``WorkerProcessManager``
(``distributed.py:603-1021``): spawn worker server processes, daily log
files with session headers, PID persistence in the config file,
revive-or-purge on restart, process-tree kill, cleanup-on-exit hooks and
delayed auto-launch.

On TPU a "worker" is not one-process-per-chip: ONE process drives all of
a host's chips through the mesh, and a chip belongs to one process at a
time.  Managed workers exist for other hosts and for CPU staging — each runs
``python -m comfyui_distributed_tpu.cli worker --port N``.  A worker
launched next to a master that holds the chips must say so in its config
(``"platform": "cpu"``); otherwise it finds no TPU, exits non-zero, and the
start-up watch below reports it.
"""

from __future__ import annotations

import atexit
import datetime
import os
import signal
import sys
import threading
from typing import Any, Dict, List, Optional

from comfyui_distributed_tpu.utils import config as cfg_mod
from comfyui_distributed_tpu.utils import constants as C
from comfyui_distributed_tpu.utils import process as proc
from comfyui_distributed_tpu.utils.constants import WORKER_STARTUP_DELAY
from comfyui_distributed_tpu.utils.logging import debug_log, log

MASTER_PID_ENV = "DTPU_MASTER_PID"

# <checkout>/.jax_cache, resolved from this package's own location: the
# path is part of what a cache hit needs, so it depends on neither the
# CWD, nor ``~``, nor anything made up at run time
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_CHECKOUT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_persistent_compile_cache(
        min_compile_secs: Optional[float] = None) -> str:
    """Turn on JAX's persistent (on-disk) XLA compilation cache and, for
    the whole process, strip the checkout's path from the source
    locations of every program's HLO (profiles and HLO dumps then show
    ``comfyui_distributed_tpu/...`` relative paths).  A fresh process pays
    trace + deserialize for a program an earlier one compiled.
    ``serve``, ``worker``, ``run``, ``bench.py``, ``chip_smoke.py``'s
    children and the tests all call this.

    One rule for the directory: where ``JAX_COMPILATION_CACHE_DIR`` is
    set, JAX already uses it and this sets NO directory in code (a sealed
    machine's cache must be placeable from outside); otherwise it is
    ``<checkout>/.jax_cache``.  Spawned workers resolve the same way, so
    one host's processes share one cache.  Returns the directory in use.

    Why the locations (``jax_hlo_source_file_canonicalization_regex``,
    a process-wide JAX option): a Pallas kernel is serialised into its
    custom call WITH them and that blob is hashed into the cache key as
    it is, so without this a checkout at a new path would compile every
    program that holds the flash kernel again (PERF.md §6, PR 25).
    Programs without a kernel are keyed with their locations stripped
    either way."""
    import re

    import jax
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      re.escape(_CHECKOUT + os.sep))
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = _CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        float(C.COMPILE_CACHE_MIN_COMPILE_SECS
              if min_compile_secs is None else min_compile_secs))
    # cache every entry that clears the time bar, regardless of
    # serialized size
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    log(f"persistent compile cache at {cache_dir}")
    return cache_dir


class WorkerProcessManager:
    """Singleton-ish manager for locally spawned worker processes."""

    def __init__(self, config_path: Optional[str] = None,
                 log_dir: Optional[str] = None,
                 models_dir: Optional[str] = None):
        self.config_path = config_path
        self.models_dir = models_dir
        self.log_dir = log_dir or os.path.join(os.getcwd(), "logs", "workers")
        self.processes: Dict[str, Dict[str, Any]] = {}
        self._lock = threading.Lock()
        self.load_processes()

    # --- launch (reference launch_worker :667, build_launch_command :644) --

    def build_launch_command(self, worker: Dict[str, Any]) -> List[str]:
        cmd = [proc.get_python_executable(), "-m",
               "comfyui_distributed_tpu.cli", "worker",
               "--port", str(worker["port"])]
        if self.config_path:
            cmd.extend(["--config", self.config_path])
        if self.models_dir:
            cmd.extend(["--models-dir", self.models_dir])
        extra = worker.get("extra_args")
        if extra:
            cmd.extend(str(extra).split())
        return cmd

    def _log_file(self, name: str) -> str:
        os.makedirs(self.log_dir, exist_ok=True)
        day = datetime.date.today().strftime("%Y%m%d")
        return os.path.join(self.log_dir, f"{name}_{day}.log")

    def launch_worker(self, worker: Dict[str, Any],
                      stop_on_master_exit: bool = True) -> Dict[str, Any]:
        wid = str(worker["id"])
        with self._lock:
            existing = self.processes.get(wid)
            if existing and (existing.get("pid") is None  # launch in flight
                             or proc.is_process_alive(existing.get("pid", -1))):
                raise RuntimeError(
                    f"worker {wid} already running (pid {existing['pid']})")
            # reserve the slot before releasing the lock so a concurrent
            # launch (auto-launch timer vs HTTP endpoint) can't double-spawn
            self.processes[wid] = {"pid": None, "launching": True}

        try:
            env = dict(os.environ)
            env[MASTER_PID_ENV] = str(os.getpid())
            # cluster identity: the spawned worker heartbeats its lease
            # back to this master (runtime/cluster.maybe_start_heartbeat)
            from comfyui_distributed_tpu.utils import constants as C
            env[C.WORKER_ID_ENV] = wid
            if C.MASTER_URL_ENV not in env:
                try:
                    from comfyui_distributed_tpu.utils import config \
                        as cfg_mod
                    master = cfg_mod.load_config(
                        self.config_path).get("master", {})
                    if master.get("port"):
                        env[C.MASTER_URL_ENV] = (
                            f"http://{master.get('host') or '127.0.0.1'}"
                            f":{master['port']}")
                except Exception:  # noqa: BLE001 - heartbeat is optional
                    pass
            # never inherit the master's pod-cluster identity: a managed
            # HTTP worker is its own single-process jax world, and a
            # duplicate jax.distributed.initialize with the master's
            # process_id would error/block inside the child's CLI boot
            for k in ("DTPU_COORDINATOR", "DTPU_NUM_PROCESSES",
                      "DTPU_PROCESS_ID"):
                env.pop(k, None)
            # a worker next to a master that holds the chips runs on the
            # CPU only when its config says so — pinned by ITS
            # environment, before it imports jax
            if str(worker.get("platform") or "").lower() == "cpu":
                env["JAX_PLATFORMS"] = "cpu"
            # serve-path mesh layout (ISSUE 16): the worker inherits
            # DTPU_TP / DTPU_MESH_SHAPE — resolve them HERE so a
            # malformed layout fails THIS launch with a clear error
            # instead of crashing every spawned worker at mesh build,
            # and the launch log records the fleet's layout
            if env.get(C.TP_ENV) or env.get(C.MESH_SHAPE_ENV):
                from comfyui_distributed_tpu.parallel.mesh import \
                    axes_from_env
                tp_axes = axes_from_env()
                if tp_axes is not None:
                    log(f"worker {wid}: serve-path mesh layout "
                        f"{tp_axes} (inherited)")
            # continuous-batching knobs (ISSUE 17, same fail-fast
            # pattern): a malformed DTPU_CB_SLOTS / DTPU_CB_PARK* value
            # dies at THIS launch with the knob named, instead of
            # poisoning the spawned worker's driver thread at its first
            # admission
            if env.get(C.CB_ENV) or env.get(C.CB_PARK_ENV) \
                    or env.get(C.CB_SLOTS_ENV) \
                    or env.get(C.CB_PARK_MAX_ENV) \
                    or env.get(C.CB_PARK_HBM_FRACTION_ENV):
                from comfyui_distributed_tpu.workflow.batch_executor \
                    import validate_cb_env
                validate_cb_env(env)
                if env.get(C.CB_PARK_ENV):
                    log(f"worker {wid}: continuous batching with "
                        f"latent paging "
                        f"({C.CB_PARK_ENV}={env[C.CB_PARK_ENV]}, "
                        f"max parked="
                        f"{env.get(C.CB_PARK_MAX_ENV) or C.CB_PARK_MAX_DEFAULT})")
            cmd = self.build_launch_command(worker)
            if stop_on_master_exit:
                # wrap with the master-death monitor (reference
                # worker_monitor.py)
                cmd = [proc.get_python_executable(), "-m",
                       "comfyui_distributed_tpu.runtime.monitor",
                       "--master-pid", str(os.getpid()), "--"] + cmd

            log_path = self._log_file(worker.get("name", wid))
            logf = open(log_path, "a", encoding="utf-8")
            try:
                logf.write(f"\n=== session "
                           f"{datetime.datetime.now().isoformat()} "
                           f"cmd={' '.join(cmd)} ===\n")
                logf.flush()
                p = proc.popen_detached(cmd, env=env, stdout=logf,
                                        stderr=logf)
            finally:
                # the child inherited the fd; keeping ours open would leak
                # one per launch across restart cycles
                logf.close()
        except BaseException:
            with self._lock:  # roll back the reservation
                self.processes.pop(wid, None)
            raise
        entry = {
            "pid": p.pid,
            "process": p,
            "log_file": log_path,
            "started_at": datetime.datetime.now().isoformat(),
            "config": {k: v for k, v in worker.items() if k != "process"},
            "launching": True,
        }
        with self._lock:
            if wid not in self.processes:
                # stop_worker popped our reservation mid-launch: honor the
                # stop — kill the just-spawned process instead of tracking it
                proc.kill_process_tree(p.pid)
                raise RuntimeError(f"worker {wid} stopped during launch")
            self.processes[wid] = entry
        self.save_processes()
        log(f"launched worker {wid} (pid {p.pid}, port {worker['port']}, "
            f"log {log_path})")
        threading.Thread(target=self._watch_startup, args=(wid, p, log_path),
                         daemon=True, name=f"dtpu-watch-{wid}").start()
        return {k: v for k, v in entry.items() if k != "process"}

    def _watch_startup(self, wid: str, p, log_path: str) -> None:
        """A worker that dies while starting (no TPU left for it, a bad
        argument) says why only in its own log; say it in the master's
        too, and keep the exit code for ``get_managed_workers``."""
        import subprocess
        try:
            code = p.wait(timeout=C.WORKER_STARTUP_WATCH_S)
        except subprocess.TimeoutExpired:
            return
        with self._lock:
            entry = self.processes.get(wid)
            if entry is None or entry.get("pid") != p.pid:
                return              # stopped on purpose, or relaunched
            entry["exit_code"] = code
            entry["launching"] = False
        try:
            with open(log_path, "rb") as f:
                f.seek(max(0, os.path.getsize(log_path) - 600))
                tail = f.read().decode("utf-8", "replace").strip()
        except OSError:
            tail = ""
        log(f"worker {wid} (pid {p.pid}) EXITED with code {code} while "
            f"starting; its log {log_path} ends:\n{tail}")

    # --- stop (reference stop_worker :768) ---------------------------------

    def stop_worker(self, worker_id: str) -> bool:
        wid = str(worker_id)
        with self._lock:
            entry = self.processes.pop(wid, None)
        if entry is None:
            return False
        pid = entry.get("pid")
        ok = proc.kill_process_tree(pid) if pid else True
        self.save_processes()
        log(f"stopped worker {wid} (pid {pid})")
        return ok

    def clear_launching(self, worker_id: str) -> None:
        with self._lock:
            if str(worker_id) in self.processes:
                self.processes[str(worker_id)]["launching"] = False

    def get_managed_workers(self) -> Dict[str, Dict[str, Any]]:
        """Liveness-annotated snapshot (reference ``get_managed_workers
        :828``)."""
        out = {}
        with self._lock:
            items = list(self.processes.items())
        for wid, entry in items:
            out[wid] = {
                "pid": entry.get("pid"),
                "alive": proc.is_process_alive(entry.get("pid", -1)),
                "exit_code": entry.get("exit_code"),
                "launching": entry.get("launching", False),
                "started_at": entry.get("started_at"),
                "log_file": entry.get("log_file"),
                "config": entry.get("config", {}),
            }
        return out

    def cleanup_all(self) -> None:
        """Stop every managed worker (reference ``cleanup_all :848``)."""
        with self._lock:
            wids = list(self.processes)
        for wid in wids:
            self.stop_worker(wid)

    # --- persistence (reference load/save_processes :861-904) --------------

    def load_processes(self) -> None:
        cfg = cfg_mod.load_config(self.config_path)
        managed = cfg.get("managed_processes", {}) or {}
        revived, purged = 0, 0
        with self._lock:
            for wid, entry in managed.items():
                pid = entry.get("pid")
                if pid and proc.is_process_alive(pid):
                    self.processes[str(wid)] = dict(entry)
                    revived += 1
                else:
                    purged += 1
        if revived or purged:
            log(f"managed workers: revived {revived}, purged {purged} stale")
        if purged:
            self.save_processes()

    def save_processes(self) -> None:
        with self._lock:
            snapshot = {
                wid: {k: v for k, v in entry.items() if k != "process"}
                for wid, entry in self.processes.items()
            }

        def mutate(cfg):
            cfg["managed_processes"] = snapshot

        # atomic RMW: a stale full-config write here would clobber worker
        # edits made concurrently through the HTTP config endpoints
        cfg_mod.mutate_config(mutate, self.config_path)

    # --- log tail (reference get_worker_log_endpoint :525-599) -------------

    def tail_log(self, worker_id: str, max_bytes: int = 65536) -> str:
        with self._lock:
            entry = self.processes.get(str(worker_id))
        path = entry.get("log_file") if entry else None
        if not path or not os.path.exists(path):
            raise FileNotFoundError(f"no log for worker {worker_id}")
        size = os.path.getsize(path)
        with open(path, "rb") as f:
            f.seek(max(0, size - max_bytes))
            return f.read().decode("utf-8", errors="replace")


_manager: Optional[WorkerProcessManager] = None
_manager_lock = threading.Lock()


def get_manager() -> WorkerProcessManager:
    global _manager
    with _manager_lock:
        if _manager is None:
            _manager = WorkerProcessManager()
        return _manager


def auto_launch_workers(manager: WorkerProcessManager,
                        delay: float = WORKER_STARTUP_DELAY) -> threading.Timer:
    """Delayed auto-launch of enabled local workers (reference
    ``delayed_auto_launch``/``auto_launch_workers``,
    ``distributed.py:1024-1092``).  Skips remote workers and ones already
    running; returns the timer so callers/tests can cancel it."""

    def run():
        cfg = cfg_mod.load_config(manager.config_path)
        if not cfg["settings"].get("auto_launch_workers"):
            return
        for w in cfg_mod.enabled_workers(cfg):
            if w.get("host") not in (None, "", "localhost", "127.0.0.1"):
                continue  # remote workers are never auto-launched
            wid = str(w["id"])
            entry = manager.processes.get(wid)
            if entry and proc.is_process_alive(entry.get("pid", -1)):
                continue
            try:
                manager.launch_worker(
                    w, stop_on_master_exit=cfg["settings"].get(
                        "stop_workers_on_master_exit", True))
            except RuntimeError as e:
                debug_log(f"auto-launch {wid}: {e}")

    t = threading.Timer(delay, run)
    t.daemon = True
    t.start()
    return t


def install_exit_hooks(manager: WorkerProcessManager) -> None:
    """atexit + signal handlers stopping managed workers when the master
    exits (reference ``cleanup_on_exit`` + handlers,
    ``distributed.py:1097-1123``)."""

    def cleanup(*_a):
        cfg = cfg_mod.load_config(manager.config_path)
        if cfg["settings"].get("stop_workers_on_master_exit", True):
            manager.cleanup_all()

    atexit.register(cleanup)
    for sig in (signal.SIGINT, signal.SIGTERM, signal.SIGHUP):
        try:
            prev = signal.getsignal(sig)
            if prev == signal.SIG_IGN:
                # previously ignored (e.g. SIGHUP under nohup): installing a
                # dying handler would defeat the ignore — leave it alone
                continue

            def handler(signum, frame, _prev=prev):
                cleanup()
                if callable(_prev):
                    _prev(signum, frame)
                else:  # SIG_DFL: mimic default termination
                    sys.exit(128 + signum)

            signal.signal(sig, handler)
        except (ValueError, OSError):  # non-main thread
            pass
