"""Worker process manager + master-death monitor."""

import os
import subprocess
import sys
import time

import pytest

from comfyui_distributed_tpu.runtime import manager as mgr_mod
from comfyui_distributed_tpu.utils import config as cfg_mod
from comfyui_distributed_tpu.utils.process import is_process_alive

SLEEPER = [sys.executable, "-c", "import time; time.sleep(60)"]


@pytest.fixture
def manager(tmp_path, monkeypatch):
    m = mgr_mod.WorkerProcessManager(
        config_path=str(tmp_path / "cfg.json"),
        log_dir=str(tmp_path / "logs"))
    # don't spawn real worker servers in unit tests
    monkeypatch.setattr(m, "build_launch_command", lambda w: list(SLEEPER))
    yield m
    m.cleanup_all()


class TestManager:
    def test_launch_tracks_and_stops(self, manager):
        entry = manager.launch_worker({"id": "w1", "name": "t", "port": 1},
                                      stop_on_master_exit=False)
        assert is_process_alive(entry["pid"])
        managed = manager.get_managed_workers()
        assert managed["w1"]["alive"] is True
        assert managed["w1"]["launching"] is True
        manager.clear_launching("w1")
        assert manager.get_managed_workers()["w1"]["launching"] is False
        assert manager.stop_worker("w1") is True
        assert not is_process_alive(entry["pid"])
        assert manager.stop_worker("w1") is False

    def test_double_launch_conflict(self, manager):
        manager.launch_worker({"id": "w1", "port": 1},
                              stop_on_master_exit=False)
        with pytest.raises(RuntimeError, match="already running"):
            manager.launch_worker({"id": "w1", "port": 1},
                                  stop_on_master_exit=False)

    def test_pid_persistence_revive_and_purge(self, manager, tmp_path):
        entry = manager.launch_worker({"id": "w1", "port": 1},
                                      stop_on_master_exit=False)
        cfg = cfg_mod.load_config(str(tmp_path / "cfg.json"))
        assert cfg["managed_processes"]["w1"]["pid"] == entry["pid"]
        # stale entry purged on load
        cfg["managed_processes"]["dead"] = {"pid": 999999}
        cfg_mod.save_config(cfg, str(tmp_path / "cfg.json"))
        m2 = mgr_mod.WorkerProcessManager(
            config_path=str(tmp_path / "cfg.json"),
            log_dir=str(tmp_path / "logs"))
        assert "w1" in m2.processes          # revived (alive)
        assert "dead" not in m2.processes    # purged
        m2.processes.pop("w1", None)         # owner is `manager` fixture

    def test_log_written_and_tailed(self, manager):
        manager.launch_worker({"id": "w1", "name": "logtest", "port": 1},
                              stop_on_master_exit=False)
        text = manager.tail_log("w1")
        assert "=== session" in text
        with pytest.raises(FileNotFoundError):
            manager.tail_log("nope")

    def test_worker_that_dies_at_startup_is_reported(self, manager,
                                                     monkeypatch):
        """A managed worker next to a master that holds the chips finds no
        TPU and exits non-zero: the launch watch says so in the master's
        log and keeps the exit code, instead of the worker quietly serving
        on the CPU."""
        monkeypatch.setattr(manager, "build_launch_command", lambda w: [
            sys.executable, "-c",
            "import sys; print('no TPU: JAX came up on cpu', "
            "file=sys.stderr); sys.exit(1)"])
        logged = []
        monkeypatch.setattr(mgr_mod, "log", logged.append)
        manager.launch_worker({"id": "w1", "name": "nochip", "port": 1},
                              stop_on_master_exit=False)
        deadline = time.time() + 20
        while time.time() < deadline:
            got = manager.get_managed_workers()["w1"]
            if got["exit_code"] is not None:
                break
            time.sleep(0.1)
        assert got["exit_code"] == 1 and got["alive"] is False
        assert got["launching"] is False
        while time.time() < deadline and len(logged) < 2:
            time.sleep(0.05)    # the watch logs right after the code lands
        assert "worker w1" in logged[-1]
        assert "EXITED with code 1" in logged[-1]
        assert "no TPU: JAX came up on cpu" in logged[-1]

    def test_cpu_worker_is_pinned_by_its_own_environment(self, manager,
                                                         monkeypatch):
        """`"platform": "cpu"` in a worker's config is the explicit ask:
        the child gets JAX_PLATFORMS=cpu before it imports jax; without
        it the child inherits the master's environment untouched."""
        from comfyui_distributed_tpu.utils import process as proc
        seen = []
        real = proc.popen_detached

        def spy(cmd, env=None, **kw):
            seen.append(env.get("JAX_PLATFORMS"))
            return real(cmd, env=env, **kw)

        monkeypatch.setattr(proc, "popen_detached", spy)
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        manager.launch_worker({"id": "a", "port": 1, "platform": "cpu"},
                              stop_on_master_exit=False)
        manager.launch_worker({"id": "b", "port": 2},
                              stop_on_master_exit=False)
        assert seen == ["cpu", "tpu"]

    def test_auto_launch_respects_settings(self, manager, tmp_path):
        cfg = cfg_mod.load_config(str(tmp_path / "cfg.json"))
        cfg_mod.upsert_worker(cfg, {"id": "w1", "port": 1, "enabled": True})
        cfg_mod.upsert_worker(cfg, {"id": "remote", "port": 2,
                                    "enabled": True, "host": "10.0.0.9"})
        cfg_mod.update_setting(cfg, "auto_launch_workers", True)
        cfg_mod.save_config(cfg, str(tmp_path / "cfg.json"))
        t = mgr_mod.auto_launch_workers(manager, delay=0.01)
        t.join(timeout=5)
        time.sleep(0.2)
        managed = manager.get_managed_workers()
        assert "w1" in managed        # local enabled -> launched
        assert "remote" not in managed  # remote never auto-launched


class TestMonitor:
    def test_monitor_kills_worker_when_master_dies(self, tmp_path):
        """Full wrapper flow (reference worker_monitor.py:92-103): fake
        master dies -> monitor terminates the worker and exits."""
        fake_master = subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(2)"])
        mon = subprocess.Popen(
            [sys.executable, "-m",
             "comfyui_distributed_tpu.runtime.monitor",
             "--master-pid", str(fake_master.pid), "--"] + SLEEPER,
            env={**os.environ, "PYTHONPATH": "/root/repo"})
        try:
            fake_master.wait(timeout=10)
            mon.wait(timeout=15)
            assert mon.returncode == 0
        finally:
            if mon.poll() is None:
                mon.kill()

    def test_monitor_propagates_worker_exit(self, tmp_path):
        mon = subprocess.Popen(
            [sys.executable, "-m",
             "comfyui_distributed_tpu.runtime.monitor",
             "--master-pid", str(os.getpid()), "--",
             sys.executable, "-c", "import sys; sys.exit(7)"],
            env={**os.environ, "PYTHONPATH": "/root/repo"})
        mon.wait(timeout=15)
        assert mon.returncode == 7


def test_put_tile_requires_existing_queue():
    """Regression: late tile posts after queue removal must be rejected, not
    resurrect an orphan queue (unbounded memory on a long-running master)."""
    import asyncio
    from comfyui_distributed_tpu.runtime.jobs import JobStore

    async def run():
        store = JobStore()
        assert not await store.put_tile("gone", {"tile_idx": 0})
        await store.get_tile_queue("live")
        assert await store.put_tile("live", {"tile_idx": 0})
        await store.remove_tile_queue("live")
        assert not await store.put_tile("live", {"tile_idx": 1})
        assert store.snapshot()["tile_jobs"] == []

    asyncio.run(run())


class TestHealthPoller:
    def test_poll_derives_status(self, tmp_path, monkeypatch):
        """online / processing / offline / disabled derivation (reference
        checkWorkerStatus, gpupanel.js:1249-1311)."""
        from comfyui_distributed_tpu.runtime import health as health_mod
        from comfyui_distributed_tpu.utils import config as cfg_mod

        cfg = cfg_mod.load_config()
        cfg["workers"] = [
            {"id": "up", "port": 1, "enabled": True},
            {"id": "busy", "port": 2, "enabled": True},
            {"id": "down", "port": 3, "enabled": True},
            {"id": "off", "port": 4, "enabled": False},
        ]
        cfg_mod.save_config(cfg)

        def fake_probe(worker, timeout=2.0):
            wid = worker["id"]
            if wid == "up":
                return {"status": "online", "queue_remaining": 0,
                        "last_seen": 1.0}
            if wid == "busy":
                return {"status": "processing", "queue_remaining": 2,
                        "last_seen": 1.0}
            return {"status": "offline", "queue_remaining": None,
                    "last_seen": None}

        monkeypatch.setattr(health_mod, "probe_worker", fake_probe)

        class FakeManager:
            cleared = []

            def clear_launching(self, wid):
                self.cleared.append(wid)

        mgr = FakeManager()
        poller = health_mod.HealthPoller(manager=mgr)
        snap = poller.poll_once()
        assert snap["up"]["status"] == "online"
        assert snap["busy"]["status"] == "processing"
        assert snap["down"]["status"] == "offline"
        assert snap["off"]["status"] == "disabled"
        # first contact clears 'launching' for reachable workers only
        assert sorted(mgr.cleared) == ["busy", "up"]
        assert poller.snapshot() == snap

    def test_probe_worker_offline(self):
        from comfyui_distributed_tpu.runtime.health import probe_worker
        st = probe_worker({"id": "x", "port": 1}, timeout=0.2)
        assert st["status"] == "offline"


class TestInterruptPolling:
    def test_poll_is_opt_in_and_knows_no_backend_by_name(self, monkeypatch):
        """The per-step poll is compiled in by DTPU_INTERRUPT_POLL=1 and
        by nothing else: a program that holds a host callback is never
        written to the persistent compile cache, so it is not the
        default, and no backend name switches it either way."""
        import jax

        from comfyui_distributed_tpu.runtime import interrupt as itr
        monkeypatch.delenv("DTPU_INTERRUPT_POLL", raising=False)
        for backend in ("cpu", "tpu", "anything"):
            monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
            assert itr.polling_enabled() is False
            monkeypatch.setenv("DTPU_INTERRUPT_POLL", "1")
            assert itr.polling_enabled() is True
            monkeypatch.setenv("DTPU_INTERRUPT_POLL", "0")
            assert itr.polling_enabled() is False
            monkeypatch.delenv("DTPU_INTERRUPT_POLL")
        assert not hasattr(itr, "_backend_supports_callbacks")

    def test_polled_program_is_not_persisted_unpolled_is(self, tmp_path):
        """The reason for the default, checked against the installed JAX:
        the same scan is written to the persistent cache without the
        poll and refused with it."""
        import os
        import subprocess
        import sys
        code = (
            "import os, sys, jax, jax.numpy as jnp\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)\n"
            "jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)\n"
            "from comfyui_distributed_tpu.models import samplers as smp\n"
            "f = jax.jit(lambda x, s: smp._scan_sampler("
            "lambda c, i, a, b: ((c[0] * 0.5, c[1]), None), x, s))\n"
            "f(jnp.ones((2, 3)), jnp.linspace(1.0, 0.0, 4)).block_until_ready()\n"
            "print(sum(n.startswith('jit__lambda') "
            "for n in os.listdir(os.environ['JAX_COMPILATION_CACHE_DIR'])))\n")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        counts = {}
        for poll in ("0", "1"):
            d = tmp_path / f"cache{poll}"
            d.mkdir()
            r = subprocess.run(
                [sys.executable, "-c", code], capture_output=True,
                text=True, timeout=120, cwd=str(tmp_path),
                env={**os.environ, "PYTHONPATH": repo,
                     "JAX_PLATFORMS": "cpu", "DTPU_INTERRUPT_POLL": poll,
                     "JAX_COMPILATION_CACHE_DIR": str(d)})
            assert r.returncode == 0, r.stderr[-2000:]
            counts[poll] = int(r.stdout.strip().splitlines()[-1])
        assert counts == {"0": 1, "1": 0}
