"""bench.py surfaces that must not rot: the real-checkpoint smoke hook
(VERDICT r3 #6) with a real single-file torch-layout checkpoint standing
in at tiny scale — written by the framework's own exporter, loaded back
through the converter by the bench, one image sampled, finite stats
asserted, PNG artifact saved."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.integration
def test_real_ckpt_smoke_hook(tmp_path):
    from comfyui_distributed_tpu.models import registry
    from comfyui_distributed_tpu.ops.base import OpContext, get_op

    # a REAL checkpoint file on disk (tiny family, full torch layout)
    pipe = registry.load_pipeline("bench-export.ckpt", family_name="tiny")
    octx = OpContext(output_dir=str(tmp_path))
    get_op("CheckpointSave").execute(octx, pipe, pipe, pipe, "tiny_real")
    ckpt = tmp_path / "tiny_real.safetensors"
    assert ckpt.exists()

    out = tmp_path / "real_ckpt.json"
    png = tmp_path / "real_ckpt.png"
    env = {**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
           "DTPU_DEFAULT_FAMILY": "tiny",
           "DISTRIBUTED_TPU_CONFIG": str(tmp_path / "c.json")}
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--real-ckpt", str(ckpt), "--platform", "cpu",
         "--height", "64", "--width", "64", "--steps", "2",
         "--out", str(out), "--png-out", str(png)],
        capture_output=True, text=True, timeout=420, cwd=str(tmp_path),
        env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    payload = json.loads(out.read_text())
    assert payload["value"] > 0.0
    assert payload["ckpt"] == "tiny_real.safetensors"
    assert "latent_std" in payload and payload["latent_std"] > 0.0
    assert png.exists() and png.stat().st_size > 0
    # the loader must have consumed the FILE, not virtual-initialized
    assert "virtual checkpoint" not in r.stderr


@pytest.mark.integration
def test_real_ckpt_missing_file_fails_structured(tmp_path):
    out = tmp_path / "fail.json"
    env = {**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
           "DTPU_DEFAULT_FAMILY": "tiny",
           "DISTRIBUTED_TPU_CONFIG": str(tmp_path / "c.json")}
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--real-ckpt", str(tmp_path / "nope.safetensors"),
         "--platform", "cpu", "--out", str(out)],
        capture_output=True, text=True, timeout=120, cwd=str(tmp_path),
        env=env)
    assert r.returncode != 0
    payload = json.loads(out.read_text())
    assert payload["error"]["stage"] == "config"
    assert payload["value"] == 0.0


class TestSuiteMode:
    """Suite detection, and the rule that nothing exits 0 after a failed
    phase or without the device the metric names."""

    def _bench(self):
        if REPO not in sys.path:
            sys.path.insert(0, REPO)
        import bench
        return bench

    def _run(self, code, **env):
        return subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=120, cwd=REPO,
            env={**os.environ, "PYTHONPATH": REPO, **env})

    def test_bare_invocation_is_suite_and_flags_opt_out(self):
        bench = self._bench()
        assert bench.parse_args([]).suite
        for argv in (["--family", "sdxl"], ["--platform", "cpu"],
                     ["--batch", "8"], ["--upscale"],
                     ["--attn", "pallas"], ["--scaling-sweep"],
                     ["--steps", "50"], ["--sampler", "dpmpp_2m"],
                     ["--repeats", "1"]):
            assert not bench.parse_args(argv).suite, argv

    def test_no_tpu_exits_nonzero_even_with_the_cpu_pinned(self):
        """Device-metric modes need the device: without --platform cpu a
        run that comes up on the CPU fails with a structured line, whether
        or not JAX_PLATFORMS=cpu asked for it."""
        r = self._run(
            "import sys; sys.argv=['bench.py', '--family', 'tiny']\n"
            "import bench\n"
            "bench.main()\n", JAX_PLATFORMS="cpu")
        assert r.returncode == 1, (r.returncode, r.stderr[-800:])
        last = json.loads(r.stdout.strip().splitlines()[-1])
        assert last["value"] == 0.0
        assert last["error"]["stage"] == "backend_init"
        assert "no TPU" in last["error"]["detail"]

    def test_fail_after_a_completed_phase_still_exits_nonzero(self):
        """A later-phase failure leaves the completed phase's line on
        stdout, ends with the failure line and exits 1."""
        r = self._run(
            "import sys; sys.argv=['bench.py']\n"
            "import bench\n"
            "a = bench.parse_args([])\n"
            "bench.emit(a, {'metric': 'sd15_x', 'value': 2.0,"
            " 'unit': 'images/sec/chip', 'vs_baseline': 1.0},"
            " partial=True)\n"
            "bench.fail(a, 'runtime', 'phase B OOM')\n")
        assert r.returncode == 1, r.stderr[-500:]
        lines = [json.loads(ln) for ln in r.stdout.strip().splitlines()]
        assert lines[0]["value"] == 2.0
        assert lines[-1]["value"] == 0.0
        assert lines[-1]["error"]["stage"] == "runtime"

    def test_sigterm_mid_run_exits_nonzero(self):
        r = self._run(
            "import os, signal, sys, time; sys.argv=['bench.py']\n"
            "import bench\n"
            "a = bench.parse_args([])\n"
            "bench._install_sigterm_payload(a)\n"
            "bench.emit(a, {'metric': 'sd15_x', 'value': 2.1,"
            " 'unit': 'images/sec/chip', 'vs_baseline': 1.0},"
            " partial=True)\n"
            "os.kill(os.getpid(), signal.SIGTERM)\n"
            "time.sleep(10)\n"
            "sys.exit(3)\n")
        assert r.returncode == 124, (r.returncode, r.stderr[-500:])
        last = json.loads(r.stdout.strip().splitlines()[-1])
        assert last["value"] == 0.0
        assert last["error"]["stage"] == "timeout"

    def test_crashed_cpu_phase_is_not_ok(self, monkeypatch):
        """_phase_subprocess reports a crashed phase instead of turning
        it into a silent None (run_suite exits 1 on any not-ok stage)."""
        bench = self._bench()

        class _R:
            returncode, stderr = 1, "boom"

        import subprocess as sp
        monkeypatch.setattr(sp, "run", lambda *a, **k: _R())
        assert bench._phase_subprocess("tensor_plane") == (None, False)

    def test_unknown_tpu_kind_is_an_error(self):
        bench = self._bench()
        assert bench.peak_flops_for("TPU v5 lite") == 197e12
        with pytest.raises(KeyError, match="PEAK_FLOPS"):
            bench.peak_flops_for("TPU v9000")


class TestTpServePhaseSurface:
    """ISSUE 16: the tp_serve phase's CLI/metric/watchdog surface.
    The harness itself (mesh build + sharded compiles) runs in
    tests/test_batching.py and the bench subprocess; here we pin the
    cheap contract: the phase parses, names its metric, and its
    exactness bar tolerates zero regression."""

    def _bench(self):
        if REPO not in sys.path:
            sys.path.insert(0, REPO)
        import bench
        return bench

    def test_phase_parses_and_names_metric(self):
        bench = self._bench()
        args = bench.parse_args(["--phase", "tp_serve"])
        assert args.phase == "tp_serve"
        assert bench.metric_name(args) == "tp_serve_bit_exact_fraction"
        assert bench.metric_unit(args) == "fraction"

    def test_exactness_bar_tolerates_nothing(self):
        bench = self._bench()
        assert bench.CHECK_TOLERANCE_PCT[
            "tp_serve_bit_exact_fraction"] == 0.0
        fresh = {"metric": "tp_serve_bit_exact_fraction",
                 "value": 0.5, "unit": "fraction"}
        base = {"metric": "tp_serve_bit_exact_fraction",
                "value": 1.0, "unit": "fraction"}
        assert bench.check_regression(fresh, base)["regressed"]
        assert not bench.check_regression(base, dict(base))["regressed"]


class TestPreemptPhaseSurface:
    """ISSUE 17: the preempt phase's CLI/metric/watchdog surface.  The
    harness itself (park/resume round trips under contention) runs in
    the bench subprocess and tests/test_batching.py; here we pin the
    cheap contract: the phase parses, names its metric, and its
    completion bar tolerates zero regression (preemption pauses work,
    never sheds it)."""

    def _bench(self):
        if REPO not in sys.path:
            sys.path.insert(0, REPO)
        import bench
        return bench

    def test_phase_parses_and_names_metric(self):
        bench = self._bench()
        args = bench.parse_args(["--phase", "preempt"])
        assert args.phase == "preempt"
        assert bench.metric_name(args) == \
            "preempt_batch_completion_under_preemption"
        assert bench.metric_unit(args) == "fraction"

    def test_completion_bar_tolerates_nothing(self):
        bench = self._bench()
        assert bench.CHECK_TOLERANCE_PCT[
            "preempt_batch_completion_under_preemption"] == 0.0
        fresh = {"metric": "preempt_batch_completion_under_preemption",
                 "value": 0.9, "unit": "fraction"}
        base = {"metric": "preempt_batch_completion_under_preemption",
                "value": 1.0, "unit": "fraction"}
        assert bench.check_regression(fresh, base)["regressed"]
        assert not bench.check_regression(base, dict(base))["regressed"]


class TestSloPhaseSurface:
    """ISSUE 18: the slo phase's CLI/metric/watchdog surface.  The
    harness itself (armed capture plane vs all-off, burn/exemplar/
    round-trip invariants) runs in the bench subprocess and
    tests/test_capture_plane.py; here we pin the cheap contract: the
    phase parses, names its metric, and carries a throughput
    tolerance."""

    def _bench(self):
        if REPO not in sys.path:
            sys.path.insert(0, REPO)
        import bench
        return bench

    def test_phase_parses_and_names_metric(self):
        bench = self._bench()
        args = bench.parse_args(["--phase", "slo"])
        assert args.phase == "slo"
        assert bench.metric_name(args) == \
            "slo_capture_plane_imgs_per_s_4prompt"
        assert bench.metric_unit(args) == "imgs/s"

    def test_throughput_tolerance_registered(self):
        bench = self._bench()
        assert bench.CHECK_TOLERANCE_PCT[
            "slo_capture_plane_imgs_per_s_4prompt"] == 15.0
        fresh = {"metric": "slo_capture_plane_imgs_per_s_4prompt",
                 "value": 50.0, "unit": "imgs/s"}
        base = {"metric": "slo_capture_plane_imgs_per_s_4prompt",
                "value": 75.0, "unit": "imgs/s"}
        assert bench.check_regression(fresh, base)["regressed"]
        assert not bench.check_regression(base, dict(base))["regressed"]
