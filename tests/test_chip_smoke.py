"""chip_smoke.py: the CPU rehearsal passes, and the chip check itself
refuses to pass anywhere but on a chip.

The real run needs a TPU (``python chip_smoke.py`` through the chip tool);
what tier-1 can hold is the control flow: the same server child, the same
requests over HTTP, the same assertions, at tiny size — and the three ways
the default mode must fail here."""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(argv, cwd, timeout, **env):
    base = {k: v for k, v in os.environ.items()
            if k not in ("JAX_PLATFORMS", "XLA_FLAGS", "DTPU_DEFAULT_FAMILY")}
    return subprocess.run([sys.executable, *argv], cwd=str(cwd),
                          env={**base, **env}, capture_output=True,
                          text=True, timeout=timeout)


def test_rehearsal_passes_on_the_cpu(tmp_path):
    """Server child -> 3 SDXL-fixture prompts -> tiled upscale -> clean
    shutdown -> kernel child (interpret mode), all asserted, at tiny
    size.  The summary line says it is a CPU rehearsal; the last line is
    the verdict and the device and nothing else."""
    r = _run([SMOKE, "--rehearse", "--out", str(tmp_path / "out")],
             tmp_path, 600)
    assert r.returncode == 0, r.stderr[-3000:]
    summary, last = map(json.loads, r.stdout.strip().splitlines())
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"
    assert isinstance(last["device"]["kind"], str)
    assert type(last["device"]["count"]) is int
    assert last["device"]["count"] >= 1
    assert summary["device"] == last["device"]
    assert summary["rehearsal"] is True
    assert summary["phases"] == ["sdxl", "upscale", "kernels"]
    facts = summary["smoke_facts"]
    assert len(facts["sdxl_request_wall_s"]) == 3
    assert facts["memory_source"] == "host_rss"     # no allocator on CPU
    assert facts["pallas_flash_attention"]["interpret"] is True
    # per shape: the path the rule chose (never the kernel on a CPU) and
    # the kernel's error against fp32 beside xla_attention's
    shapes = facts["pallas_flash_attention"]["shapes"]
    assert len(shapes) == 2
    for row in shapes:
        assert set(row) == {"q", "kv_len", "path", "rel_err", "rel_err_xla"}
        assert row["path"] in ("xla_whole", "xla_chunked")
        assert row["rel_err"] <= max(1.25 * row["rel_err_xla"], 4e-6)
    # the fused GEGLU (interpreter) beside the module as written
    assert facts["pallas_geglu"]["limit"] == 2.0 ** -8
    assert [set(row) for row in facts["pallas_geglu"]["shapes"]] \
        == [{"x", "path", "rel_err", "rel_err_xla"}]
    for row in facts["pallas_geglu"]["shapes"]:
        assert row["path"] == "xla"
        assert row["rel_err"] <= max(row["rel_err_xla"], 4e-6)
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    with open(tmp_path / "out" / "summary.json", encoding="utf-8") as f:
        assert json.load(f) == summary
    # the server worked under the output directory, not in the checkout
    assert os.path.isdir(tmp_path / "out" / "server" / "output")
    assert not os.path.exists(tmp_path / "output")


def test_lm_phase_holds_the_served_logits_to_the_reference(tmp_path):
    """``--phases lm``: verify_lm.py as a child (a server of its own, one
    request of the prompt expander's graph, then the plain reference),
    tiny on the CPU; the 8-bit readings have to be refused."""
    r = _run([SMOKE, "--rehearse", "--phases", "lm", "--out",
              str(tmp_path / "out")], tmp_path, 900)
    assert r.returncode == 0, r.stderr[-3000:]
    summary, last = map(json.loads, r.stdout.strip().splitlines())
    assert last == {"ok": True, "device": summary["device"]}
    assert summary["phases"] == ["lm"] and summary["rehearsal"] is True
    lm = summary["smoke_facts"]["language_model"]
    assert lm["positions"] == 4 and lm["argmax_agree"] == 1.0
    assert lm["mean_over_std"] <= lm["limits"]["mean_over_std"]
    assert lm["weights_8bit"] > lm["limits"]["mean_over_std"]
    assert lm["cache_8bit"] > lm["limits"]["mean_over_std"]
    with open(tmp_path / "out" / "verify_lm" / "verify_lm.json") as f:
        assert json.load(f)["ok"] is True
    # then four requests sent together: one execution, four rows of
    # different lengths, each inside the same limits
    rows = summary["smoke_facts"]["language_model_together"]
    assert rows["executions"] == 1 and rows["rows"] == 4
    assert rows["followers_served"] == 3 and rows["padded_rows"] == 0
    assert len(rows["served"]) == 4 and rows["limits"] == lm["limits"]
    assert len({row["prompt_ids"] for row in rows["served"]}) == 4
    for row in rows["served"]:
        assert row["positions"] == 4 and row["argmax_agree"] == 1.0
        assert row["mean_over_std"] <= rows["limits"]["mean_over_std"]
        assert row["max_over_std"] <= rows["limits"]["max_over_std"]
    # and the state-space model (PR 40), alone and four rows of unequal
    # length together, against a reference of its own; the four readings
    # that have to fail do
    ssm = summary["smoke_facts"]["language_model_ssm"]
    assert ssm["rows"] == 5 and ssm["positions"] == 4
    assert ssm["together"]["executions"] == 1 \
        and ssm["together"]["rows"] == 4
    assert ssm["mean_over_std"] <= ssm["limits"]["mean_over_std"]
    for reading in ("state_bf16", "cache_8bit", "weights_8bit",
                    "skip_dropped"):
        assert ssm[reading] > ssm["limits"]["mean_over_std"], reading
    with open(tmp_path / "out" / "verify_lm_ssm"
              / "verify_lm_ssm.json") as f:
        assert json.load(f)["ok"] is True
    # and the model that selects its keys (PR 42), alone and four rows
    # together, the reference forced to the program's choices and
    # selections; the eight readings that have to fail do
    dsa = summary["smoke_facts"]["language_model_dsa"]
    assert dsa["rows"] == 5 and dsa["positions"] == 4
    assert dsa["together"]["executions"] == 1 \
        and dsa["together"]["rows"] == 4
    assert dsa["mean_over_std"] <= dsa["limits"]["mean_over_std"]
    assert dsa["selection_agree"] == 1.0
    assert dsa["free"]["expert_choices_agree"] == 1.0
    for reading in ("cache_8bit", "weights_8bit", "no_selection",
                    "last_topk", "no_relu", "no_head_weights", "top7_of_8",
                    "no_renormalisation"):
        assert dsa[reading] > dsa["limits"]["mean_over_std"], reading
    with open(tmp_path / "out" / "verify_lm_dsa_moe"
              / "verify_lm_dsa_moe.json") as f:
        assert json.load(f)["ok"] is True
    # and four rows started from the snapshot of their shared
    # instructions (PR 41): against the reference of the WHOLE prompt
    # (logits, greedy ids, the state behind the prompt) and against the
    # full path over the same prompts
    shared = summary["smoke_facts"]["language_model_ssm_prefix"]
    assert (shared["prefix_ids"], shared["prompt_tokens"]) == (14, 48)
    assert shared["counted"]["lm.prefix_hits"] == 4
    assert shared["counted"]["lm.prefix_misses"] == 1
    assert shared["counted"]["lm.prefill_positions"] == 4 * (48 - 14)
    assert len({row["own_ids"] for row in shared["rows"]}) == 4
    for row in shared["rows"]:
        assert row["against_reference"]["correct"]
        assert row["against_reference"]["argmax_agree"] == 1.0
        assert row["state_against_reference"]["correct"]
        against = row["against_full_path"]
        assert against["ids_alike"] == against["steps_compared"] == 4
        for part in ("logits", "ssm", "conv", "keys"):
            assert against[part]["max_over_std"] \
                <= shared["limits"]["max_over_std"]


def test_default_mode_refuses_a_cpu_pinned_jax(tmp_path):
    """JAX_PLATFORMS=cpu (this sandbox) is not a chip: exit non-zero with
    the reason, before any child starts, and print no result."""
    r = _run([SMOKE, "--out", str(tmp_path / "out")], tmp_path, 60,
             JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "does not fall back" in r.stderr
    assert not os.path.exists(tmp_path / "out")


def test_default_mode_fails_when_jax_finds_no_accelerator(tmp_path):
    """With nothing pinned, JAX falls back to the CPU on its own when no
    TPU comes up; the server child refuses that, so the smoke fails with
    the child's reason and prints no result."""
    r = _run([SMOKE, "--phases", "sdxl", "--out", str(tmp_path / "out")],
             tmp_path, 300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "FAILED" in r.stderr and "no TPU" in r.stderr


def test_alone_in_a_directory_it_fails(tmp_path):
    """The script drives the repo it lives in; without it there is
    nothing to pass."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = _run([str(tmp_path / "chip_smoke.py")], tmp_path, 60)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "not next to this script" in r.stderr


def _load_smoke():
    import importlib.util
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    return smoke


def test_kernel_phase_holds_the_kernel_to_xla_attentions_error():
    """On the chip the kernel child runs the shapes the rule sends to the
    kernel: the four large self-attentions of the two benchmarked UNets
    among them, each a shape `attention_path` answers ``fused`` for."""
    smoke = _load_smoke()
    from comfyui_distributed_tpu.models.layers import attention_path
    fused = [(q, m) for q, m in smoke.KERNEL_SHAPES
             if attention_path("tpu", q[0], q[1], m, q[2]) == "fused"]
    for shape in (((2, 4096, 10, 64), 4096), ((2, 1024, 20, 64), 1024),
                  ((2, 4096, 8, 40), 4096), ((2, 1024, 8, 80), 1024)):
        assert shape in fused
    assert len(fused) < len(smoke.KERNEL_SHAPES)    # and some it keeps
    assert smoke.KERNEL_ERR_RATIO == 1.25


def test_kernel_phase_holds_the_geglu_kernel_to_one_rounding():
    """On the chip the kernel child runs the fused GEGLU at the five
    feed-forward shapes of the two benchmarked UNets, each a shape
    `geglu_path` answers ``fused`` for, against a limit of one bf16 ulp."""
    smoke = _load_smoke()
    from comfyui_distributed_tpu.models.layers import geglu_path
    assert set(smoke.GEGLU_SHAPES) == {
        (2, 4096, 640), (2, 1024, 1280),
        (2, 4096, 320), (2, 1024, 640), (2, 256, 1280)}
    for b, t, c in smoke.GEGLU_SHAPES:
        assert geglu_path("tpu", b * t, c) == "fused"
    assert smoke.GEGLU_ERR_LIMIT == 2.0 ** -8
