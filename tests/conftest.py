"""Test harness: an 8-device virtual CPU mesh (SURVEY.md §4).

The reference's multi-process browser+HTTP topology is untestable in CI; the
TPU framework's collectives are testable single-process by forcing XLA to
expose N host devices.  Env vars must be set before jax initializes a backend,
hence this module-level block in conftest.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# a pytest plugin may have imported jax before this file set the env (jax
# reads it at import); the live config makes the pin hold either way
jax.config.update("jax_platforms", "cpu")

# Compilation is a one-time cost (the tensor-plane contract): share the
# persistent XLA compilation cache across the whole suite AND across runs
# (where runtime/manager.enable_persistent_compile_cache puts it:
# JAX_COMPILATION_CACHE_DIR, else the checkout's .jax_cache/).  The many
# tiny-model programs the tests compile are identical across modules and
# rounds — virtual weights differ only in VALUES, not HLO — so each
# compiles once per container instead of once per test module.
# min_compile_secs=0: the suite's compiles are individually small but
# collectively dominate its wall-clock.
from comfyui_distributed_tpu.runtime.manager import \
    enable_persistent_compile_cache  # noqa: E402

enable_persistent_compile_cache(min_compile_secs=0.0)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Cheapest-first module order (the same principle bench.py's suite mode
# uses): the tier-1 gate runs this suite under a hard wall-clock timeout,
# and after the shard_map shim fix ~175 previously-uncollectable tests
# actually execute, pushing the full suite past that window.  Ordering
# modules by measured cost makes a timeout truncate the expensive
# sampling-heavy tail instead of the broad cheap majority — every
# completed test is a completed test either way.  Costs: measured module
# wall-clock seconds (2026-08-03 full run, warm compile cache); unlisted
# modules default cheap.  Stable sort keeps intra-module order (and
# module/class fixture scoping) intact.
_MODULE_COST_S = {
    "test_models.py": 778,
    "test_parallel.py": 300,
    "test_workflow.py": 160,
    "test_controlnet.py": 190,
    "test_train.py": 100,
    "test_samplers.py": 60,
    "test_server.py": 45,
    "test_tensor_plane.py": 28,
    "test_pipeline.py": 21,
    "test_observability.py": 19,
    # capture plane (PR 18): exporter rotation/retention units are
    # instant; the two ServerState e2e surfaces dominate (~15s total)
    "test_capture_plane.py": 15,
    "test_attention.py": 35,
    # the chip_smoke.py CPU rehearsal: one server child + one kernel child
    "test_chip_smoke.py": 40,
    "test_multihost.py": 30,
    "test_checkpoints_canonical.py": 18,
    "test_torch_parity.py": 18,
    "test_bench.py": 16,
    "test_packaging.py": 13,
    # non-slow share only (the two loopback fault-acceptance tests are
    # marked slow in-file, ~40s each with real master+worker exec loops)
    "test_cluster.py": 12,
    # non-slow share only (the 3-tenant overload acceptance is marked
    # slow in-file, ~40s with a real loopback fleet + chaos)
    "test_overload.py": 2,
    # non-slow share only (the two loopback election/recovery
    # acceptance tests are marked slow in-file, ~20s each with real
    # master+standby+worker exec loops over a shared WAL)
    "test_durable.py": 12,
    "test_resource.py": 12,
    # pure-AST static analysis (dtpu-lint): parses the package ~15x
    # (fixtures + live-tree gate + the v1 AND v2/interprocedural
    # seeded mutations, each a full run_lint with call-graph build),
    # no device work
    "test_analysis.py": 36,
    # continuous batching (PR 12) + latent paging (PR 17): bucket-level
    # exactness, park/resume edge cases, preemption harness, and a few
    # real CB ServerStates on the tiny model (~60s warm-cache non-slow
    # share; the two-sampler exactness proofs are slow-marked in-file)
    "test_batching.py": 60,
    "test_tiling.py": 10,
    # cross-request compute reuse (PR 13): non-slow share only (the
    # tile-tier bit-exactness proofs and the SSE client-gone acceptance
    # are slow-marked in-file, ~25s together with real refine runs)
    "test_reuse.py": 15,
    # multi-master shard plane (PR 14): ring math + exec-less loopback
    # forwarding/takeover/router tests run in ~1s; the 3-master
    # kill-mid-upscale acceptance (~32s, real fan-out + absorb) is
    # slow-marked in-file
    "test_shard.py": 2,
    # traffic twin (PR 19): pure-Python discrete-event sim on a virtual
    # clock — no device work, whole module <2s
    "test_sim.py": 1,
    # critical-path analytics (PR 20): pure-stdlib blame/diff units and
    # virtual-clock sim round-trips are instant; the one ServerState
    # e2e surface (~10s) dominates
    "test_trace_analysis.py": 11,
}


# Tests marked `slow` at collection time (tier-1 runs `-m 'not slow'`).
# Criteria: measured call time >= ~12s in the 2026-08-03 full run AND the
# test was NOT passing in the seed baseline (it was uncollectable or
# failing through the empty-op-registry cascade) — so the timed gate
# keeps every test the seed gate effectively had, plus the cheap
# majority of the restored ones, and finishes inside its window.  The
# full `pytest tests/` run (README) still executes everything.
_SLOW_TESTS = {
    "test_parallel.py::TestDryrunMultichip::test_dryrun_green[8]",
    "test_parallel.py::TestDryrunMultichip::test_dryrun_green[16]",
    # TP serve workloads (ISSUE 16 budget guard + cache hygiene): the
    # 2-D-mesh bucket programs can't use the persistent compile cache
    # (see parallel/mesh._tp_compile_cache_guard — the disable is sticky
    # for the whole process), so they pay full compiles every run AND
    # strand every later test in the same process cacheless.  Tier-1
    # therefore runs NO in-process tensor>1 serve programs at all; the
    # slow tier and the bench tp_serve subprocess keep the coverage.
    "test_batching.py::TestBucketTensorParallel::"
    "test_late_join_bit_identical_to_solo_under_tp",
    "test_batching.py::TestBucketTensorParallel::"
    "test_zero_steady_state_retraces_under_tp",
    "test_batching.py::TestBucketTensorParallel::"
    "test_bucket_buffers_carry_canonical_rows_layout",
    "test_batching.py::TestLatentPagingTensorParallel::"
    "test_park_resume_bit_identical_under_tp",
    "test_parallel.py::TestServingTensorParallel::"
    "test_tp_sharded_sample_matches_replicated_oracle",
    "test_parallel.py::TestServingTensorParallel::"
    "test_upstream_sharded_concat_miscompile",
    "test_train.py::test_sharded_train_step_runs",
    "test_train.py::test_training_reduces_loss",
    "test_samplers.py::TestRound5SamplerLongTail::"
    "test_ksampler_runs_the_long_tail_end_to_end",
    "test_models.py::TestComponentLoadersRound5::"
    "test_dual_clip_loader_sdxl_towers",
    "test_models.py::TestComponentLoadersRound5::"
    "test_unet_loader_samples_end_to_end",
    "test_models.py::TestSelfAttentionGuidance::"
    "test_sag_changes_output_and_zero_scale_matches_plain",
    "test_models.py::TestSelfAttentionGuidance::"
    "test_sag_falls_back_without_uncond_benefit",
    "test_models.py::TestDeepShrink::test_node_patch_and_window",
    "test_models.py::TestCustomSampling::"
    "test_split_sigmas_two_stage_roundtrip",
    "test_models.py::TestCustomSampling::"
    "test_sampler_custom_matches_ksampler",
    "test_models.py::TestRegionalPromptingFixups::"
    "test_sibling_control_scoped_to_its_region",
    "test_models.py::TestRegionalPromptingFixups::"
    "test_sibling_control_reaches_sampling",
    "test_models.py::TestRegionalPromptingFixups::"
    "test_combined_negative_reaches_sampling",
    "test_models.py::TestTimestepRange::"
    "test_scheduled_prompts_change_sampling",
    "test_models.py::TestGligen::test_textbox_apply_and_sampling",
    "test_models.py::TestGligen::"
    "test_textbox_apply_reaches_combined_siblings",
    "test_models.py::TestModelPatchesRound4::test_model_sampling_discrete",
    "test_models.py::TestModelPatchesRound4::"
    "test_perp_neg_reduces_to_cfg_when_empty_is_negative",
    "test_models.py::TestModelPatchesRound4::"
    "test_perp_neg_guider_matches_patch",
    "test_models.py::TestModelPatchesRound4::test_hypertile_node_runs",
    "test_models.py::TestRescaleCFG::"
    "test_node_patches_and_rides_derivations",
    "test_models.py::TestHypernetwork::test_loader_node_steers_sampling",
    "test_models.py::TestCustomSamplingAdvanced::"
    "test_dual_cfg_with_controlnet",
    "test_models.py::TestCustomSamplingAdvanced::"
    "test_dual_cfg_collapses_to_cfg_when_cond2_is_negative",
    "test_models.py::TestCustomSamplingAdvanced::"
    "test_dual_cfg_distinct_middle_finite_and_differs",
    "test_models.py::TestCustomSamplingAdvanced::"
    "test_dual_cfg_honors_rescale_patch",
    "test_models.py::TestCustomSamplingAdvanced::"
    "test_cfg_guider_matches_sampler_custom",
    "test_models.py::TestCustomSamplingAdvanced::"
    "test_basic_guider_is_cfg_one",
    "test_models.py::TestFreeU::test_freeu_sampling_e2e",
    "test_models.py::TestFreeU::"
    "test_freeu_changes_output_and_params_shared",
    "test_models.py::TestRegionalPrompting::"
    "test_mask_node_and_multistep_finite",
    "test_models.py::TestRegionalPrompting::"
    "test_one_step_halves_match_single_cond_runs",
    "test_models.py::TestAdvancedOps::"
    "test_ksampler_advanced_window_composition",
    "test_models.py::TestSD21Family::test_v_prediction_pipeline_samples",
    "test_models.py::TestSDXLRefinerFamily::"
    "test_refiner_shaped_unet_forward_and_key_walk",
    "test_models.py::TestSDXLRefinerFamily::"
    "test_refiner_size_cond_steers_sampling",
    "test_models.py::TestTokenMerging::test_node_patches_and_steers",
    "test_controlnet.py::TestSamplingAndOps::"
    "test_positive_only_control_does_not_steer_uncond",
    "test_controlnet.py::TestSamplingAndOps::"
    "test_control_changes_sample_output",
    "test_controlnet.py::TestPerEntryControlWindows::"
    "test_each_entry_keeps_its_own_window",
    "test_controlnet.py::TestControlNetAdvancedRound5::"
    "test_full_window_matches_plain_apply_on_both_sides",
    "test_controlnet.py::TestControlNetAdvancedRound5::"
    "test_empty_window_is_exact_noop",
    "test_controlnet.py::TestSameNetChainedTwice::"
    "test_two_links_of_one_net_sum",
    "test_controlnet.py::TestControlNetChaining::"
    "test_zero_net_chain_is_additive_identity",
    "test_controlnet.py::TestControlNetChaining::"
    "test_per_entry_nets_both_steer",
    "test_attention.py::TestRingIntegration::"
    "test_sd_scale_unet_forward_default_threshold",
    "test_attention.py::TestRingIntegration::"
    "test_unet_forward_ring_matches_oracle",
    "test_workflow.py::TestSdxlRefinerFixture::"
    "test_two_stage_handoff_fans_out",
    "test_workflow.py::TestImg2ImgE2E::"
    "test_hires_fix_chain_not_reexpanded",
    "test_workflow.py::TestImg2ImgE2E::"
    "test_denoise_below_one_preserves_source_structure",
    "test_workflow.py::TestHiresFixE2E::test_hires_fix_fans_out",
    "test_workflow.py::TestRepoFixtures::test_upscale_fixture",
    "test_workflow.py::TestRound4Fixtures::test_inpaint_model_fixture",
    "test_workflow.py::TestIp2pFixture::test_ip2p_fixture_fans_out",
    "test_bench.py::test_real_ckpt_smoke_hook",
    # PR 2: the coalesced-vs-serial bit-equivalence proof pays the
    # module's first-in-process trace cost (~18s cold); the acceptance
    # invariants (1.3x overlap win, single coalesced dispatch) live in
    # the cheap non-slow tests of the same module
    "test_pipeline.py::TestCoalescedExecution::"
    "test_coalesced_matches_serial_per_prompt",
    "test_server.py::TestPromptExtraPnginfo::"
    "test_extra_data_reaches_saved_pngs",
    "test_server.py::TestProfiling::test_profile_endpoints",
    # PR 9 headroom trim (tier-1 gate budget, ROADMAP item 7): the
    # three priciest remaining non-slow tests (25s/25s/18s measured
    # 2026-08-04) move out of the timed gate — each is a deep-oracle
    # variant whose cheaper siblings still run; the full `pytest
    # tests/` (README) keeps them all
    "test_torch_parity.py::"
    "test_clip_text_encoder_matches_transformers[tiny]",
    "test_checkpoints.py::test_roundtrip_exact[tiny]",
    "test_controlnet.py::TestControlNetChaining::"
    "test_two_live_nets_accumulate",
    # PR 12: the continuous-batching late-join bit-exactness proof
    # (~14s warm, ~27s cold — two samplers x serial references), the
    # same precedent as PR 2's coalesced==serial proof; the cheap
    # behavioral tests of the same module (non-contiguous merge,
    # slot-exit provenance, fallback, zero-retrace churn) stay in the
    # gate, and `bench.py --phase batching` re-proves exactness on
    # every watchdog run
    "test_batching.py::TestBucketExactness::"
    "test_late_join_bit_identical_to_serial",
    # PR 17: the park/resume two-sampler serial-reference proof follows
    # the same precedent (~18s warm); the single-sampler executor-level
    # exactness test (TestSloPreemption::
    # test_preempted_row_resumes_and_matches_serial) and the park
    # edge-case tests stay in the gate, and `bench.py --phase preempt`
    # re-proves park/resume bit-exactness on every watchdog run
    "test_batching.py::TestLatentPagingExactness::"
    "test_park_resume_bit_identical_to_serial",
    # PR 17 gate-budget drift fix (satellite): the four priciest
    # non-slow tests from the 2026-08-07 baseline top-10 (13.4s, 13.0s,
    # 12.4s, 11.7s) move out of the timed window to make room for the
    # latent-paging coverage — each is a deep variant whose cheaper
    # siblings keep the behavior covered; `pytest tests/` runs them all
    "test_controlnet.py::TestControlNetAdvancedRound5::"
    "test_diff_loader_adds_base_weights",
    "test_workflow.py::TestImg2ImgE2E::test_variation_sweep_fans_out",
    "test_reuse.py::TestResultTier::"
    "test_clear_memory_invalidates_and_reports",
    "test_models.py::TestComponentLoadersRound5::"
    "test_clip_loader_op_virtual_and_type_validation",
    # PR 19 gate-budget replenish (satellite): the nine priciest
    # non-slow tests from the 2026-08-07 top-10 (15.5s..9.7s, ~108s
    # total) move out of the timed window to restore >=100s headroom
    # for the traffic-twin suite and future growth — each is a deep
    # variant whose cheaper siblings keep the behavior covered (the
    # tenth, torch-parity clip[sd15], stays: its [tiny] sibling is
    # already slow-marked and the gate should keep one clip parity
    # proof); the full `pytest tests/` (README) still runs them all
    "test_workflow.py::TestRepoFixtures::test_txt2img_fixture",
    "test_pipeline.py::TestCoalescedExecution::"
    "test_burst_coalesces_into_one_dispatch",
    "test_workflow.py::TestImg2ImgE2E::test_side_branch_not_fanned_out",
    "test_models.py::TestBf16WeightStorage::"
    "test_flag_casts_unet_clip_not_vae",
    "test_tensor_plane.py::TestWorkflowTensorPlane::"
    "test_spine_moves_zero_host_bytes",
    "test_observability.py::TestServerTraceLifecycle::"
    "test_single_prompt_trace_tree",
    "test_workflow.py::TestRegionalTiledUpscale::"
    "test_regional_spmd_matches_single_device_oracle",
    "test_reuse.py::TestKillSwitch::test_cache_off_means_zero_lookups",
    "test_workflow.py::TestPngWorkflowMetadata::"
    "test_save_image_embeds_and_round_trips",
    # PR 20 gate-budget trim (satellite): the two priciest non-slow
    # tests from the 2026-08-07 top-10 (16.7s, 12.4s) move out of the
    # timed window to offset the analytics suite — regional tiling
    # stays covered by TestRepoFixtures::test_regional_fixture_fans_out
    # and the round-4 fixtures by test_sdxl_dualprompt_fixture; the
    # full `pytest tests/` (README) still runs them all
    "test_workflow.py::TestRegionalTiledUpscale::"
    "test_regional_masks_engage",
    "test_workflow.py::TestRound4Fixtures::test_unclip_fixture",
}


def pytest_collection_modifyitems(session, config, items):
    for item in items:
        key = f"{os.path.basename(str(item.fspath))}::" \
            + item.nodeid.split("::", 1)[1]
        if key in _SLOW_TESTS:
            item.add_marker(pytest.mark.slow)
    items.sort(key=lambda it: _MODULE_COST_S.get(
        os.path.basename(str(it.fspath)), 5))


# Gate-budget visibility (ROADMAP item 7): the tier-1 gate runs under a
# hard wall-clock window, and every PR grows the suite — print the
# top-10 slowest calls at the end of EVERY run so the next session sees
# where the budget went without re-running with --durations.
_test_durations: dict = {}


def pytest_runtest_logreport(report):
    if report.when == "call":
        _test_durations[report.nodeid] = report.duration


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    if not _test_durations:
        return
    top = sorted(_test_durations.items(), key=lambda kv: -kv[1])[:10]
    total = sum(_test_durations.values())
    terminalreporter.write_sep(
        "=", f"top-10 slowest calls (of {total:.0f}s total call time; "
             "tier-1 window 870s)")
    for nodeid, dur in top:
        terminalreporter.write_line(f"{dur:7.2f}s  {nodeid}")


@pytest.fixture(autouse=True)
def _isolated_config(tmp_path, monkeypatch):
    """Point the config layer at a per-test temp file."""
    monkeypatch.setenv("DISTRIBUTED_TPU_CONFIG",
                       str(tmp_path / "cluster_config.json"))
    yield


@pytest.fixture(autouse=True)
def _no_leaked_interrupt():
    """A leaked process-global interrupt flag silently NO-OPS every
    compiled sampler (the scan skips all steps and returns the noised
    input) — and most assertions still pass on no-op outputs, so the
    leak is near-invisible.  Guard every test on both sides."""
    from comfyui_distributed_tpu.runtime import interrupt as itr
    itr.clear_interrupt()
    yield
    itr.clear_interrupt()


@pytest.fixture
def rng():
    return np.random.default_rng(0)
