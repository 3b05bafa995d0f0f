"""Test harness: 8 emulated CPU devices — the TPU analogue of the
reference's "2-process gloo on a laptop" test strategy (SURVEY.md §4).

Real ``psum``/sharding semantics are exercised in-process over 8
virtual devices. Must configure the platform before any JAX backend
initializes: pytest plugins may import jax before this file runs, so
we both set the env var and force the config.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
if "xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
# Persistent XLA compilation cache env vars. Two measured findings
# (round 3) before touching these:
# 1. They do NOT engage the cache under pytest — plugin entry points
#    import jax before conftest runs, so jax's config default
#    (compilation_cache_dir=None) is already frozen. Forcing it with
#    jax.config.update() here DID engage it (~3× warm-run speedup)
#    but XLA:CPU AOT deserialization on this host warns of a machine-
#    feature mismatch ("+prefer-no-scatter … could lead to … SIGILL")
#    and cache-loaded executables abort mid-suite. Do not re-enable
#    executable caching on the CPU suite.
# 2. REMOVING these two lines deterministically deadlocks the GPipe
#    trainer test's ppermute rendezvous on the emulated mesh (A/B/A
#    verified); with them present the suite is green. The mechanism
#    is opaque (the cache never engages either way) — treat them as
#    part of the known-good environment, not as cache configuration.
_CACHE = os.path.join(os.path.dirname(__file__), os.pardir, ".jax_cache")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.abspath(_CACHE))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# Round 6 (jax 0.4.x image): finding 1 above no longer holds — on this
# jax version the env-var cache DOES engage under pytest (tens of
# thousands of entries appeared in .jax_cache), and loading them hits
# exactly the machine-feature mismatch documented above (observed as
# segfaults inside resumed-trainer tests; removing the cache dir fixed
# them). Keep the env vars (finding 2: removing them deadlocks the
# GPipe ppermute rendezvous) but turn the cache OFF at the config
# level — which finding 1 showed was the effective state on the old
# image anyway.
jax.config.update("jax_enable_compilation_cache", False)
# ... and the same for SUBPROCESSES (test_breadth / test_real_data_e2e
# / multihost spawn train.py runs): they inherit the env vars above
# but not this process's config state, so without this they repopulate
# .jax_cache and then SIGSEGV loading their own entries on the next
# spawned run (the resume-style tests are exactly two runs deep).
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "multihost: spawns real jax.distributed worker processes",
    )
    config.addinivalue_line(
        "markers",
        "smoke: fast representative per-subsystem tier "
        "(`pytest -m smoke`, <6 min; full suite is the round gate)",
    )
    config.addinivalue_line(
        "markers",
        "slow: heavyweight tests (≥9s measured, or multihost spawns) "
        "excluded from the tier-1 gate (`-m 'not slow'`); run them "
        "via the full unfiltered suite",
    )


# One or two FAST representatives per subsystem (node-id substrings),
# selected from measured durations (round 4: full suite 33 min / 407
# tests — too slow as an inner loop). `pytest -m smoke` runs just
# these; the full suite remains the pre-commit/round gate. A pattern
# that stops matching (rename) fails collection loudly below.
_SMOKE_PATTERNS = (
    # model zoo + flagship parity
    "test_model.py::test_forward_shape_and_dtype",
    "test_model.py::test_param_count",
    # data: sampler / loader / readers / vendored real data / augment
    "test_sampler.py::TestCoverage::test_disjoint_union_covers_dataset",
    "test_loader.py::TestSharding::test_batch_is_sharded_over_data_axis",
    "test_mnist_reader.py::TestLocalCache::test_load_from_cached_gz",
    "test_uci_digits.py::test_loads_with_mnist_shapes",
    "test_augment.py::TestOps::test_flip_is_flip_or_identity",
    "test_cifar.py::test_corrupt_cached_tar_falls_back",
    "test_imagenet.py::test_registry_loads_synthetic",
    "test_ppm.py::test_resize_matches_pil_closely",
    "test_bpe.py::TestTokenizer::test_roundtrip_exact",
    # native C++ layer
    "test_native.py::test_prefetcher_matches_python_gather",
    # DDP step + eval + fast path + accumulation
    "test_train_step.py::TestEvalStep::test_weighted_counts",
    "test_fast.py::test_epoch_runner_matches_stepwise",
    "test_grad_accum.py::test_cli_flag_parses",
    # checkpointing
    "test_checkpoint.py::TestRoundTrip::test_save_restore_identical",
    "test_checkpoint.py::TestGqaQkvFormat::"
    "test_verify_gqa_qkv_flags_wrong_k_and_reads_stacked_kernels",
    # round-5 composition guards (construction-time only: cheap)
    "test_pipeline_lm.py::"
    "test_pp_sp_ring_rejected_on_handsched_and_trainer_guards",
    # attention: kernel, dispatch, ring/causal
    "test_flash.py::test_flash_matches_dense",
    "test_attention.py::TestBestAttentionDispatch",
    "test_ring.py::TestCausal::test_ring_causal_matches_dense_8way",
    # parallelism: tp / fsdp / zero1 / ep / moe specs + pipeline fwd
    "test_tp.py::test_seq_param_specs_assignment",
    "test_seq_compose.py::test_fsdp_actually_shards_params_and_moments",
    "test_zero1.py::test_opt_state_sharded_params_replicated",
    "test_ep_lm.py::test_ep_specs_assignment",
    "test_moe.py::TestMoEMLP::test_top1_matches_dense_reference",
    "test_pipeline.py::test_pipeline_forward_matches_sequential",
    "test_one_f1b.py::test_schedule_invariants_and_counts",
    "test_interleaved.py::TestSchedule::test_complete_and_wellformed",
    # sequence family + LM + generation + GQA
    "test_lm.py::test_causality_no_future_leakage",
    "test_gqa.py::TestGQAModel::test_cache_is_compact",
    "test_generate.py::TestFilterLogits::test_top_k_keeps_exactly_k",
    # serving: admission front door + the static-shape pin
    "test_serve.py::TestScheduler::test_admission_control",
    "test_serve.py::TestEngine::test_no_recompilation_after_warmup",
    # fault tolerance: chaos-spec round-trip property + the
    # corruption→quarantine→fallback pin (ISSUE 5 smoke-tier entries)
    "test_chaos.py::test_chaos_spec_roundtrip_property",
    "test_chaos.py::test_corrupt_latest_quarantines_and_falls_back",
    "test_fetch.py::test_retries_transient_then_succeeds",
    # config / metrics / watchdog / optim
    "test_config.py::test_reference_defaults",
    "test_metrics.py::test_writer_disabled_is_noop",
    "test_watchdog.py::test_fires_when_beats_stop",
    # static analysis (ddp_tpu.analysis): the self-lint CI gate
    # (scripts/lint.py --self, the compileall gate's sibling), one
    # fixture-corpus representative, and the transfer-guard pin of
    # the runtime sanitizer (--sanitize)
    "test_lint.py::test_self_lint_clean",
    "test_lint.py::test_rule_true_positives_pinned",
    "test_sanitize.py::TestSanitizerUnit::test_guard_blocks_implicit_transfer",
    # observability: whole-tree syntax gate, trace-exporter schema pin,
    # and the tracing-off-is-free guarantee (ddp_tpu.obs)
    "test_obs.py::test_compileall_package_and_scripts",
    "test_obs.py::test_trace_schema_valid",
    "test_obs.py::test_disabled_tracer_is_pinned_free",
    # run health: health-off-is-free pin + the Prometheus-text
    # exposition lint (the trace-schema validator's siblings)
    "test_health.py::test_disabled_health_is_pinned_free",
    "test_promtext.py::test_builder_render_and_validate",
    # request tracing + SLO (ISSUE 11): span schema + causal-ordering
    # validation, the seeded-breach gauge lint (validate_promtext
    # over every new gauge), and the off-is-free exposition pin
    "test_reqtrace.py::TestPerfettoExport::"
    "test_exported_spans_reconstruct_causally",
    "test_slo.py::TestEngineAndGauges::"
    "test_seeded_breach_visible_everywhere",
    "test_slo.py::TestEngineAndGauges::"
    "test_disabled_exposition_byte_identical",
    "test_optim_extras.py::TestParamEma::test_recurrence_exact",
    # fleet router (ISSUE 14): breaker state machine, retry math,
    # hedging first-completion-wins, and the fleet gauge lint — all
    # fake-transport/fake-clock, milliseconds each
    "test_fleet.py::TestCircuitBreaker::"
    "test_state_machine_closed_open_halfopen_closed",
    "test_fleet.py::test_retry_backoff_bounds",
    "test_fleet.py::TestHedging::"
    "test_first_completion_wins_and_loser_cancelled",
    "test_fleet.py::test_render_fleet_gauges_lint_clean",
    # one real trainer e2e (the priciest smoke entry, ~1 min compile)
    "test_e2e.py::TestEndToEnd::test_train_checkpoints_and_resumes",
)


# Tests excluded from the tier-1 gate (`-m 'not slow'`). Entries are
# node-id substrings like _SMOKE_PATTERNS. The rule: a test is
# slow-marked for a MEASURED duration, stated beside it.
#
# The budget that holds now is the driver's command: `timeout 1470`,
# `-p xdist -n 6 --dist loadfile` — six workers, and one FILE's tests
# share a worker, so what a test costs tier-1 is what it adds to its
# file's worker. PR 29's tree ran 1,019 tests in 356 s of those
# 1,470 s; PR 30 brought 30 main-path tests back from this list (the
# serve engine, flash-decode, the LM, checkpoint/resume, preemption,
# generation, remat: 2-25 s each) because a test that `-m 'not slow'`
# never runs guards nothing.
#
# The seconds below are setup + call + teardown from one run of
# `-m slow` alone under that command's workers (PR 30: 158 tests,
# 2,702 s of test time in 490 s of wall clock; six at once on 8
# cores, so each reads high). 128 tests are matched here (1,769 s);
# the other 30 carry `@pytest.mark.slow` or `multihost` in their
# files (real worker processes, environment-sensitive). Bringing one
# back: run its FILE under JAX_PLATFORMS=cpu, see it pass, delete its
# line (ROADMAP C16).
_SLOW_PATTERNS = (
    "test_bpe.py::test_train_and_generate_text_e2e",  # 49 s
    "test_breadth.py::TestElasticResume::test_resume_across_device_count_change",  # 5 s
    "test_breadth.py::TestInferenceRestore::test_predict_cli_dataset_and_npy",  # 97 s
    "test_breadth.py::TestResetOptState::test_recipe_change_keeps_weights",  # 6 s
    "test_breadth.py::TestResumeEpoch::test_rewind_to_requested_epoch",  # 12 s
    "test_chaos.py::test_chaos_sigterm_preempts_then_resume_completes",  # 7 s
    "test_checkpoint.py::TestGqaQkvFormat::test_gqa_convert_script_end_to_end",  # 38 s
    "test_elastic_shard.py::test_fsdp_lm_checkpoint_restores_on_wider_fsdp",  # 11 s
    "test_elastic_shard.py::test_replicated_checkpoint_restores_onto_fsdp_mesh",  # 1 s
    "test_ep_lm.py::test_ep4_parity_with_dp4",  # 11 s
    "test_ep_lm.py::test_ep_exact_parity_with_replicated",  # 17 s
    "test_ep_lm.py::test_ep_expert_memory_shards",  # 10 s
    "test_ep_lm.py::test_full_stack_gqa_moe_tp_ep_sp",  # 11 s
    "test_fast.py::test_epoch_runner_trains",  # 18 s
    "test_gqa.py::TestGQATraining::test_gqa_tp_trains_with_parity",  # 18 s
    "test_gqa.py::TestGQATraining::test_seq_parallel_step_matches_dense_reference",  # 31 s
    "test_gqa.py::TestGQATraining::test_trainer_cli_and_guards",  # 24 s
    "test_gqa.py::TestGQAxMoE::test_decode_matches_dense_forward",  # 17 s
    "test_gqa.py::TestGQAxMoE::test_pipe_gqa_moe_matches_sequential",  # 26 s
    "test_gqa.py::TestGQAxMoE::test_trains_and_loss_tracks_each_feature_alone",  # 16 s
    "test_grad_accum.py::TestSPMDAccum::test_accum_matches_full_batch_on_tp_mesh",  # 11 s
    "test_interleaved.py::TestKernel::test_step_matches_single_device_reference",  # 31 s
    "test_interleaved.py::TestKernel::test_trains_and_smoothing",  # 8 s
    "test_interleaved.py::TestTrainer::test_cli_trains",  # 10 s
    "test_metrics.py::test_profile_dir_produces_trace",  # 5 s
    "test_models_zoo.py::test_ddp_step_trains_with_model_state[<lambda>0]",  # 19 s
    "test_models_zoo.py::test_ddp_step_trains_with_model_state[<lambda>1]",  # 10 s
    "test_models_zoo.py::test_resnet18_forward_shape_and_bn_state",  # 14 s
    "test_moe.py::TestExpertParallel::test_ep_train_step_learns",  # 6 s
    "test_moe_lm.py::test_moe_lm_through_trainer",  # 47 s
    "test_moe_lm.py::test_moe_lm_trains_and_aux_contributes",  # 27 s
    "test_optim_extras.py::TestParamEma::test_resume_with_ema_enabled_grafts_from_params",  # 8 s
    "test_paged.py::TestConstructionValidation::test_spec_engine_allocates_reserve_pages",  # 4 s
    "test_paged.py::TestTokenIdentity",  # 9 tests, 113 s
    "test_paged.py::TestTransfersAndCompiles::test_no_recompilation_after_warmup",  # 6 s
    "test_pipe_fsdp.py::TestGPipeFsdp::test_matches_data_axis_run",  # 8 s
    "test_pipe_fsdp.py::TestGPipeFsdp::test_params_and_moments_rest_sharded",  # 5 s
    "test_pipe_fsdp.py::TestHandScheduledFsdp::test_1f1b_matches_gpipe_under_fsdp",  # 8 s
    "test_pipe_fsdp.py::TestHandScheduledFsdp::test_interleaved_fsdp_matches_data_axis",  # 10 s
    "test_pipe_fsdp.py::TestTrainerPipeFsdp::test_cli_trains_and_resumes",  # 11 s
    "test_pipeline_lm.py::test_all_three_schedules_update_identically",  # 11 s
    "test_pipeline_lm.py::test_gpipe_loss_matches_sequential_reference",  # 15 s
    "test_pipeline_lm.py::test_interleaved_virtual_stages_match_sequential",  # 6 s
    "test_pipeline_lm.py::test_moe_every_generalized_including_odd_depth",  # 8 s
    "test_pipeline_lm.py::test_moe_pipe_matches_sequential",  # 23 s
    "test_pipeline_lm.py::test_pp_ep_exact_parity_with_dp[1f1b]",  # 13 s
    "test_pipeline_lm.py::test_pp_ep_exact_parity_with_dp[gpipe]",  # 21 s
    "test_pipeline_lm.py::test_pp_ep_fsdp_composition",  # 16 s
    "test_pipeline_lm.py::test_pp_ep_sp_triple_composition_exact",  # 12 s
    "test_pipeline_lm.py::test_pp_ep_validation_and_trainer_e2e",  # 20 s
    "test_pipeline_lm.py::test_pp_sp_matches_pipe_only[1f1b-ulysses]",  # 7 s
    "test_pipeline_lm.py::test_pp_sp_matches_pipe_only[gpipe-ring]",  # 9 s
    "test_pipeline_lm.py::test_pp_tp_interleaved_matches_pp_only",  # 9 s
    "test_pipeline_lm.py::test_pp_tp_matches_pp_only[1f1b]",  # 6 s
    "test_pipeline_lm.py::test_pp_tp_matches_pp_only[gpipe]",  # 8 s
    "test_pipeline_lm.py::test_pp_tp_moe_gpipe_exact_and_handsched_refused",  # 17 s
    "test_pipeline_lm.py::test_tied_embedding_gradient_sums_both_ends",  # 15 s
    "test_pipeline_lm.py::test_trainer_cli_pipe_lm_e2e",  # 14 s
    "test_pipeline_vit.py::Test1F1B::test_1f1b_step_matches_gpipe_step",  # 9 s
    "test_pipeline_vit.py::Test1F1B::test_label_smoothing_schedules_agree",  # 8 s
    "test_pipeline_vit.py::TestPpTp::test_pp_tp_matches_pp_only",  # 24 s
    "test_real_data_e2e.py::test_train_cli_on_real_idx_files",  # 34 s
    "test_reqtrace.py::TestSpecRounds::test_spec_engine_timeline_carries_rounds",  # 15 s
    "test_sanitize.py::test_engine_sanitized_decode_and_seeded_violation",  # 9 s
    "test_seq_compose.py::test_fsdp_seq_step_matches_replicated",  # 14 s
    "test_seq_compose.py::test_grad_accum_matches_single_step",  # 8 s
    "test_seq_compose.py::test_trainer_composes_fsdp_accum_smoothing_text",  # 13 s
    "test_seq_transformer.py::TestEquivalence::test_seq_parallel_matches_dense[ring]",  # 8 s
    "test_seq_transformer.py::TestTraining::test_grads_match_dense_reference",  # 16 s
    "test_seq_transformer.py::TestTraining::test_trains_on_dp_sp_mesh",  # 6 s
    "test_slo.py::TestAggregator::test_cli_end_to_end",  # 7 s
    "test_slo.py::TestAggregator::test_fleet_view_across_two_scraped_endpoints",  # 6 s
    "test_slo.py::TestAggregator::test_offline_metrics_files_merge",  # 4 s
    "test_spec_decode.py::TestSpecEngine::test_compile_counts_stable_and_labeled",  # 10 s
    "test_spec_decode.py::TestSpecEngine::test_greedy_equivalent_across_bucket_edges",  # 38 s
    "test_spec_decode.py::TestSpecEngine::test_metrics_carry_acceptance",  # 4 s
    "test_spec_decode.py::TestSpecEngine::test_seeded_equivalent",  # 10 s
    "test_spec_decode.py::TestSpecEngine::test_selfdraft_acceptance_is_one",  # 8 s
    "test_spec_decode.py::TestSpecEngine::test_transfer_stays_small_int32_under_sanitize",  # 3 s
    "test_spec_decode.py::TestVerifyStep::test_full_match_advances_gamma",  # 20 s
    "test_spmd.py::test_tp_fsdp_matches_ddp",  # 7 s
    "test_spmd.py::test_tp_only_mesh",  # 5 s
    "test_tp.py::test_classifier_tp_parity",  # 13 s
    "test_tp.py::test_tp_bf16_runs",  # 5 s
    "test_tp.py::test_tp_loss_parity[axes0-2]",  # 15 s
    "test_tp.py::test_tp_loss_parity[axes1-4]",  # 4 s
    "test_tp.py::test_tp_loss_parity[axes2-4]",  # 5 s
    "test_tp.py::test_tp_loss_parity[axes3-8]",  # 6 s
    "test_tp.py::test_tp_loss_parity[axes4-4]",  # 5 s
    "test_tp.py::test_tp_rejects_indivisible_heads",  # 7 s
    "test_tp.py::test_tp_ulysses_parity",  # 3 s
    "test_tp.py::test_tp_with_accum_parity",  # 6 s
    "test_trainer_fast.py::test_fast_epoch_trains_and_resumes",  # 21 s
    "test_trainer_fast.py::test_lm_fast_epoch_composes_with_fsdp",  # 12 s
    "test_trainer_fast.py::test_lm_fast_epoch_loss_identical_to_step_loop",  # 22 s
    "test_trainer_fast.py::test_pipe_fast_epoch_composes_with_fsdp_and_ep",  # 26 s
    "test_trainer_fast.py::test_pipe_lm_fast_epoch_loss_identical_to_step_loop[1f1b]",  # 11 s
    "test_trainer_fast.py::test_pipe_lm_fast_epoch_loss_identical_to_step_loop[gpipe]",  # 19 s
    "test_trainer_fast.py::test_pipe_vit_fast_epoch_trains",  # 7 s
    "test_trainer_pipe.py::test_pipe_schedules_agree",  # 12 s
    "test_trainer_pipe.py::test_pipe_trainer_augment_trains[1f1b]",  # 6 s
    "test_trainer_pipe.py::test_pipe_trainer_augment_trains[gpipe]",  # 7 s
    "test_trainer_pipe.py::test_pipe_trainer_augment_trains[interleaved]",  # 13 s
    "test_trainer_pipe.py::test_pipe_trainer_resumes",  # 13 s
    "test_trainer_pipe.py::test_pipe_trainer_trains_and_evals[1f1b]",  # 7 s
    "test_trainer_pipe.py::test_pipe_trainer_trains_and_evals[gpipe]",  # 20 s
    "test_trainer_seq.py::TestCausalLMTrainer::test_bf16_runs",  # 10 s
    "test_trainer_seq.py::TestCausalLMTrainer::test_train_eval_resume",  # 26 s
    "test_trainer_seq.py::test_bf16_mixed_precision",  # 12 s
    "test_trainer_seq.py::test_remat_composes",  # 17 s
    "test_trainer_seq.py::test_train_eval_checkpoint_resume",  # 37 s
    "test_trainer_seq.py::test_ulysses_strategy_trains",  # 8 s
    "test_trainer_spmd.py::test_expert_parallel_trainer",  # 14 s
    "test_trainer_spmd.py::test_tp_fsdp_trainer_trains_and_resumes",  # 18 s
    "test_zero.py::test_trainer_zero_e2e_sanitized_resume",  # 6 s
    "test_zero.py::test_trainer_zero_lm_trains",  # 6 s
    "test_zero.py::test_zero_lm_gspmd_matches_plain_lm",  # 4 s
    "test_zero1.py::test_trainer_zero1_checkpoints_and_resumes",  # 19 s
    "test_zero1.py::test_zero1_adam_single_step_matches",  # 16 s
    "test_zero1.py::test_zero1_step_matches_replicated_step",  # 10 s
)


def pytest_collection_modifyitems(config, items):
    unmatched = set(_SMOKE_PATTERNS) | set(_SLOW_PATTERNS)
    for item in items:
        for pat in _SMOKE_PATTERNS:
            if pat in item.nodeid:
                item.add_marker(pytest.mark.smoke)
                unmatched.discard(pat)
        for pat in _SLOW_PATTERNS:
            if pat in item.nodeid:
                item.add_marker(pytest.mark.slow)
                unmatched.discard(pat)
        if item.get_closest_marker("multihost"):
            item.add_marker(pytest.mark.slow)
    # The whole suite (``tests/`` or a directory above it among the
    # arguments) holds every pattern: a renamed or deleted file raises.
    # A targeted `pytest tests/test_foo.py` or `pytest tests/benchmark`
    # run legitimately misses the patterns of every other file and is
    # held to those of the files it collected (a count of items cannot
    # tell the two apart: `tests/benchmark` alone collects over 300).
    here = os.path.dirname(os.path.abspath(__file__))
    base = str(config.invocation_params.dir)
    whole = any(
        (here + os.sep).startswith(
            os.path.abspath(os.path.join(base, a.split("::")[0])) + os.sep)
        for a in config.args
    )
    if not whole:
        seen = {os.path.basename(i.nodeid.split("::")[0]) for i in items}
        unmatched = {p for p in unmatched if p.split("::")[0] in seen}
    if unmatched:
        raise pytest.UsageError(
            f"smoke patterns match nothing (renamed tests?): "
            f"{sorted(unmatched)}"
        )


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert devs[0].platform == "cpu" and len(devs) == 8, devs
    return devs


@pytest.fixture(scope="session")
def mesh8(devices):
    from ddp_tpu.runtime.mesh import MeshSpec, make_mesh

    return make_mesh(MeshSpec(data=8), devices=devices)


@pytest.fixture(scope="session")
def mnist_synthetic():
    from ddp_tpu.data import mnist

    return mnist.synthetic(4096, seed=0), mnist.synthetic(1024, seed=1)


@pytest.fixture()
def backward_over_budget(monkeypatch):
    """``ops/flash.py``'s planning function handed a VMEM budget no head
    fits, so every backward traced under it takes the GRID form
    (``flash_dq`` + ``flash_dkv``), as a ring hop at 32k does. The
    budget is the planning function's argument: the program has no
    switch."""
    from ddp_tpu.ops import flash

    chosen = flash._backward_form
    monkeypatch.setattr(
        flash, "_backward_form",
        lambda *a, **kw: chosen(*a, **kw, budget=1024))


@pytest.fixture()
def tmp_ckpt_dir(tmp_path):
    return str(tmp_path / "checkpoints")
