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
    # autotuner (ISSUE 18): the warm-cache-is-free pin — a seeded
    # cache answers with zero engines built and zero programs priced
    "test_tune.py::test_cache_hit_is_pure",
    # one real trainer e2e (the priciest smoke entry, ~1 min compile)
    "test_e2e.py::TestEndToEnd::test_train_checkpoints_and_resumes",
)


# Tests excluded from the tier-1 gate (`-m 'not slow'`), selected from
# measured durations (round 6: with the jax-0.4.x compat shims in
# place ~190 previously-erroring tests run for real, and the full
# suite is ~37 min — far past the 870 s tier-1 budget). Entries are
# node-id substrings like _SMOKE_PATTERNS: the heaviest individual
# tests plus the `multihost` spawn tests (real worker processes,
# ~20 s each and environment-sensitive). The full unfiltered suite
# remains the round gate and still runs everything here.
_SLOW_PATTERNS = (
    # sanitize: the engine builds + warms two engines (~11 s); the
    # trainer-level violation pin stays in tier-1
    "test_sanitize.py::test_engine_sanitized_decode_and_seeded_violation",
    # second measured cut: with the first cut applied, compile
    # costs shift onto surviving module-mates — these re-crossed
    # the 9 s line in a tier-1-only timing run (802 s wall, too
    # close to the 870 s budget; ~510 s after this cut).
    "test_breadth.py::TestElasticResume::test_resume_across_device_count_change",
    "test_breadth.py::TestResetOptState::test_recipe_change_keeps_weights",
    "test_ep_lm.py::test_ep_expert_memory_shards",
    "test_models_zoo.py::test_ddp_step_trains_with_model_state[<lambda>1]",
    "test_models_zoo.py::test_resnet18_forward_shape_and_bn_state",
    "test_optim_extras.py::TestParamEma::test_resume_with_ema_enabled_grafts_from_params",
    "test_pipe_fsdp.py::TestGPipeFsdp::test_matches_data_axis_run",
    "test_pipe_fsdp.py::TestGPipeFsdp::test_params_and_moments_rest_sharded",
    "test_pipeline_lm.py::test_interleaved_virtual_stages_match_sequential",
    "test_chaos.py::test_chaos_sigterm_preempts_then_resume_completes",
    "test_preemption.py::test_preempt_after_imported_checkpoint_resumes_exactly",
    "test_preemption.py::test_preempt_mid_epoch_then_resume_exactly",
    "test_remat.py::test_remat_with_dropout_same_rng_stream",
    "test_tp.py::test_tp_loss_parity[axes4-4]",
    "test_tp.py::test_tp_rejects_indivisible_heads",
    "test_tp.py::test_tp_with_accum_parity",
    "test_train_step.py::TestTraining::test_loss_decreases",
    "test_trainer_fast.py::test_fast_epoch_trains_and_resumes",
    "test_trainer_fast.py::test_pipe_vit_fast_epoch_trains",
    "test_trainer_pipe.py::test_pipe_trainer_augment_trains[1f1b]",
    "test_trainer_pipe.py::test_pipe_trainer_augment_trains[gpipe]",
    "test_trainer_pipe.py::test_pipe_trainer_augment_trains[interleaved]",
    "test_trainer_pipe.py::test_pipe_trainer_trains_and_evals[1f1b]",
    "test_trainer_pipe.py::test_pipe_trainer_trains_and_evals[gpipe]",
    "test_trainer_seq.py::test_ulysses_strategy_trains",
    "test_bpe.py::test_train_and_generate_text_e2e",
    "test_breadth.py::TestInferenceRestore::test_predict_cli_dataset_and_npy",
    "test_breadth.py::TestResumeEpoch::test_rewind_to_requested_epoch",
    "test_checkpoint.py::TestGqaQkvFormat::test_gqa_convert_script_end_to_end",
    "test_e2e.py::TestEndToEnd::test_rerun_at_same_epochs_trains_nothing",
    "test_e2e.py::TestEndToEnd::test_train_checkpoints_and_resumes",
    "test_elastic_shard.py::test_fsdp_lm_checkpoint_restores_on_wider_fsdp",
    "test_elastic_shard.py::test_replicated_checkpoint_restores_onto_fsdp_mesh",
    "test_ep_lm.py::test_ep4_parity_with_dp4",
    "test_ep_lm.py::test_ep_exact_parity_with_replicated",
    "test_ep_lm.py::test_full_stack_gqa_moe_tp_ep_sp",
    "test_fast.py::test_epoch_runner_trains",
    "test_generate.py::TestBeamSearch::test_beam_one_is_greedy",
    "test_generate.py::test_greedy_matches_stepwise_dense_argmax",
    "test_generate.py::test_predict_cli_generates_from_trained_checkpoint[dense]",
    "test_generate.py::test_predict_cli_generates_from_trained_checkpoint[moe]",
    "test_gqa.py::TestGQATraining::test_gqa_tp_trains_with_parity",
    "test_gqa.py::TestGQATraining::test_seq_parallel_step_matches_dense_reference",
    "test_gqa.py::TestGQATraining::test_trainer_cli_and_guards",
    "test_gqa.py::TestGQAxMoE::test_decode_matches_dense_forward",
    "test_gqa.py::TestGQAxMoE::test_pipe_gqa_moe_matches_sequential",
    "test_gqa.py::TestGQAxMoE::test_trains_and_loss_tracks_each_feature_alone",
    "test_grad_accum.py::TestDDPAccum::test_accum_trains",
    "test_grad_accum.py::TestSPMDAccum::test_accum_matches_full_batch_on_tp_mesh",
    "test_interleaved.py::TestKernel::test_step_matches_single_device_reference",
    "test_interleaved.py::TestKernel::test_trains_and_smoothing",
    "test_interleaved.py::TestTrainer::test_cli_trains",
    "test_lm.py::test_lm_learns_progressions",
    "test_lm.py::test_remat_variant_runs",
    "test_metrics.py::test_profile_dir_produces_trace",
    "test_models_zoo.py::test_ddp_step_trains_with_model_state[<lambda>0]",
    "test_moe.py::TestExpertParallel::test_ep_train_step_learns",
    "test_moe_lm.py::test_moe_lm_through_trainer",
    "test_moe_lm.py::test_moe_lm_trains_and_aux_contributes",
    "test_pipe_fsdp.py::TestHandScheduledFsdp::test_1f1b_matches_gpipe_under_fsdp",
    "test_pipe_fsdp.py::TestHandScheduledFsdp::test_interleaved_fsdp_matches_data_axis",
    "test_pipe_fsdp.py::TestTrainerPipeFsdp::test_cli_trains_and_resumes",
    "test_pipeline_lm.py::test_all_three_schedules_update_identically",
    "test_pipeline_lm.py::test_gpipe_loss_matches_sequential_reference",
    "test_pipeline_lm.py::test_moe_every_generalized_including_odd_depth",
    "test_pipeline_lm.py::test_moe_pipe_matches_sequential",
    "test_pipeline_lm.py::test_pp_ep_exact_parity_with_dp[1f1b]",
    "test_pipeline_lm.py::test_pp_ep_exact_parity_with_dp[gpipe]",
    "test_pipeline_lm.py::test_pp_ep_fsdp_composition",
    "test_pipeline_lm.py::test_pp_ep_sp_triple_composition_exact",
    "test_pipeline_lm.py::test_pp_ep_validation_and_trainer_e2e",
    "test_pipeline_lm.py::test_pp_sp_matches_pipe_only[1f1b-ulysses]",
    "test_pipeline_lm.py::test_pp_sp_matches_pipe_only[gpipe-ring]",
    "test_pipeline_lm.py::test_pp_tp_interleaved_matches_pp_only",
    "test_pipeline_lm.py::test_pp_tp_matches_pp_only[1f1b]",
    "test_pipeline_lm.py::test_pp_tp_matches_pp_only[gpipe]",
    "test_pipeline_lm.py::test_pp_tp_moe_gpipe_exact_and_handsched_refused",
    "test_pipeline_lm.py::test_tied_embedding_gradient_sums_both_ends",
    "test_pipeline_lm.py::test_trainer_cli_pipe_lm_e2e",
    "test_pipeline_vit.py::Test1F1B::test_1f1b_step_matches_gpipe_step",
    "test_pipeline_vit.py::Test1F1B::test_label_smoothing_schedules_agree",
    "test_pipeline_vit.py::TestPpTp::test_pp_tp_matches_pp_only",
    "test_real_data_e2e.py::test_train_cli_on_real_idx_files",
    "test_remat.py::test_remat_grads_match_baseline[resnet18-kw1-shape1]",
    "test_remat.py::test_remat_grads_match_baseline[vit_micro-kw0-shape0]",
    "test_remat.py::test_remat_grads_match_baseline[vit_moe_micro-kw2-shape2]",
    "test_remat.py::test_seq_transformer_remat_matches",
    "test_seq_compose.py::test_fsdp_seq_step_matches_replicated",
    "test_seq_compose.py::test_grad_accum_matches_single_step",
    "test_seq_compose.py::test_trainer_composes_fsdp_accum_smoothing_text",
    "test_seq_transformer.py::TestEquivalence::test_seq_parallel_matches_dense[ring]",
    "test_seq_transformer.py::TestTraining::test_grads_match_dense_reference",
    "test_seq_transformer.py::TestTraining::test_trains_on_dp_sp_mesh",
    "test_serve.py::TestEngine::test_greedy_matches_generate",
    "test_serve.py::TestEngine::test_moe_routing_config_threaded",
    "test_serve.py::TestDecodePath::test_bucket_boundary_greedy_matches_generate",
    "test_serve.py::TestDecodePath::test_seeded_sampling_matches_generate",
    "test_spmd.py::test_tp_fsdp_matches_ddp",
    "test_spmd.py::test_tp_only_mesh",
    "test_tp.py::test_classifier_tp_parity",
    "test_tp.py::test_tp_bf16_runs",
    "test_tp.py::test_tp_loss_parity[axes0-2]",
    "test_tp.py::test_tp_loss_parity[axes1-4]",
    "test_tp.py::test_tp_loss_parity[axes2-4]",
    "test_tp.py::test_tp_loss_parity[axes3-8]",
    "test_tp.py::test_tp_ulysses_parity",
    "test_trainer_fast.py::test_lm_fast_epoch_composes_with_fsdp",
    "test_trainer_fast.py::test_lm_fast_epoch_loss_identical_to_step_loop",
    "test_trainer_fast.py::test_pipe_fast_epoch_composes_with_fsdp_and_ep",
    "test_trainer_fast.py::test_pipe_lm_fast_epoch_loss_identical_to_step_loop[1f1b]",
    "test_trainer_fast.py::test_pipe_lm_fast_epoch_loss_identical_to_step_loop[gpipe]",
    "test_trainer_pipe.py::test_pipe_schedules_agree",
    "test_trainer_pipe.py::test_pipe_trainer_resumes",
    "test_trainer_seq.py::TestCausalLMTrainer::test_bf16_runs",
    "test_trainer_seq.py::TestCausalLMTrainer::test_train_eval_resume",
    "test_trainer_seq.py::test_bf16_mixed_precision",
    "test_trainer_seq.py::test_remat_composes",
    "test_trainer_seq.py::test_train_eval_checkpoint_resume",
    "test_trainer_spmd.py::test_expert_parallel_trainer",
    "test_trainer_spmd.py::test_tp_fsdp_trainer_trains_and_resumes",
    "test_zero1.py::test_trainer_zero1_checkpoints_and_resumes",
    "test_zero1.py::test_zero1_adam_single_step_matches",
    "test_zero1.py::test_zero1_step_matches_replicated_step",
    # ISSUE-7 zero strategy: the trainer e2e runs and the LM GSPMD
    # parity are the heavy entries (~7-9 s each); the step-level
    # parity/padding/layout pins stay in tier-1.
    "test_zero.py::test_trainer_zero_e2e_sanitized_resume",
    "test_zero.py::test_trainer_zero_lm_trains",
    "test_zero.py::test_zero_lm_gspmd_matches_plain_lm",
    # ISSUE-10 decode path: the engine-level bucket sweeps compile
    # 7-15 programs each (~10-15 s); the kernel/op pins, the seeded
    # token-identity runs, and the transfer/validation pins stay in
    # tier-1.
    "test_flash_decode.py::TestFlashEngine::test_bucket_edges_greedy_token_identity",
    "test_flash_decode.py::TestFlashEngine::test_seeded_sampling_token_identity",
    "test_flash_decode.py::TestFlashEngine::test_compile_counts_stable_and_labeled",
    "test_flash_decode.py::TestInt8KV::test_engine_int8_bounded_divergence_pin",
    "test_spec_decode.py::TestSpecEngine::test_greedy_equivalent_across_bucket_edges",
    "test_spec_decode.py::TestSpecEngine::test_compile_counts_stable_and_labeled",
    "test_spec_decode.py::TestSpecEngine::test_selfdraft_acceptance_is_one",
    "test_spec_decode.py::TestVerifyStep::test_full_match_advances_gamma",
    # ISSUE-11 request tracing: the speculative-engine timeline pin
    # compiles the whole draft program set (~10 s); the plain-engine
    # schema/causality/transfer pins stay in tier-1.
    "test_reqtrace.py::TestSpecRounds::"
    "test_spec_engine_timeline_carries_rounds",
    # third measured cut (PR 12): the tier-1 wall clock sat at
    # 736-871 s across back-to-back identical runs on this 1-core
    # host (~18% load variance) — over the 870 s budget on a bad
    # day. These are the ≥9 s survivors of the PR-10/11 serve-family
    # additions (measured via --durations on this host); each builds
    # its own engine/server pair, and each invariant keeps a cheaper
    # fast-tier sibling (seeded identity: test_serve seeded pin;
    # transfer spy: test_serve + test_paged spies; aggregator: the
    # in-process merge tests in test_slo's engine class).
    "test_spec_decode.py::TestSpecEngine::test_seeded_equivalent",
    "test_spec_decode.py::TestSpecEngine::"
    "test_transfer_stays_small_int32_under_sanitize",
    "test_serve.py::TestDecodePath::"
    "test_tail_chunk_near_total_len_matches_generate",
    "test_slo.py::TestAggregator::"
    "test_fleet_view_across_two_scraped_endpoints",
    "test_slo.py::TestAggregator::test_cli_end_to_end",
    "test_slo.py::TestAggregator::test_offline_metrics_files_merge",
    # ...and the 6-9 s band, after the cut above still left only
    # ~25 s of margin on a loaded run (812 s measured): each has a
    # cheaper fast-tier guard (warmup-count pin: bench.py asserts
    # compile_counts stability on every capture; flash+int8: the
    # per-op quantization pins; HTTP surface: test_graceful_drain).
    "test_serve.py::TestEngine::test_no_recompilation_after_warmup",
    "test_flash_decode.py::TestFlashEngine::"
    "test_flash_int8_compose_under_sanitize",
    "test_serve.py::TestServer::test_http_roundtrip",
    "test_spec_decode.py::TestSpecEngine::test_metrics_carry_acceptance",
    # paged KV (PR 12): every identity sweep that compiles its own
    # engine pair re-measured past (or near) the 9 s line — the
    # tier-1 budget was already within ~60 s of its 870 s ceiling
    # before this PR, so only the compile-light pins stay fast: the
    # transfer spy, /metricsz byte-identity, page-starved FIFO
    # requeue, the rejection matrix, and the pure-host allocator
    # property tests. The identity sweeps (incl. the forked-prefix
    # reuse pin) run in the full round gate like the other heavy
    # serve identity tests.
    "test_paged.py::TestTokenIdentity",
    "test_paged.py::TestTransfersAndCompiles::test_no_recompilation_after_warmup",
    "test_paged.py::TestConstructionValidation::test_spec_engine_allocates_reserve_pages",
    # autotuner (ISSUE 18): the cold search builds 3-4 engines
    # (~19 s), the engine-vs-engine identity pin builds 2 (~10 s),
    # the trainer load-path e2e trains a real zero epoch (~6 s);
    # the space/cost/cache/precedence pins stay in tier-1.
    "test_tune.py::test_tune_serve_end_to_end",
    "test_tune.py::test_measured_tokens_identical_across_bucket_edges",
    "test_tune.py::test_trainer_loads_zero_cache_by_default",
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
    # Only enforce when the full suite was collected — a targeted
    # `pytest tests/test_foo.py` run legitimately misses most patterns.
    if len(items) > 300 and unmatched:
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
def tmp_ckpt_dir(tmp_path):
    return str(tmp_path / "checkpoints")
