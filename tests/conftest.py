"""Test harness config: run everything on an 8-device virtual CPU mesh.

The TPU analogue of the reference's "N containers on one box" topology
(SURVEY.md §4): multi-device behavior is exercised without hardware via
``--xla_force_host_platform_device_count`` (read at backend init), with
the platform pinned to the host before any backend exists.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
# Keep f32 matmuls exact on CPU so oracle-parity tolerances are
# meaningful. NOT under TDN_TEST_TPU=1: the hardware gates measure the
# chip's default-precision MXU path, which this would mask.
if os.environ.get("TDN_TEST_TPU", "0") != "1":
    os.environ.setdefault("JAX_DEFAULT_MATMUL_PRECISION", "highest")

import jax  # noqa: E402

# TDN_TEST_TPU=1 leaves JAX's own platform resolution in place so the
# hardware-gated tests (test_tpu_hardware.py) can run against the real
# chip. Only that module is meant to run under the flag: the rest of the
# suite assumes the 8-device CPU topology and CPU-exact matmul tolerances.
if os.environ.get("TDN_TEST_TPU", "0") != "1":
    jax.config.update("jax_platforms", "cpu")
# Persistent XLA compile cache, at the one place every entry point uses
# (utils/backend.py): the suite's wall time is dominated by recompiling
# the same shard_map/scan programs every run.
from tpu_dist_nn.utils.backend import enable_compile_cache  # noqa: E402

enable_compile_cache()

import pytest  # noqa: E402

# ---------------------------------------------------------------------
# Quick tier (VERDICT r4 weak item 6: the full suite costs 12-35 min
# depending on box load; driver/judge boxes need a fast gate).
#
#   python -m pytest tests/ -m quick -q        # every family, < 5 min
#   python -m pytest tests/ -q                 # the full suite
#
# Curated representatives per module: the core parity/behavior test of
# each family plus its cheapest validation test, chosen from the
# round-5 `--durations=0` run. An entry is a bare test name (all
# parametrizations) or an exact id with brackets (that one case). "*"
# marks every test in the module (used for the TPU-gated hardware
# module, which skips without hardware either way).
# tests/test_quick_tier.py asserts every module has an entry and every
# entry resolves, so the list cannot rot silently.
QUICK_TESTS = {
    # PR 25: the AOT listing's HLO reader on the recorded parent step.
    "test_aot_step_ops": ["test_big_ops_keeps_layout_scope_and_target"],
    "test_autoscale": [
        # ISSUE 12 acceptance smokes: the 2->3->2 loopback scale
        # drill under a faults.py-paced burst (zero dropped), the
        # one-tick burn->spawn control-loop anchor, hedging's
        # first-reply-wins contract + the loopback straggler rescue,
        # and the POST /router/scale override.
        "test_autoscale_smoke_fleet_scales_up_and_back_down",
        "test_synthetic_burn_scales_up_within_one_tick",
        "test_hedge_fires_once_first_reply_wins_loser_cancelled",
        "test_hedge_rescues_straggler_over_loopback_wire",
        "test_manual_scale_override_via_post_route_and_status_route"],
    "test_batcher_pipeline": [
        "test_batches_launch_while_prior_fetch_in_flight",
        "test_warm_buckets_ladder_gauge_and_no_misses_after_warm"],
    "test_checkpoint": ["test_async_manager_saves_and_restores",
                        "test_manager_latest_and_retention",
                        "test_resume_noop_when_complete"],
    "test_continuous": [
        "test_continuous_matches_static_greedy_tokens",
        "test_serve_continuous_loopback_parity_and_counters",
        "test_mixed_budgets_take_fewer_steps_than_run_to_completion",
        # ISSUE 7: prefix-cache bit parity is the correctness anchor,
        # the prefilled-token counts what the pool is for.
        "test_prefix_cache_greedy_bit_parity_including_eos",
        "test_warm_prefix_pool_hits_and_prefills_fewer_tokens"],
    "test_conv": ["test_conv_forward_matches_oracle",
                  "test_engine_routes_conv_model"],
    "test_conv_kernel": ["test_conv_matches_lax[stride1-same]",
                         "test_shape_mismatch_rejected"],
    "test_data": ["test_synthetic_dataset_shapes_and_range"],
    "test_engine_cli": ["test_cli_up_smoke", "test_cli_oracle"],
    "test_errors_multihost": [
        "test_engine_down_then_unavailable_then_relaunch"],
    "test_examples": ["test_centralized_experiments_on_real_digits"],
    "test_expert_parallel": ["test_ep_forward_matches_grouped_oracle[4-2]",
                             "test_top2_training_learns"],
    "test_fastloader": ["test_gather_rows_threads_and_big_batch"],
    "test_fleet_obs": [
        # ISSUE 9 quick smokes: /slo + /timeseries endpoints and the
        # 2-process loopback stitched trace (single trace_id, spans
        # from both processes, lanes named by process).
        "test_slo_endpoint_and_gauges_smoke",
        "test_timeseries_endpoint_smoke",
        "test_two_process_loopback_stitched_trace"],
    "test_flash_attention": ["test_forward_matches_reference[32-False]",
                             "test_rejects_mismatched_shapes"],
    "test_incident": [
        # ISSUE 11 acceptance smokes: the loopback burn->bundle path,
        # the 2-replica stitched fleet drill (+ tdn incident/debug
        # CLI), both crash-path subprocess proofs, and the armed
        # recorder's one detector pass a sampler tick.
        "test_burn_detector_captures_bundle_with_faulted_span",
        "test_fleet_drill_burn_trips_router_recorder_stitched_bundle",
        "test_crash_unhandled_exception_leaves_valid_bundle",
        "test_crash_sigabrt_leaves_valid_bundle_then_dies_by_signal",
        "test_sampler_ticks_an_armed_recorder_once_and_quiet_captures_nothing"],
    "test_forward_parity": ["test_forward_matches_oracle_small",
                            "test_softmax_stability"],
    "test_gen_loop_clock": [
        # ISSUE 24: the loop's phases partition its wall time, the
        # iteration ring is bounded, a request's decode span says what
        # it rode.
        "test_phase_totals_sum_to_the_loops_wall_time",
        "test_ring_is_bounded_and_keeps_the_newest",
        "test_decode_span_says_what_the_request_rode_and_no_step_spans",
    ],
    "test_generate": ["test_greedy_generation_matches_teacher_forced_oracle",
                      "test_slot_step_never_carries_or_selects_the_cache",
                      "test_pipeline_generate_matches_single_chip",
                      "test_tp_generate_greedy_matches_single_chip"],
    # ISSUE 14: goodput conservation on the loopback wire (odd rows
    # forced into pow2 buckets, useful+pad==total exactly, /goodput
    # shares sum to 1), iteration-level continuous accounting + prefix
    # savings, the timeseries families across a counter reset, the tdn
    # top MFU/pad column in both modes + the --iterations CI path; the
    # loopback smoke's disarmed case records nothing.
    "test_goodput": [
        "test_loopback_serving_pad_accounting_exact",
        "test_continuous_scheduler_conservation_and_prefix_savings",
        "test_static_generate_accounting_eos_frozen_exact",
        "test_timeseries_goodput_families_and_counter_reset",
        "test_top_renders_mfu_pad_columns_fleet_and_single",
        "test_cli_top_iterations_reads_goodput_from_live_endpoint"],
    "test_graft_entry": ["test_entry_is_jittable",
                         "test_dryrun_multichip_odd_device_count"],
    "test_hetero_pipeline": ["test_forward_matches_single_program"],
    "test_interleaved": ["test_schedule_tables_build_and_verify",
                         "test_interleaved_lm_grads_match_single_chip"],
    # ISSUE 19 acceptance smokes: the bit-flip fingerprint detector,
    # the numeric guard's row-level failover bit-parity anchor, canary
    # golden stability across prober restarts, the full quarantine
    # lifecycle against two real replicas (detect -> drain-refusal ->
    # evidence -> reverify-readmit -> strikes -> break-glass), the
    # spot-check tamper arbitration, and the end-to-end quick-scaled
    # corruption drill.
    "test_integrity": [
        "test_array_checksum_and_fingerprint_detect_bitflip",
        "test_guard_partial_rows_failover_bit_parity",
        "test_canary_golden_stable_across_prober_restarts",
        "test_quarantine_lifecycle_detect_drain_refusal_evidence_reverify",
        "test_spotcheck_tamper_mismatch_arbitrates_to_guilty_replica",
        "test_corruption_drill_scenario_quarantines_exactly_one"],
    "test_interop": ["test_torch_round_trip", "test_torch_forward_parity"],
    "test_interop_keras": ["test_keras_forward_parity",
                           "test_keras_round_trip"],
    "test_kernels": ["test_matches_jnp[relu]", "test_shape_mismatch_raises"],
    # PR 25: the in-place K/V row write, partial last lane block.
    # PR 26: the second block family against its plain reference.
    "test_sala": ["test_forward_matches_reference",
                  "test_prefill_then_decode_matches_full_forward[37]",
                  "test_rebinding_a_slot_leaves_no_state_behind"],
    # PR 31: the third block family (rings, a scan, a chunk without
    # logits) against its plain reference.
    "test_sambay": ["test_forward_matches_reference",
                    "test_prefill_then_decode_matches_full_forward[13]",
                    "test_scheduler_streams_complete_with_lengths_as_asked"],
    # PR 33: the fourth block family (a latent cache, a share of the
    # routed experts) against its plain reference.
    "test_mla_moe": ["test_forward_matches_reference",
                     "test_prefill_then_decode_matches_full_forward[136]",
                     "test_the_shares_add_up_to_the_uncut_layer"],
    # PR 37: the fifth block family (window rings beside full K/V, two
    # head counts, the shared expert layer) against its plain reference,
    # and the programs of the two families it shares code with.
    "test_laguna": ["test_forward_matches_reference",
                    "test_prefill_then_decode_matches_full_forward[23]",
                    "test_the_shares_add_up_to_the_uncut_layer",
                    "test_kimis_programs_are_the_parents_operation_for_operation[step]"],
    # The sixth block family (Mamba-2 beside GQA in every layer,
    # the chunked SSD form) against its plain reference and the recurrence.
    "test_falcon_h1": ["test_forward_matches_reference",
                       "test_prefill_then_decode_matches_full_forward[13]",
                       "test_chunked_form_equals_the_recurrence_across_blocks_and_chunks",
                       "test_a_readmitted_slot_starts_from_zero_state"],
    # The expert layer both routed families call: the ragged form
    # against the masked form, and what it is built from.
    "test_experts": [
        "test_ragged_form_is_the_masked_form[crowded-float32]",
        "test_ragged_form_sorts_the_choices_and_sizes_nothing_by_the_worst_case"],
    "test_kv_write": [
        "test_write_rows_lands_rows_and_nothing_else[partial_lane_block]"],
    # PR 28: the chunk's block-masked attention kernel against the loop.
    "test_sparse_attend": [
        "test_kernel_matches_the_loop[1024-bfloat16]",
        "test_the_shapes_alone_decide_which_path_runs[ragged-chunk]"],
    # PR 32: the decode step's shared-K/V attention kernel against the
    # XLA path, and a planted fault it must catch.
    "test_decode_attend": [
        "test_kernel_matches_the_xla_path[tile-edges-bfloat16--0.4]",
        "test_a_kernel_that_reads_one_tile_too_few_is_caught",
        "test_the_shapes_alone_decide[cell-step]"],
    # PR 34: the chunk's expanded latent attention kernel against the loop.
    "test_expand_attend": [
        "test_kernel_matches_the_loop[37-bfloat16]",
        "test_the_shapes_alone_decide_which_path_runs[rehearsal]"],
    # PR 36: the decode step's absorbed latent attention kernel against
    # the XLA einsums, and a planted fault it must catch.
    "test_latent_attend": [
        "test_kernel_matches_the_xla_path[tile-edges-bfloat16-2]",
        "test_a_kernel_that_reads_one_tile_too_few_is_caught",
        "test_the_shapes_alone_decide[cell-step]"],
    "test_multihost_real": ["test_two_process_collectives"],
    "test_native_codec": ["test_examples_roundtrip_and_parity",
                          "test_fuzz_model_roundtrip_native_vs_python"],
    "test_obs": ["test_counter_gauge_histogram_basics",
                 "test_render_text_format_and_round_trip",
                 "test_loopback_serving_metrics_and_healthz",
                 "test_prometheus_exposition_conformance"],
    "test_optimizers": ["test_default_is_exactly_adam",
                        "test_warmup_ramps_learning_rate",
                        "test_grad_accum_no_update_until_k_steps"],
    "test_pipeline": ["test_four_stage_pipeline_matches_oracle",
                      "test_input_dim_validation"],
    "test_pipeline_1f1b": [
        "test_1f1b_matches_gpipe_grads[dims4-distribution4-3-1-1-3]",
        "test_1f1b_rejects_unknown_schedule"],
    "test_pipeline_ep": ["test_pp_ep_validates_batch_divisibility",
                         "test_pp_ep_shard_roundtrip",
                         "test_pp_ep_1f1b_grads_match_grouped_oracle[2-2-1-2]"],
    "test_pipeline_sp": ["test_pp_sp_forward_matches_single_chip[2-2-2-ulysses]",
                         "test_pp_sp_validates_divisibility",
                         "test_ring_collective_rotation_matches_ppermute"],
    "test_pipeline_tp": ["test_pp_tp_forward_matches_single_chip[2-2-2]",
                         "test_pp_tp_shard_roundtrip"],
    "test_pipeline_tp_sp": [
        "test_pp_tp_sp_1f1b_grads_match_single_chip[ulysses]"],
    "test_profile": [
        # The ISSUE-6 quick-tier smoke: loopback /profile shares sum
        # to the measured root wall.
        "test_loopback_profile_process_shares_sum_to_wall"],
    "test_profiling": ["test_latency_stats_summary",
                       "test_annotate_inside_jit"],
    "test_quantized": ["test_weight_quantization_roundtrip_error_bounded",
                       "test_quantized_forward_close_to_f32",
                       "test_quantize_honors_metadata_distribution"],
    # ISSUE 18 acceptance smokes: generator determinism, the
    # incident-bundle -> WorkloadTrace -> replay round trip (exact mix
    # + per-decile arrival fidelity over a live loopback fleet), the
    # seeded-probability fault mode, the stream-resume bound at its
    # exact boundary, and one quick-scaled scenario verdict.
    "test_replay": [
        "test_generators_deterministic_and_well_formed",
        "test_fault_plan_probability_mode_deterministic_under_seed",
        "test_bundle_round_trip_exact_mix_and_arrival_deciles",
        "test_stream_resume_bound_boundary_and_overflow_counter",
        "test_scenario_quick_smoke_deterministic_verdict"],
    "test_router": [
        # ISSUE 8: the loopback p2c smoke (spread + tdn_router_*
        # family on /metrics) and the breaker-registry-eviction
        # regression.
        "test_router_loopback_spreads_load_and_exposes_metrics",
        "test_pool_remove_evicts_breaker_registry_for_reused_address"],
    "test_resilience": [
        "test_chaos_smoke_quick_tier_recovers_via_retries",
        "test_breaker_cycle_closed_open_half_open_closed",
        "test_shed_at_watermark_surfaces_resource_exhausted"],
    # ISSUE 15 acceptance smokes: the 2x-overload degradation drill
    # (critical completes, best_effort absorbs the sheds), the
    # real-model preemption bit-parity anchor, class-watermark sheds
    # + deadline expiry on the shared core, the retry-after floor over
    # a real loopback shed, and the router class hop.
    "test_sched_core": [
        "test_overload_drill_critical_never_shed_best_effort_absorbs",
        "test_preempted_greedy_generate_bit_matches_unpreempted",
        "test_class_watermark_sheds_best_effort_first",
        "test_expired_entry_fails_deadline_exceeded_at_pop_without_launch",
        "test_shed_reply_carries_retry_after_and_client_honors_floor",
        "test_router_forwards_class_and_server_labels_it"],
    "test_real_data": ["test_real_digits_load_shapes_and_content",
                       "test_realtext_corpus_supports_valid_heldout_at_scale",
                       "test_cli_train_digits_end_to_end"],
    "test_ring_attention": ["test_matches_full_attention",
                            "test_gradients_match"],
    "test_schema": ["test_model_json_round_trip",
                    "test_shipped_sample_configs_load_and_run"],
    "test_serving": ["test_codec_round_trip",
                     "test_grpc_round_trip_matches_local",
                     "test_serve_generate_single_chip_and_validation"],
    # ISSUE 16 streaming smokes: frame codec + TokenStream channel
    # invariants (pure host logic, milliseconds), the loopback
    # router-hop stream (first token delivered BEFORE retirement,
    # tokens bit-identical to unary through the same hop), and the
    # hedging exemption contract.
    "test_stream": [
        "test_frame_codec_roundtrips_and_rejects_garbage",
        "test_token_stream_cursor_dedupes_replayed_prefix",
        "test_stream_first_token_before_retirement_through_router",
        "test_hedge_policy_rejects_generate_stream"],
    # ISSUE 13: the tdn lint gate in both directions — zero
    # non-baselined findings on the shipped tree, exit 1 on a planted
    # violation, each rule firing on its fixture with the exact id and
    # line.
    "test_tdnlint": [
        "test_rule_fires_on_violating_fixture",
        "test_rule_silent_on_clean_twin",
        "test_shipped_tree_is_clean_via_tdn_lint_cli",
        "test_tdn_lint_exits_nonzero_on_planted_violation"],
    "test_tensor_parallel": ["test_forward_matches_single_chip[spec1]",
                             "test_shard_roundtrip"],
    # ISSUE 21: --platform is asserted and clients open no backend; one
    # main-path kernel and the raised flash ceiling compile for a v5e.
    "test_platform": [
        "test_platform_tpu_on_a_cpu_process_exits_nonzero_naming_cpu",
        "test_only_device_commands_resolve_a_platform",
        "test_clients_and_router_initialise_no_backend"],
    "test_tpu_compile": [
        "test_kernel_compiles_to_a_mosaic_call_for_v5e[f32_chain_b256]",
        "test_kernel_compiles_to_a_mosaic_call_for_v5e"
        "[flash_t8192_h12_d64_bf16_grad]"],
    "test_tpu_hardware": ["*"],
    # ISSUE 10: the codec fast lane's correctness anchor (byte-exact
    # scalar/vectorized equivalence + fuzz agreement), the decode-into-
    # staging path through a real batcher, and the loopback fast-path
    # counter check.
    "test_wire_codec": [
        "test_encode_vectorized_matches_scalar_bytes_exactly",
        "test_decode_fuzz_fast_and_scalar_agree_on_mutated_bytes",
        "test_batcher_stages_wire_matrices_straight_into_bucket_buffer",
        "test_loopback_serving_round_trip_rides_fast_path"],
    "test_trace": ["test_chrome_trace_export_schema",
                   "test_loopback_round_trip_is_one_trace_tree",
                   "test_sampling_rate_edge_cases"],
    "test_trace_gaps": ["test_gaps_are_put_down_to_the_phases_that_cover_them",
                        "test_device_seconds_by_scope_from_event_metadata"],
    "test_train": ["test_single_chip_training_learns",
                   "test_train_lm_does_not_invalidate_caller_params"],
    "test_transformer": ["test_loss_descends_on_copy_task",
                         "test_pipeline_matches_single_chip",
                         "test_load_corpus_prefers_vendored_real_then_explicit"],
    "test_zb_v": ["test_zb_v_tables_build_and_verify",
                  "test_zb_v_beats_same_granularity_schedules",
                  "test_zb_v_grads_match_single_chip[2-2-2]"],
    "test_zero": ["test_opt_state_actually_sharded",
                  "test_shardings_prefer_largest_divisible_axis"],
    "test_zero_bubble": ["test_zb_tables_build_and_verify",
                         "test_zb_halves_the_1f1b_bubble",
                         "test_zb_train_step_runs",
                         "test_zb_stash_grads_match_single_chip[2-1-4]"],
    "test_split_backward": ["*"],
    "test_quick_tier": ["*"],
    # PR 29: the documents and the tools against the tree (static).
    "test_docs": ["*"],
    "test_tools": ["*"],
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "quick: fast representative tier — every family in < 5 min "
        "(run with `-m quick`; see conftest.QUICK_TESTS)",
    )


def pytest_collection_modifyitems(config, items):
    for item in items:
        module = os.path.basename(str(item.fspath))[:-3]
        entries = QUICK_TESTS.get(module, ())
        name = item.name
        bare = name.split("[")[0]
        for entry in entries:
            if entry == "*" or entry == name or (
                "[" not in entry and entry == bare
            ):
                item.add_marker(pytest.mark.quick)
                break
