"""Test bootstrap: force an 8-device virtual CPU mesh.

The suite runs under `JAX_PLATFORMS=cpu`; ensure_virtual_cpu_devices
targets JAX at 8 virtual CPU devices before the first backend
initialization, which is the supported path for testing multi-chip
sharding without hardware. The chip is reached only through
`chip_smoke.py` (tests/test_chip_compile.py compiles for a DESCRIBED
chip and is the only file that does).
"""

import os
import sys

# repo root on sys.path so `import kubeml_tpu` works without installation
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kubeml_tpu.testing import ensure_virtual_cpu_devices  # noqa: E402

ensure_virtual_cpu_devices(8)

# Cost-ledger XLA capture OFF by default in the suite: the extra AOT
# lower+compile per program per engine instance adds ~50% wall time to
# the engine-heavy files (measured on test_serving.py) and would blow
# the tier-1 budget. The capture path itself stays covered by
# tests/test_cost_ledger.py, which opts back in explicitly
# (CostLedger(capture_enabled=True) in the canonical budget inventory,
# KUBEML_COST_LEDGER=1 in its subprocess/engine tests). Everything
# else the ledger does — analytic records, dispatch attribution,
# snapshots, reconciliation of closed forms — is env-independent and
# still exercised by every engine test.
os.environ.setdefault("KUBEML_COST_LEDGER", "0")

import pytest  # noqa: E402

# ---------------------------------------------------------------- test tiers
#
# Smoke tier (`pytest -m "not slow"`) must stay under ~3 minutes so it is
# usable as the inner-loop check; the full tier runs everything (CI).
# Slowness is a measured property, not a design one, so it is maintained
# HERE as a list of node ids (measured with --durations=0 on the 8-device
# CPU mesh) instead of decorators scattered across files. Every subsystem
# keeps at least one fast test in the smoke tier.
SLOW_TESTS = {
    # model learning / convergence (tens of seconds each)
    "test_models_text.py::test_text_model_learns[lstm-0.01]",
    "test_models_text.py::test_text_model_learns[bert-tiny-0.001]",
    "test_models_vision.py::test_resnet18_engine_round",
    "test_models_vision.py::test_forward_shapes[resnet18-32]",
    "test_models_vision.py::test_forward_shapes[resnet50-64]",
    "test_models_vision.py::test_forward_shapes[resnet32-32]",
    "test_models_vision.py::test_forward_shapes[vgg11-32]",
    "test_models_lenet.py::test_lenet_learns",
    "test_models_gpt.py::test_gpt_learns",
    "test_models_gpt.py::test_gpt_moe_learns",
    "test_models_gpt.py::test_gpt_forward_shapes",
    "test_models_gpt.py::test_gpt_cached_generate_matches_infer",
    "test_models_gpt.py::test_gpt_cached_generate_sampling_and_clip",
    "test_models_gpt.py::test_gpt_seq_parallel_ring_matches_dense",
    "test_models_gpt.py::test_gpt_seq_parallel_ulysses_matches_dense",
    "test_models_gpt.py::test_gpt_moe_loss_includes_aux",
    "test_models_gpt.py::test_gpt_causality",
    "test_models_text.py::test_forward_shapes[bert-tiny-2]",
    "test_models_text.py::test_forward_shapes[lstm-4]",
    "test_models_text.py::test_bert_seq_parallel_matches_dense",
    "test_parallel_tp_sp.py::test_gpt_tp_forward_matches_replicated",
    "test_models_gpt.py::test_gpt_moe_ep_sharded_matches_unsharded",
    "test_models_gpt.py::test_gpt_moe_seq_parallel_matches_dense",
    "test_models_gpt.py::test_gpt_moe_seq_parallel_default_capacity_runs",
    "test_models_gpt.py::test_gpt_moe_trains_seq_parallel",
    "test_models_gpt.py::test_gpt_pipelined_matches_dense",
    "test_models_text.py::test_bert_max_len_guard",
    # experiment harness grids
    "test_experiments.py::test_baseline_text_grids_run[bert]",
    "test_experiments.py::test_baseline_text_grids_run[lstm]",
    "test_experiments.py::test_bench_text_engine_arm_runs",
    "test_experiments.py::test_bench_text_generate_arm_runs",
    "test_experiments.py::test_single_node_baseline_arm",
    # examples (full end-to-end function runs)
    "test_examples.py::test_gpt_example_trains_end_to_end",
    "test_examples.py::test_lenet_example_trains_end_to_end",
    "test_examples.py::test_two_jobs_run_concurrently",
    # parallelism equivalence / convergence
    "test_parallel_tp_sp.py::test_kavg_trains_tp_sharded_variables",
    "test_parallel_tp_sp.py::test_kavg_trains_tp_sharded_gpt",
    "test_parallel_tp_sp.py::test_kavg_trains_seq_parallel_bert_ring",
    "test_parallel_tp_sp.py::test_kavg_trains_seq_parallel_gpt_ring",
    "test_parallel_tp_sp.py::test_kavg_trains_seq_parallel_gpt_ulysses",
    "test_job.py::test_job_tensor_parallel_bert",
    "test_job.py::test_job_seq_parallel_gpt",
    "test_control_plane.py::test_tensor_parallel_job_through_controller",
    "test_parallel_tp_sp.py::test_ring_attention_grads_match",
    "test_parallel_tp_sp.py::test_ulysses_grads_match",
    "test_parallel_tp_sp.py::test_ring_attention_matches_full",
    "test_parallel_tp_sp.py::test_bert_tp_forward_matches_replicated",
    "test_parallel_pp_ep.py::test_moe_training_converges",
    "test_parallel_pp_ep.py::test_moe_sharded_matches_unsharded",
    "test_parallel_pp_ep.py::test_moe_matches_per_token_reference",
    "test_parallel_pp_ep.py::test_pipeline_grads_match",
    "test_parallel_pp_ep.py::test_moe_grads_finite",
    "test_parallel_pp_ep.py::test_pipeline_training_converges",
    "test_parallel_pp_ep.py::test_pipeline_aux_matches_sequential",
    "test_parallel_pp_ep.py::test_moe_trunk_pipelines",
    "test_parallel_pp_ep.py::test_moe_trunk_pipelines_expert_sharded",
    "test_parallel_pp_ep.py::test_moe_pipeline_rejects_indivisible_experts",
    # manual TP (round 3): every engine/grad/forward-parity test compiles
    # multi-axis shard_map programs (tens of seconds each on the CPU
    # mesh); the init-shapes check stays as the smoke-tier representative
    "test_manual_tp.py::"
    "test_bert_manual_tp_forward_matches_dense[float32-1e-05-1e-05]",
    "test_manual_tp.py::"
    "test_bert_manual_tp_forward_matches_dense[bfloat16-0.05-0.02]",
    "test_manual_tp.py::"
    "test_gpt_manual_tp_forward_matches_dense[float32-1e-05-1e-05]",
    "test_manual_tp.py::"
    "test_gpt_manual_tp_forward_matches_dense[bfloat16-0.05-0.02]",
    "test_manual_tp.py::test_manual_tp_grads_match_dense",
    "test_manual_tp.py::test_kavg_trains_manual_tp_bert",
    "test_manual_tp.py::test_kavg_trains_tp_sp_combined",
    "test_manual_tp.py::test_kavg_trains_tp_sp_combined_gpt",
    "test_manual_tp.py::test_kavg_manual_tp_compressed_merge",
    "test_manual_tp.py::test_kavg_sp_compressed_merge",
    "test_manual_tp.py::test_manual_tp_rejects_indivisible_heads",
    "test_manual_tp.py::test_manual_tp_init_matches_dense_shapes",
    "test_job.py::test_job_tensor_and_seq_parallel_combined",
    # round-3 re-tier (smoke measured 375s vs the <180s contract after
    # the new suites landed; durations re-measured on this machine) —
    # every file below keeps at least one fast test in the smoke tier
    "test_models_gpt.py::test_gpt_generate",
    "test_models_gpt.py::test_gpt_moe_registered_and_shapes",
    "test_models_gpt.py::test_gpt_generate_interior_and_all_pad",
    "test_models_gpt.py::test_gpt_infer_empty_prompt",
    "test_models_gpt.py::test_gpt_pipelined_guards",
    "test_parallel_tp_sp.py::test_sp_loss_handles_padding_across_shards",
    "test_parallel_tp_sp.py::test_ring_attention_causal",
    "test_parallel_tp_sp.py::test_ring_attention_causal_with_padding",
    "test_parallel_tp_sp.py::test_ulysses_causal_with_padding",
    "test_control_plane.py::test_end_to_end_train_infer",
    "test_control_plane.py::test_task_stop_via_controller",
    "test_control_plane.py::test_infer_cache_invalidates_on_new_checkpoint",
    "test_experiments.py::test_grid_sweep_live",
    "test_job.py::test_max_parallelism_caps_scheduler_growth",
    "test_job.py::test_job_shuffle_option",
    "test_job.py::test_dynamic_parallelism_callback",
    "test_job.py::test_warm_start_function_mismatch_rejected",
    "test_pallas_flash.py::test_ring_flash_matches_full",
    "test_pallas_flash.py::test_flash_grads_all_pad_row_match_reference",
    "test_pallas_flash.py::"
    "test_ring_flash_causal_noncontiguous_layout_poisons",
    "test_models_text.py::test_bert_seq_parallel_ulysses_matches_dense",
    "test_parallel_pp_ep.py::test_pipeline_matches_sequential",
    "test_syncdp.py::test_syncdp_matches_single_stream[True]",
    "test_syncdp.py::test_fsdp_matches_single_stream",
    "test_models_text.py::test_bert_padding_invariance",
    "test_models_gpt.py::test_gpt_infer_rejects_overlong_prompt",
    # distributed / deployment / control-plane long paths
    "test_distributed.py::test_kavg_round_over_multislice_mesh",
    "test_distributed_multiprocess.py::"
    "test_two_process_cluster_runs_kavg_round",
    "test_distributed_multiprocess.py::"
    "test_two_process_result_matches_single_process",
    "test_distributed_multiprocess.py::"
    "test_checkpoint_written_by_coordinator",
    "test_distributed_multiprocess.py::"
    "test_full_job_runs_across_two_processes",
    "test_distributed_multiprocess.py::"
    "test_full_job_matches_single_process",
    "test_role_deployment.py::test_split_role_processes_train",
    "test_distributed_multiprocess.py::"
    "test_job_survives_rank_death_via_supervisor_restart",
    "test_standalone_jobs.py::test_standalone_stop",
    "test_standalone_jobs.py::test_standalone_train_updates_and_infer",
    "test_standalone_jobs.py::test_dual_standalone_jobs_with_partitions",
    "test_standalone_jobs.py::test_crashed_job_process_releases_partition",
    "test_standalone_jobs.py::test_crashed_job_restarts_from_checkpoint",
    "test_standalone_jobs.py::test_restart_budget_exhausted_fails_job",
    "test_standalone_jobs.py::"
    "test_two_crashes_two_restarts_continuous_history",
    "test_standalone_jobs.py::"
    "test_sigterm_preemption_reschedules_without_budget",
    # elastic degraded mode: the per-round sweep runs 7 crash+resume job
    # pairs; the single-point preempt/resume tests stay in the smoke
    # tier as the fast representatives
    "test_elastic.py::test_crash_at_every_round_resumes_bit_identical",
    # donation-aliasing regression needs a larger slab and 4 repeat
    # trials (the corruption is allocator-timing dependent)
    "test_elastic.py::test_resume_survives_buffer_donation",
    "test_pallas_flash.py::"
    "test_ulysses_flash_training_round_matches_reference",
    "test_control_plane.py::test_dynamic_parallelism_through_scheduler",
    "test_control_plane.py::test_metrics_exposition_and_clearing",
    "test_control_plane.py::test_mid_job_inference",
    "test_cli.py::test_cli_full_flow",
    "test_job.py::test_checkpoint_every_and_warm_start",
    "test_job.py::test_job_seq_and_expert_parallel_moe",
    # round-5 job-level parity arms (70-160 s each: two full jobs per
    # test); the PP/EP surface keeps fast smoke representatives in
    # test_job_pipeline_parallel_misconfigs (~0 s: 400s fire before any
    # compile) + the elastic/fsdp/rounds-per-dispatch tests (5-8 s)
    "test_job.py::test_job_pipeline_parallel_matches_dense",
    "test_job.py::test_job_pipeline_parallel_with_experts",
    "test_job.py::test_job_pipeline_parallel_bert_matches_dense",
    "test_job.py::test_job_dp_ep_gspmd_matches_replicated",
    "test_parallel_pp_ep.py::test_kavg_sp_ep_round_matches_sp_only",
    "test_parallel_pp_ep.py::test_ep_alltoall_ffn_matches_dense",
    "test_parallel_pp_ep.py::test_moe_pipeline_alltoall_matches_replicated",
    "test_pallas_flash.py::test_flash_grads_match_reference",
    "test_pallas_flash.py::"
    "test_ring_flash_grads_match_dense_ring_causal_ragged",
    "test_pallas_flash.py::test_ring_flash_training_round_matches_dense",
    "test_pallas_flash.py::test_ring_flash_causal",
    "test_pallas_flash.py::test_ring_flash_causal_with_padding",
}


# Nightly tier (round 4): the full tier was outgrowing CI's 45-minute
# cap (~39 min measured). These are the heaviest tests whose coverage
# is REPRESENTED by a faster sibling that stays in the CI tier — each
# entry names its stand-in. CI runs `-m "not nightly"`; the nightly
# workflow (and any local `pytest tests/`... with `-m ""`) runs all.
# Nightly tests are also slow-marked, so the smoke tier is unaffected.
NIGHTLY_TESTS = {
    # job-level TP+SP / SP carving: stood in for by
    # test_job_seq_and_expert_parallel_moe (seq+expert carving, same
    # code path) + the engine-level combined tests in test_manual_tp
    "test_job.py::test_job_tensor_and_seq_parallel_combined",
    "test_job.py::test_job_seq_parallel_gpt",
    # vision engine convergence: bench.py measures the same round on
    # hardware every round; test_lenet_learns keeps a convergence run
    "test_models_vision.py::test_resnet18_engine_round",
    # resnet50 forward shape: resnet18/32/vgg11 shape tests remain
    "test_models_vision.py::test_forward_shapes[resnet50-64]",
    # flash-ring grads: the causal+ragged superset case and the full
    # training-round parity stay in the CI tier
    "test_pallas_flash.py::test_ring_flash_grads_match_dense_ring",
    "test_pallas_flash.py::test_ring_flash_grads_match_dense_ring_causal",
    # function-registry end-to-end: the lenet example test keeps the
    # registry path; GPT training is covered by test_gpt_learns
    "test_examples.py::test_gpt_example_trains_end_to_end",
    # TP through the full control plane: control-plane train covered by
    # test_end_to_end_train_infer, TP job by test_job_tensor_parallel_bert
    "test_control_plane.py::test_tensor_parallel_job_through_controller",
    # text sweep harness: the lstm grid arm stays
    "test_experiments.py::test_baseline_text_grids_run[bert]",
    # manual-TP suite: grads-match + bert training + tp_sp_combined
    # (bert) remain; the gpt combined variant and the TP compressed
    # merge (sp compressed merge remains) move out
    "test_manual_tp.py::test_kavg_trains_tp_sp_combined_gpt",
    "test_manual_tp.py::test_kavg_manual_tp_compressed_merge",
    # SP x MoE training: the replicated-expert SP round runs as the
    # reference arm INSIDE test_kavg_sp_ep_round_matches_sp_only
    "test_models_gpt.py::test_gpt_moe_trains_seq_parallel",
    # chained two-crash supervised recovery: the one-crash supervised
    # test (test_job_survives_rank_death_via_supervisor_restart) keeps
    # the crash->supervisor-restart->resume path in the CI tier
    "test_distributed_multiprocess.py::"
    "test_two_crashes_two_supervised_restarts",
}


def pytest_collection_modifyitems(config, items):
    matched = set()
    for item in items:
        # node id relative to tests/: "<file>::<name>[<param>]"
        nodeid = item.nodeid.split("/")[-1]
        if nodeid in SLOW_TESTS or nodeid in NIGHTLY_TESTS:
            matched.add(nodeid)
            item.add_marker(pytest.mark.slow)
        if nodeid in NIGHTLY_TESTS:
            item.add_marker(pytest.mark.nightly)
    # a stale entry (renamed/removed test) would silently put a slow
    # test back into the smoke tier — make it a collection error instead.
    # Only enforced on whole-file collection (no ::nodeid selection, no
    # -k narrowing); partial selections legitimately match a subset.
    if config.option.keyword or any("::" in a for a in config.args):
        return
    collected_files = {item.nodeid.split("/")[-1].split("::")[0]
                       for item in items}
    stale = {t for t in (SLOW_TESTS | NIGHTLY_TESTS) - matched
             if t.split("::")[0] in collected_files}
    if stale:
        raise pytest.UsageError(
            f"SLOW_TESTS/NIGHTLY_TESTS entries match no collected test: "
            f"{sorted(stale)}")


@pytest.fixture(scope="session")
def mesh8():
    from kubeml_tpu.parallel.mesh import make_mesh
    return make_mesh(n_data=8)


@pytest.fixture(scope="session")
def mesh4x2():
    from kubeml_tpu.parallel.mesh import make_mesh
    return make_mesh(n_data=4, n_model=2)


@pytest.fixture()
def tmp_home(tmp_path, monkeypatch):
    """Isolated KUBEML_TPU_HOME per test."""
    monkeypatch.setenv("KUBEML_TPU_HOME", str(tmp_path / "kubeml_home"))
    return tmp_path / "kubeml_home"
