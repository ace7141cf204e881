"""Test configuration.

Tests run on CPU with 8 virtual devices (multi-chip sharding validated without
TPU hardware — same technique the driver's dryrun uses) and float64 enabled
for gradient checks (the reference's oracle also runs in double precision,
``gradientcheck/GradientCheckUtil.java``).  The platform is forced through
``jax.config`` so the suite is CPU-only whatever the caller's environment
says, and JAX's persistent compilation cache is switched off: the package
would otherwise write every tiny test program into the checkout's cache
directory (``nn/compile_cache.py``) from six xdist workers at once.
"""
import os

# read by jax at import: off for this process and, through the environment,
# for every child process a test spawns
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402

# Heavy tests (>5s on the 1-core CPU environment, mostly XLA compiles of
# full zoo architectures).  Fast loop: pytest -m "not slow" (~6.5 min);
# full suite ~14 min.  Centralized here so test files stay unmarked.
_SLOW_TESTS = {
    "test_googlenet_forward",
    "test_two_process_training_and_crash_recovery",
    "test_facenet_embeddings_normalized",
    "test_resnet50_small_train_step",
    "test_3d_transformer_training_step",
    "test_ring_attention_exact",
    "test_graph_fold_resnet_block",
    "test_alexnet_forward",
    "test_switch_transformer_block_moe",
    "test_graph_builder_modules",
    "test_vgg_forward",
    "test_inception_resnet_v1_forward",
    "test_vae_pretrain_and_generate",
    "test_lenet_train_step",
    "test_transformer_lm_trains_and_predicts",
    "test_generate_tokens_greedy_recovers_cycle",
    "test_learns_and_tracks_aux",
    "test_gpipe_gradients_match_sequential",
    "test_simplecnn_forward",
    "test_sharded_moe_matches_single_device",
    "test_seq2seq_vertices",
    "test_transformer_incremental_decode_matches_full_forward",
    "test_moe_layer_rnn_input",
    "test_lenet_style_mnist_training",
    "test_transformer_lm_trains",
    "test_training_matches_scan",
    "test_parameter_averaging_learns_iris",
    "test_graph_fit_on_device",
    "test_dryrun_in_process_8_devices",
    "test_poisoned_default_backend_falls_back_to_subprocess",
    "test_mp_parameter_averaging_trains",
    "test_mp_shared_gradients_trains_and_exchanges",
    "test_mp_evaluate_and_score_match_local",
    "test_mp_averaging_retry_reexecutes_dead_worker",
    "test_mp_shared_retry_reexecutes_from_mirror",
    "test_mp_shared_ack_protocol_exact_counts",
    "test_mp_evaluate_retry_stateless_reexecution",
    "test_mp_retries_exhausted_raises",
    "test_mp_crash_windows_around_done",
    "test_multiprocess_word2vec_matches_thread_version",
    "test_multiprocess_word2vec_retry",
    "test_early_stopping_over_multiprocess_master",
    "test_pretrained_keras_weights_bridge",
    # chaos soak tests (tests/test_cluster.py): spawn real OS processes
    # and SIGKILL them mid-run; also carry the `chaos` marker so the
    # whole harness can be run alone with `pytest -m chaos`
    "test_chaos_sigkill_elastic_host_between_checkpoints",
    "test_chaos_crash_mid_checkpoint_commit",
    "test_chaos_sigkill_mp_worker_mid_round",
    "test_mp_heartbeat_watchdog_evicts_wedged_worker",
    # sharded barrier chaos (tests/test_elastic_sharded.py): two real OS
    # processes share one store and get hard-killed mid-protocol
    "test_shard_chaos_fault_free_barrier_store_reshards",
    "test_shard_chaos_non_primary_dies_mid_block",
    "test_shard_chaos_primary_dies_before_commit",
    "test_shard_chaos_partition_during_barrier",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.name.split("[")[0] in _SLOW_TESTS:
            item.add_marker(pytest.mark.slow)
