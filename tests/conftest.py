"""Test harness: emulate an 8-device TPU mesh on CPU.

Reference test strategy (SURVEY.md §4): the reference needs real CUDA
devices for every XLA test.  Here multi-device behaviour is tested on CPU
via ``--xla_force_host_platform_device_count`` — collectives, shardings
and pipeline schedules execute for real across 8 virtual devices.
"""

import os

# Tests run on the CPU backend: multi-device sharding is exercised on 8
# emulated CPU devices.  The chip is driven by chip_smoke.py / tests_tpu.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

# Persistent compile cache: the suite's wall time is dominated by XLA
# compiles of 8-device trainers (measured 102s -> 26s on one pipeline
# test with a warm cache).  Keyed on HLO + platform, so source changes
# that alter the computation recompile; stale entries are harmless.
from torchacc_tpu.utils.compile_cache import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache(min_compile_secs=1.0)

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 emulated devices, got {len(devs)}"
    return devs


# -- fast/slow split ---------------------------------------------------------
# `make test` runs -m "not slow" (< 5 min quick gate on one core);
# `make test-all` and CI run everything.  Heavy e2e tests measured >= 13s
# on the reference box are centrally marked here (plus any test already
# marked @pytest.mark.slow inline).
_SLOW = {
    "test_pp_x_sp_matches_pp_and_sp",
    "test_gc_cnt_partial_remat_matches",
    "test_gc_cls_submodule_remat_matches",
    "test_two_process_dp_step",
    "test_moe_aux_loss_contributes",
    "test_pp_matches_single",
    "test_hf_trainer_adapter",
    "test_ep_matches_single_device",
    "test_save_restore_resume_exact",
    "test_attn_dropout_grad_accum_decorrelated",
    "test_restore_into_different_layout",
    "test_pp_1f1b_matches_single",
    "test_grad_accum_uneven_token_counts",
    "test_grad_accum_matches_big_batch",
    "test_tp_matches_single_device",
    "test_pp_1f1b_tied_embeddings",
    "test_pp_1f1b_memory_beats_gpipe",
    "test_trainer_fused_matches_unfused",
    "test_converted_model_trains",
    "test_accuracy_parity_harness",
    "test_accuracy_parity_adamw_bf16_leg",
    "test_tp_with_cp_composition",
    "test_pp_with_fsdp_trains",
    "test_e2e_training_with_cp",
    "test_fit_loop",
    "test_train_loss_decreases",
    "test_moe_aux_loss_survives_gc_cnt",
    "test_expert_parallel_training",
    "test_checkpoint_manager_rotation",
    "test_offload_policy_real_multi_device",
    "test_remat_policies_train",
    "test_cp_grads_match_local",
    "test_cp_window_grads_match_local",
    "test_pp_1f1b_interleaved_matches_single",
    "test_pp_1f1b_interleaved_with_fsdp_and_dropout",
    "test_pp_1f1b_with_tp_matches_single",
    "test_pp_unrolled_layers_matches_scan",
    "test_ep_x_pp_composition",
}


def pytest_collection_modifyitems(config, items):
    matched = set()
    for item in items:
        base = item.name.split("[")[0]
        if base in _SLOW:
            matched.add(base)
            item.add_marker(pytest.mark.slow)
    stale = _SLOW - matched
    if not stale:
        return
    # renamed/deleted tests must not silently rejoin the fast gate — but
    # only a FULL collection can judge staleness (subset runs legitimately
    # miss entries).
    here = os.path.dirname(os.path.abspath(__file__))
    all_files = {f for f in os.listdir(here)
                 if f.startswith("test_") and f.endswith(".py")}
    collected_files = {os.path.basename(str(item.fspath)) for item in items}
    if all_files <= collected_files:
        raise pytest.UsageError(
            f"stale entries in conftest._SLOW (rename them too): "
            f"{sorted(stale)}")
