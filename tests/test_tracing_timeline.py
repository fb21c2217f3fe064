"""One timeline (obs/tracing.py, PR 24): the program's host spans land in
any open ``jax.profiler`` trace, its device work carries the registered
``jax.named_scope`` names, and the registries are the one source of both
lists.

- every registered device scope appears in the op_name metadata of the
  compiled toy train step / decode / prefill programs (one case a scope);
- with a profiler trace open on CPU, a toy ``ServeEngine`` and a toy
  ``Trainer`` put every registered hot-path span on the ``/host:CPU``
  plane, parents cover their children (one case a span; ONE
  module-scoped trace serves them all);
- with both sinks idle ``span()`` is the shared no-op, and the ring
  stays off while only the profiler sink is live;
- docs/observability.md's span table lists exactly the registry;
- the private seams the on-chip benchmark reads keep their names.
"""

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import torchacc_tpu as ta
from torchacc_tpu.models import TransformerLM, get_preset
from torchacc_tpu.obs import tracing
from torchacc_tpu.train import accelerate

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# where each registered device scope has to show up
TRAIN_SCOPES = ("embed_tokens", "layers", "ln1", "ln2", "attn", "o_proj",
                "mlp", "final_norm", "flash_fwd", "flash_dq", "flash_dkv",
                "fused_ce", "optimizer")
DECODE_SCOPES = ("embed", "layers", "ln1", "ln2", "qkv", "kv_write",
                 "paged_attn", "o_proj", "mlp", "head", "sample")
PREFILL_SCOPES = ("embed", "layers", "qkv", "kv_write", "paged_attn",
                  "mlp", "head")

# a latent-attention / held-expert model's decode program (PR 26)
LATENT_DECODE_SCOPES = ("mla_q", "mla_kv", "latent_attn", "kv_write",
                        "router", "moe_dispatch", "experts",
                        "shared_expert", "moe_combine")
# what its serve/deliver spans carry (chipbench/readers/expert_load.py)
MOE_SPAN_ATTRS = ("moe_pairs", "moe_max", "moe_hit", "moe_slots",
                  "moe_layer_steps")

# the decode program of a model of two latent layer kinds (PR 30): the
# indexed selection of its full layers, the window layers' kernel, the
# headwise gate
SPARSE_DECODE_SCOPES = ("index_write", "indexer", "index_topk",
                        "sparse_latent_attn", "window_latent_attn",
                        "attn_gate")
# what its serve/deliver spans carry (chipbench/readers/
# selected_context.py, rooflines/sparse_select_common.py) and its
# serve/admit spans (blocks in use by kind of layer)
SELECT_SPAN_ATTRS = ("sel_attended", "sel_cached", "win_attended")
ADMIT_BLOCK_ATTRS = ("blocks_full", "blocks_window", "window_blocks_freed")

# the decode program of a grouped-query model of two layer kinds (PR 33):
# the sliding layers' kernel call has its own scope beside paged_attn
WINDOW_DECODE_SCOPES = ("window_paged_attn", "paged_attn", "qkv",
                        "kv_write", "router", "experts")
# what its serve/deliver spans carry (chipbench/rooflines/
# gqa_window_common.py); its serve/admit spans carry ADMIT_BLOCK_ATTRS
WINDOW_SPAN_ATTRS = ("ctx_attended", "win_attended")

# the programs of a model of single-mixer layers (PR 42): a decode step
# updates every slot's recurrent state, a prefill chunk scans from it
SSM_DECODE_SCOPES = ("ssm_mixer", "ssm_conv", "ssm_step", "ln1",
                     "paged_attn", "kv_write", "router", "experts",
                     "shared_expert")
SSM_PREFILL_SCOPES = ("ssm_mixer", "ssm_conv", "ssm_scan", "paged_attn")
# what its decode steps' serve/deliver spans carry (chipbench/rooflines/
# ssm_common.py) and its admitting serve/admit spans
STATE_SPAN_ATTRS = ("state_bytes", "ssm_layers", "ctx_attended")

# what the serve/deliver span of a request's FIRST token carries (PR 37;
# chipbench/readers/first_token_spans.py): what its wait was made of
FIRST_TOKEN_ATTRS = ("sid", "prefill_programs", "queue_steps",
                     "wait_steps", "queue_ms", "prefill_ms", "lag_ms",
                     "ttft_ms")

# a train step of a model with dropless expert layers, its experts over
# 'ep' (PR 46): the exchange's scope beside the expert layer's others,
# and the backward kernels by name under `experts`
ROUTED_TRAIN_SCOPES = ("router", "moe_dispatch", "moe_exchange", "experts",
                       "moe_combine", "flash_fwd", "flash_dq", "flash_dkv",
                       "fused_ce")
ROUTED_TRAIN_KERNELS = ("grouped_matmul", "gmm_dx", "gmm_dw")
# what its train/step spans carry while a sink listens
EXPERT_STEP_ATTRS = ("resolved_step", "moe_pairs", "moe_max", "moe_hit",
                     "moe_slots", "moe_layer_steps", "aux_loss",
                     "moe_live_rows", "moe_buffer_rows")

# the spans a traced serve loop / fit has to leave on the host plane
SERVE_SPANS = ("serve/step", "serve/sweep", "serve/admit", "serve/prefill",
               "serve/decode", "serve/deliver", "serve/wait")
TRAIN_SPANS = ("train/step", "train/dispatch", "train/resolve",
               "train/wait", "train/data_wait")


def _model_cfg(**kw):
    return get_preset(
        "llama-tiny", dtype=jnp.float32, num_layers=2, hidden_size=128,
        num_heads=2, num_kv_heads=2, intermediate_size=256, vocab_size=256,
        max_seq_len=256, **kw)


def _latent_model_cfg():
    """A toy of the latent-attention / held-expert family."""
    return get_preset(
        "llama-tiny", dtype=jnp.float32, num_layers=2, hidden_size=64,
        num_heads=2, num_kv_heads=2, intermediate_size=128, vocab_size=256,
        max_seq_len=128, rope_interleaved=True, kv_lora_rank=32,
        q_lora_rank=48, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, first_dense_layers=1, num_experts=4,
        num_experts_per_tok=2, moe_intermediate_size=32,
        moe_scoring="sigmoid", moe_n_group=2, moe_topk_group=1,
        moe_route_scale=2.5, moe_shared_experts=1, moe_router_width=8,
        moe_first_expert=2, moe_dispatch="grouped")


def _routed_model_cfg():
    """A toy of the mellum family (sliding and YaRN full layers 3:1 under
    softmax-routed experts on the dropless path), one expert a device."""
    import types

    from torchacc_tpu.models.hf import config_from_hf
    n = len(jax.devices())
    pub = dict(
        model_type="mellum", hidden_size=64, intermediate_size=128,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        vocab_size=256, num_hidden_layers=4, rms_norm_eps=1e-6,
        layer_types=["sliding_attention"] * 3 + ["full_attention"],
        mlp_layer_types=["sparse"] * 4, sliding_window=8,
        num_experts=n, num_experts_per_tok=2, moe_intermediate_size=32,
        norm_topk_prob=True, tie_word_embeddings=False,
        rope_parameters={
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000},
            "full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                               "factor": 16, "beta_fast": 32, "beta_slow": 1,
                               "original_max_position_embeddings": 16,
                               "attention_factor": 1.2772588722239782}})
    return config_from_hf(types.SimpleNamespace(**pub), max_seq_len=32,
                          dtype=jnp.float32)


def _routed_trainer():
    cfg = ta.Config()
    cfg.compute.attention_impl = "pallas"
    cfg.dist.ep.size = len(jax.devices())
    trainer, _ = accelerate(_routed_model_cfg(), None, cfg,
                            optimizer=optax.adamw(1e-3))
    return trainer


def _sparse_model():
    """``(ModelConfig, params)`` of a toy of the family of two latent
    layer kinds: one dense full layer, then one period (full, sliding);
    indexer of 2 heads choosing 6 positions, a window of 5.  Its weights
    and their layout are the benchmark's (the module's own init refuses
    the family: it runs through ServeEngine alone)."""
    import types

    from chipbench.layouts import mla_sparse_window_moe_decoder as layout
    from chipbench.weights import mla_sparse_window_moe_decoder as weights
    from torchacc_tpu.models.hf import config_from_hf
    pub = dict(
        model_type="dots3_note", hidden_size=64, intermediate_size=128,
        num_attention_heads=2, num_key_value_heads=2, vocab_size=256,
        kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, swa_num_attention_heads=2,
        swa_num_key_value_heads=2, swa_kv_lora_rank=40, swa_q_lora_rank=48,
        swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8, swa_v_head_dim=16,
        swa_rope_theta=500, swa_attention_gate_type="headwise",
        attention_gate_type="headwise", apply_mla_qkv_lora_rescale=True,
        index_head_dim=16, index_n_heads=2, index_topk=6,
        sliding_window_size=5, first_k_dense_replace=1,
        moe_intermediate_size=32, moe_layer_freq=1, n_routed_experts=4,
        n_shared_experts=1, num_experts_per_tok=2, norm_topk_prob=True,
        routed_scaling_factor=1, scoring_func="sigmoid",
        topk_method="noaux_tc", hidden_act="silu", rms_norm_eps=1e-5,
        rope_theta=10000, rope_scaling=None, max_position_embeddings=128,
        num_hidden_layers=3, attention_bias=False,
        layer_types=["full_attention", "full_attention",
                     "sliding_attention"], tie_word_embeddings=False)
    mc = config_from_hf(types.SimpleNamespace(**pub), max_seq_len=128,
                        dtype=jnp.float32, param_dtype=jnp.float32)
    return mc, layout.to_program_params(
        weights.make(weights.base_key(7), pub, 3, jnp.float32), mc)


def _window_model():
    """``(ModelConfig, params)`` of a toy of the grouped-query family of
    two layer kinds: a dense sliding layer, then one period (sliding,
    global); a window of 5.  Weights and layout are the benchmark's."""
    import types

    from chipbench.layouts import gqa_window_moe_decoder as layout
    from chipbench.weights import gqa_window_moe_decoder as weights
    from torchacc_tpu.models.hf import config_from_hf
    pub = dict(
        model_type="exaone_moe", hidden_size=64, intermediate_size=128,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        vocab_size=256, first_k_dense_replace=1, hidden_act="silu",
        layer_types=["sliding_attention", "sliding_attention",
                     "full_attention"],
        mlp_layer_types=["dense", "sparse", "sparse"],
        max_position_embeddings=128, moe_intermediate_size=32, n_group=1,
        topk_group=1, norm_topk_prob=True, num_experts=4,
        num_experts_per_tok=2, num_hidden_layers=3, num_shared_experts=1,
        rms_norm_eps=1e-5,
        rope_parameters={"rope_theta": 10000, "rope_type": "default"},
        routed_scaling_factor=2.5, scoring_func="sigmoid", sliding_window=5,
        tie_word_embeddings=False)
    mc = config_from_hf(types.SimpleNamespace(**pub), max_seq_len=128,
                        dtype=jnp.float32, param_dtype=jnp.float32)
    return mc, layout.to_program_params(
        weights.make(weights.base_key(7), pub, 3, jnp.float32), mc)


def _ssm_model():
    """``(ModelConfig, params)`` of a toy of the single-mixer family:
    ``MEM*E`` — state-space, expert and attention layers, one mixer
    each; weights and layout are the benchmark's (the module's forward
    refuses the family)."""
    import types

    from chipbench.layouts import ssm_attn_moe_decoder as layout
    from chipbench.weights import ssm_attn_moe_decoder as weights
    from torchacc_tpu.models.hf import config_from_hf
    pub = dict(
        model_type="nemotron_h", hidden_size=64, intermediate_size=32,
        num_attention_heads=2, num_key_value_heads=2, head_dim=16,
        vocab_size=256, hybrid_override_pattern="MEM*E",
        num_hidden_layers=5, mamba_num_heads=4, mamba_head_dim=8,
        n_groups=2, ssm_state_size=16, conv_kernel=4, chunk_size=8,
        layer_norm_epsilon=1e-5, mamba_hidden_act="silu",
        mlp_hidden_act="relu2", mamba_proj_bias=False, use_conv_bias=True,
        use_bias=False, attention_bias=False, mlp_bias=False,
        moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
        n_routed_experts=4, n_shared_experts=1, num_experts_per_tok=2,
        n_group=1, topk_group=1, norm_topk_prob=True,
        routed_scaling_factor=2.5, tie_word_embeddings=False,
        time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4)
    mc = config_from_hf(types.SimpleNamespace(**pub), max_seq_len=128,
                        dtype=jnp.float32, param_dtype=jnp.float32)
    return mc, layout.to_program_params(
        weights.make(weights.base_key(7), pub, 5, jnp.float32), mc)


def _scopes_in(hlo_text):
    """Every registered scope named by some op_name of a compiled
    program (a transform wraps the first name under it:
    ``jvp(fused_ce)``)."""
    found = set()
    for path in set(re.findall(r'op_name="([^"]+)"', hlo_text)):
        for part in path.split("/"):
            name = re.sub(r"^(?:[\w.\-]+\()+|\)+$", "", part)
            if name in tracing.DEVICE_SCOPES:
                found.add(name)
    return found


@pytest.fixture(scope="module")
def program_scopes():
    """Scopes in the compiled toy programs: the train step with the
    Pallas flash kernels (interpret mode) and fused CE, and the paged
    decoder's decode and prefill programs."""
    cfg = ta.Config()
    cfg.compute.attention_impl = "pallas"
    trainer, _ = accelerate(_model_cfg(), None, cfg,
                            optimizer=optax.adamw(1e-3))
    batch = {"input_ids": jnp.zeros((len(jax.devices()), 256), jnp.int32)}
    shardings = trainer._batch_shardings(batch)
    batch = {k: jax.device_put(v, shardings[k]) for k, v in batch.items()}
    trainer.init()
    trainer._ensure_compiled(batch)
    assert trainer._use_fused_ce
    with jax.sharding.set_mesh(trainer.mesh):
        train = trainer._train_step.lower(trainer.state, batch) \
            .compile().as_text()

    rtrainer = _routed_trainer()
    rbatch = {"input_ids": jnp.zeros((len(jax.devices()), 32), jnp.int32)}
    shardings = rtrainer._batch_shardings(rbatch)
    rbatch = {k: jax.device_put(v, shardings[k]) for k, v in rbatch.items()}
    rtrainer.init()
    rtrainer._ensure_compiled(rbatch)
    with jax.sharding.set_mesh(rtrainer.mesh):
        routed_train = rtrainer._train_step.lower(rtrainer.state, rbatch) \
            .compile().as_text()

    from torchacc_tpu.serve.scheduler import PagedDecoder
    mc = _model_cfg()
    sc = ta.config.ServeConfig(block_size=8, num_blocks=16, max_slots=2,
                               prefill_chunk=8)
    decoder = PagedDecoder(mc, sc, "xla")
    params = jax.eval_shape(
        lambda: TransformerLM(mc).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    from torchacc_tpu.serve import make_pools
    pools = jax.eval_shape(lambda: make_pools(mc, sc))
    sds = jax.ShapeDtypeStruct
    carry = {"tok": sds((2,), jnp.int32), "key": sds((2, 2), jnp.uint32)}
    # the steps' addressing pytree: every slot's table rows, one row
    tables, row = sds((2, 15), jnp.int32), sds((15,), jnp.int32)
    decode = decoder._decode.lower(
        params, pools, carry, {"blocks": tables},
        sds((2,), jnp.int32), sds((2,), jnp.bool_), sds((2,), jnp.float32),
        sds((2,), jnp.int32), sds((2,), jnp.float32), False
    ).compile().as_text()
    i32 = sds((), jnp.int32)
    prefill = decoder._prefill.lower(
        params, pools, {"blocks": row}, i32,
        sds((8,), jnp.int32), i32, True).compile().as_text()
    lmc = _latent_model_cfg()
    ldecoder = PagedDecoder(lmc, sc, "xla")
    lparams = jax.eval_shape(
        lambda: TransformerLM(lmc).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    latent = ldecoder._decode.lower(
        lparams, jax.eval_shape(lambda: make_pools(lmc, sc)), carry,
        {"blocks": tables}, sds((2,), jnp.int32), sds((2,), jnp.bool_),
        sds((2,), jnp.float32), sds((2,), jnp.int32),
        sds((2,), jnp.float32), True).compile().as_text()
    smc, sparams = _sparse_model()
    sparse_lowered = PagedDecoder(smc, sc, "xla")._decode.lower(
        jax.eval_shape(lambda: sparams),
        jax.eval_shape(lambda: make_pools(smc, sc)), carry,
        {"blocks": tables, "window": tables}, sds((2,), jnp.int32),
        sds((2,), jnp.bool_), sds((2,), jnp.float32), sds((2,), jnp.int32),
        sds((2,), jnp.float32), True)
    sparse = sparse_lowered.compile().as_text()
    wmc, wparams = _window_model()
    window = PagedDecoder(wmc, sc, "xla")._decode.lower(
        jax.eval_shape(lambda: wparams),
        jax.eval_shape(lambda: make_pools(wmc, sc)), carry,
        {"blocks": tables, "window": tables}, sds((2,), jnp.int32),
        sds((2,), jnp.bool_), sds((2,), jnp.float32), sds((2,), jnp.int32),
        sds((2,), jnp.float32), True
    ).compile().as_text()
    mmc, mparams = _ssm_model()
    mdecoder = PagedDecoder(mmc, sc, "xla")
    mpools = jax.eval_shape(lambda: make_pools(mmc, sc))
    ssm_decode = mdecoder._decode.lower(
        jax.eval_shape(lambda: mparams), mpools, carry,
        {"blocks": tables}, sds((2,), jnp.int32), sds((2,), jnp.bool_),
        sds((2,), jnp.float32), sds((2,), jnp.int32),
        sds((2,), jnp.float32), True).compile().as_text()
    ssm_prefill = mdecoder._prefill.lower(
        jax.eval_shape(lambda: mparams), mpools,
        {"blocks": row, "slot": i32}, i32,
        sds((8,), jnp.int32), i32, True).compile().as_text()
    return {"train": _scopes_in(train), "decode": _scopes_in(decode),
            "routed_train": _scopes_in(routed_train),
            # the kernels by the names the traced ops carry under the
            # `experts` scope: .../experts/gmm_dw/...
            "routed_train_kernels": set(re.findall(
                r'op_name="[^"]*/experts/(\w+)/', routed_train)),
            "ssm_decode": _scopes_in(ssm_decode),
            "ssm_prefill": _scopes_in(ssm_prefill),
            "prefill": _scopes_in(prefill),
            "latent_decode": _scopes_in(latent),
            "sparse_decode": _scopes_in(sparse),
            "window_decode": _scopes_in(window),
            # read before the compile: the persistent compile cache's
            # key leaves op names out, so a scope moved with no change
            # to the computation comes back under its old names
            "sparse_decode_scatters": set(re.findall(
                r'loc\("([^"]+/scatter)"',
                sparse_lowered.as_text(debug_info=True)))}


@pytest.mark.parametrize("program,scope", [
    *(("train", s) for s in TRAIN_SCOPES),
    *(("routed_train", s) for s in ROUTED_TRAIN_SCOPES),
    *(("decode", s) for s in DECODE_SCOPES),
    *(("prefill", s) for s in PREFILL_SCOPES),
    *(("latent_decode", s) for s in LATENT_DECODE_SCOPES),
    *(("sparse_decode", s) for s in SPARSE_DECODE_SCOPES),
    *(("window_decode", s) for s in WINDOW_DECODE_SCOPES),
    *(("ssm_decode", s) for s in SSM_DECODE_SCOPES),
    *(("ssm_prefill", s) for s in SSM_PREFILL_SCOPES)])
def test_device_scope_in_compiled_program(program_scopes, program, scope):
    assert scope in tracing.DEVICE_SCOPES
    assert scope in program_scopes[program]


@pytest.mark.parametrize("kernel", ROUTED_TRAIN_KERNELS)
def test_grouped_matmul_kernels_are_told_apart_by_name(program_scopes,
                                                       kernel):
    """``expert_matmul_train_roofline`` reads the scope ``experts``; a
    trace tells the forward product from dX and from dW by the kernel's
    own name under it."""
    assert kernel in program_scopes["routed_train_kernels"]


def test_every_pool_write_of_the_sparse_program_is_a_kv_write(
        program_scopes):
    """``kv_pool_time_pct.serve`` reads the scope ``kv_write``: the
    index key's scatter into its pool lies under it like the latent
    rows' (``index_write`` keeps the key's projection, norm and rope)."""
    writes = {n for n in program_scopes["sparse_decode_scatters"]
              if "kv_write" in n or "index_write" in n}
    assert writes and all("kv_write" in n for n in writes), writes


def test_every_registered_scope_is_placed():
    placed = (set(TRAIN_SCOPES) | set(ROUTED_TRAIN_SCOPES)
              | set(DECODE_SCOPES) | set(PREFILL_SCOPES)
              | set(LATENT_DECODE_SCOPES) | set(SPARSE_DECODE_SCOPES)
              | set(WINDOW_DECODE_SCOPES) | set(SSM_DECODE_SCOPES)
              | set(SSM_PREFILL_SCOPES))
    assert placed == set(tracing.DEVICE_SCOPES)


# -- host spans in an open profiler trace -------------------------------------

def _host_events(trace_dir):
    """(name, start_ns, end_ns) of every /host:CPU event named in the
    span registry, from the one xplane under ``trace_dir``."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in tracing.SPAN_NAMES:
                    events.append((e.name, e.start_ns,
                                   e.start_ns + e.duration_ns,
                                   dict(e.stats)))
    return events


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One profiler trace over a toy serve loop and a toy fit, with the
    ring OFF: what lands in the trace came through the profiler sink."""
    from torchacc_tpu.serve import Request, ServeEngine
    mc = get_preset(
        "llama-tiny", dtype=jnp.float32, num_layers=2, hidden_size=64,
        num_heads=4, num_kv_heads=2, intermediate_size=128,
        vocab_size=257, max_seq_len=128)
    model = TransformerLM(mc)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    engine = ServeEngine(model, params, ta.Config(
        serve=ta.config.ServeConfig(block_size=8, num_blocks=64,
                                    max_slots=4, prefill_chunk=8,
                                    decode_depth=2)))
    rng = np.random.default_rng(0)
    reqs = [Request(prompt_ids=rng.integers(1, 257, size=n).tolist(),
                    max_new_tokens=4) for n in (5, 9)]
    engine.generate(reqs[:1])              # compile outside the trace

    tmc = get_preset("llama-tiny", vocab_size=64, hidden_size=32,
                     num_layers=1, num_heads=2, num_kv_heads=2,
                     intermediate_size=64, dtype=jnp.float32)
    trainer, _ = accelerate(tmc, None, ta.Config(),
                            optimizer=optax.adam(1e-3))
    batches = [{"input_ids": rng.integers(0, 64, size=(8, 16))
                .astype(np.int32)} for _ in range(3)]
    trainer.fit(batches[:1], log_every=1)  # compile outside the trace

    rtrainer = _routed_trainer()
    rbatches = [{"input_ids": rng.integers(
        0, 256, size=(len(jax.devices()), 32)).astype(np.int32)}
        for _ in range(3)]
    rtrainer.fit(rbatches[:1], log_every=1)  # compile outside the trace

    lmodel = TransformerLM(_latent_model_cfg())
    lengine = ServeEngine(
        lmodel, lmodel.init(jax.random.PRNGKey(1),
                            jnp.zeros((1, 8), jnp.int32))["params"],
        ta.Config(serve=ta.config.ServeConfig(
            block_size=8, num_blocks=64, max_slots=2, prefill_chunk=8)))
    lreqs = [Request(prompt_ids=rng.integers(1, 256, size=n).tolist(),
                     max_new_tokens=3) for n in (5, 19)]
    lengine.generate(lreqs[:1])            # compile outside the trace

    smc, sparams = _sparse_model()
    sengine = ServeEngine(TransformerLM(smc), sparams, ta.Config(
        serve=ta.config.ServeConfig(block_size=4, num_blocks=64,
                                    max_slots=2, prefill_chunk=8)))
    sreqs = [Request(prompt_ids=rng.integers(1, 256, size=n).tolist(),
                     max_new_tokens=3) for n in (5, 19)]
    sengine.generate(sreqs[:1])            # compile outside the trace

    wmc, wparams = _window_model()
    wengine = ServeEngine(TransformerLM(wmc), wparams, ta.Config(
        serve=ta.config.ServeConfig(block_size=4, num_blocks=64,
                                    max_slots=2, prefill_chunk=8)))
    wreqs = [Request(prompt_ids=rng.integers(1, 256, size=n).tolist(),
                     max_new_tokens=3) for n in (5, 19)]
    wengine.generate(wreqs[:1])            # compile outside the trace

    mmc, mparams = _ssm_model()
    mengine = ServeEngine(TransformerLM(mmc), mparams, ta.Config(
        serve=ta.config.ServeConfig(block_size=4, num_blocks=64,
                                    max_slots=2, prefill_chunk=8)))
    mreqs = [Request(prompt_ids=rng.integers(1, 256, size=n).tolist(),
                     max_new_tokens=3) for n in (5, 19)]
    mengine.generate(mreqs[:1])            # compile outside the trace

    assert not tracing.enabled()
    tracing.clear()
    trace_dir = str(tmp_path_factory.mktemp("timeline"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        engine.generate(reqs)
        trainer.fit(batches, log_every=1)
        rtrainer.fit(rbatches, log_every=1)
        lengine.generate(lreqs)
        sengine.generate(sreqs)
        wengine.generate(wreqs)
        mengine.generate(mreqs)
    finally:
        jax.profiler.stop_trace()
    mengine.close()
    engine.close()
    lengine.close()
    sengine.close()
    wengine.close()
    return {"events": _host_events(trace_dir),
            "ring": tracing.snapshot()}


@pytest.mark.parametrize("span", SERVE_SPANS + TRAIN_SPANS)
def test_span_on_the_profilers_host_plane(traced, span):
    assert span in tracing.SPAN_NAMES
    assert any(name == span for name, *_ in traced["events"])


@pytest.mark.parametrize("attr", EXPERT_STEP_ATTRS)
def test_train_step_span_carries_the_expert_layers_load(traced, attr):
    """``expert_load_max_over_mean.train`` and
    ``expert_matmul_train_roofline`` read them
    (chipbench/readers/expert_load.py): every pair is on some shard's
    held expert, so a resolved step counts rows x top-k pairs a layer."""
    spans = [st for name, _, _, st in traced["events"]
             if name == "train/step" and "moe_pairs" in st]
    assert spans and all(attr in st for st in spans)
    n = len(jax.devices())
    for st in spans:
        assert int(st["moe_layer_steps"]) == 4
        assert int(st["moe_slots"]) == 4 * n
        assert int(st["moe_pairs"]) == 4 * n * 32 * 2
        assert int(st["moe_max"]) * n >= int(st["moe_pairs"])
        # the busiest shard's sorted buffers (PR 47): sized for every
        # pair of the rows it saw, live in its own experts' pairs — at
        # least the mean shard's, at most all
        assert int(st["moe_buffer_rows"]) == int(st["moe_pairs"])
        assert (int(st["moe_pairs"]) <= int(st["moe_live_rows"]) * n
                and int(st["moe_live_rows"]) <= int(st["moe_buffer_rows"]))


def _covered(events, child, parent):
    parents = [(a, b) for n, a, b, _ in events if n == parent]
    return all(any(pa <= a and b <= pb for pa, pb in parents)
               for n, a, b, _ in events if n == child)


@pytest.mark.parametrize("child,parent", [
    ("serve/decode", "serve/step"), ("serve/prefill", "serve/step"),
    ("serve/admit", "serve/step"), ("serve/deliver", "serve/step"),
    ("serve/wait", "serve/deliver"), ("train/dispatch", "train/step")])
def test_parent_span_covers_child(traced, child, parent):
    assert _covered(traced["events"], child, parent)


def test_profiler_sink_carries_scalars_and_leaves_the_ring_off(traced):
    assert traced["ring"] == []            # ObsConfig gates the ring only
    admits = [st for n, _, _, st in traced["events"] if n == "serve/admit"]
    assert any(str(st.get("admitted")) == "1" and "queue_ms" in st
               for st in admits)
    decodes = [st for n, _, _, st in traced["events"] if n == "serve/decode"]
    assert decodes and all("slots" in st and "traces" not in st
                           for st in decodes)


@pytest.mark.parametrize("attr", MOE_SPAN_ATTRS)
def test_deliver_spans_carry_the_expert_layers_counts(traced, attr):
    """An expert model's serve/deliver spans reach the profiler with
    the counts of the steps behind them (set after the token fetch, on
    the open annotation); a dense model's carry none."""
    # (the toy of two latent layer kinds has expert layers too: its
    # spans are read by the next test)
    with_counts = [st for n, _, _, st in traced["events"]
                   if n == "serve/deliver" and "moe_pairs" in st
                   and "sel_cached" not in st and "ctx_attended" not in st
                   and "ssm_layers" not in st]
    without = [st for n, _, _, st in traced["events"]
               if n == "serve/deliver" and "moe_pairs" not in st]
    assert with_counts and without
    assert all(attr in st for st in with_counts)
    # 2 held experts of 8, one expert layer: a decode step of <= 2 slots
    # x 2 picks puts at most 4 pairs here; a 'first' entry brings every
    # prefill chunk of its prompt (19 tokens: 3 chunks)
    assert all(0 <= int(st["moe_hit"]) <= int(st["moe_slots"])
               and int(st["moe_max"]) <= int(st["moe_pairs"])
               for st in with_counts)
    assert max(int(st["moe_layer_steps"]) for st in with_counts) == 3
    assert sum(int(st["moe_pairs"]) for st in with_counts) > 0


@pytest.mark.parametrize("attr", SELECT_SPAN_ATTRS + ADMIT_BLOCK_ATTRS)
def test_spans_carry_the_selection_and_the_blocks_by_kind(traced, attr):
    """A model of two latent layer kinds: its serve/deliver spans carry
    the positions a full layer attended and had cached and the positions
    a window layer attended, for the queries behind the tokens (a 'first'
    entry: its whole prompt's; a decode step: one query a slot); its
    admitting serve/admit spans the blocks in use by kind and the window
    blocks given back.  Other models' spans carry none of them."""
    spans = "serve/admit" if attr in ADMIT_BLOCK_ATTRS else "serve/deliver"
    key = "blocks_window" if attr in ADMIT_BLOCK_ATTRS else "sel_cached"
    have = [st for n, _, _, st in traced["events"]
            if n == spans and key in st]
    rest = [st for n, _, _, st in traced["events"]
            if n == spans and key not in st]
    assert have and rest and all(attr in st for st in have)
    if attr in ADMIT_BLOCK_ATTRS:
        return
    # index_topk 6, window 5: a prompt of 19 tokens attends
    # 1 + 2 + .. + 6 + 13 x 6 = 99 of 190 cached positions on a full
    # layer and 1 + .. + 5 + 14 x 5 = 85 on a window layer
    firsts = [st for st in have if str(st.get("kind")) == "first"]
    assert ["99", "190", "85"] in [[str(st[a]) for a in SELECT_SPAN_ATTRS]
                                    for st in firsts]
    assert all(int(st["sel_attended"]) <= int(st["sel_cached"])
               and int(st["win_attended"]) <= int(st["sel_cached"])
               for st in have)


@pytest.mark.parametrize("attr", WINDOW_SPAN_ATTRS + MOE_SPAN_ATTRS)
def test_spans_carry_what_each_kind_of_grouped_query_layer_attended(
        traced, attr):
    """A grouped-query model of sliding and global layers: its
    serve/deliver spans carry the positions a global layer attended
    (all that were cached) and a sliding layer attended, for the queries
    behind the tokens, beside the expert layers' counts; no selection's
    counts.  Its admitting serve/admit spans carry the blocks by kind."""
    have = [st for n, _, _, st in traced["events"]
            if n == "serve/deliver" and "ctx_attended" in st
            and "state_bytes" not in st]
    assert have and all(attr in st and "sel_cached" not in st
                        for st in have)
    # a window of 5: a prompt of 19 tokens attends 1 + .. + 19 = 190
    # positions on a global layer and 1 + .. + 5 + 14 x 5 = 85 on a
    # sliding one
    firsts = [st for st in have if str(st.get("kind")) == "first"]
    assert ["190", "85"] in [[str(st[a]) for a in WINDOW_SPAN_ATTRS]
                             for st in firsts]
    assert all(int(st["win_attended"]) <= int(st["ctx_attended"])
               for st in have)
    admits = [st for n, _, _, st in traced["events"]
              if n == "serve/admit" and "blocks_window" in st]
    assert len(admits) >= 4                # this model's and the latent one's


@pytest.mark.parametrize("attr", STATE_SPAN_ATTRS + MOE_SPAN_ATTRS)
def test_spans_carry_the_state_a_decode_step_moved(traced, attr):
    """A model with state-space layers: its decode steps' serve/deliver
    spans carry the recurrent-state bytes the step read and wrote (every
    decoding slot's, once a state-space layer: 2 layers x (a float32
    state of 4 x 8 x 16 and 3 rows of 96 float32 channels) x 2), the
    state-space layers and the positions an attention layer attended,
    beside the expert layers' counts; a request's admission says that
    the slot's state restarts."""
    have = [st for n, _, _, st in traced["events"]
            if n == "serve/deliver" and "state_bytes" in st]
    firsts = [st for n, _, _, st in traced["events"]
              if n == "serve/deliver" and "ssm_layers" in st
              and "state_bytes" not in st]
    assert len(firsts) == 2 and all(str(st["kind"]) == "first"
                                    for st in firsts)
    assert have and all(attr in st and "win_attended" not in st
                        and str(st["kind"]) == "decode" for st in have)
    a_slot = 2 * 2 * (4 * 4 * 8 * 16 + 3 * 96 * 4)
    assert {int(st["state_bytes"]) for st in have} <= {a_slot, 2 * a_slot}
    assert all(int(st["ssm_layers"]) == 2 and int(st["moe_layer_steps"]) == 2
               and int(st["ctx_attended"]) > 0 for st in have)
    resets = [st for n, _, _, st in traced["events"]
              if n == "serve/admit" and "state_reset" in st]
    assert len(resets) == 2 and all(int(st["state_reset"]) == 1
                                    for st in resets)


@pytest.mark.parametrize("attr", FIRST_TOKEN_ATTRS)
def test_first_tokens_deliver_span_says_what_the_wait_was_made_of(
        traced, attr):
    """Every model's serve/deliver span of kind 'first' reaches the
    profiler with the request's way to its first token (set after the
    token is recorded, on the open annotation); a 'decode' one carries
    none of it.  tests/test_first_token_path.py reads the ring sink."""
    delivers = [st for n, _, _, st in traced["events"]
                if n == "serve/deliver"]
    firsts = [st for st in delivers if str(st.get("kind")) == "first"]
    decodes = [st for st in delivers if str(st.get("kind")) == "decode"]
    assert len(firsts) == 10 and decodes   # five engines, two prompts each
    assert all(attr in st for st in firsts)
    assert not any(attr in st for st in decodes)
    # chunks of 8: a prompt of 5 tokens is one program, of 9 two, of 19
    # three (prompts of 5 + 9 once, of 5 + 19 four times)
    assert sorted(int(st["prefill_programs"]) for st in firsts) == \
        [1, 1, 1, 1, 1, 2, 3, 3, 3, 3]
    for st in firsts:
        assert int(st["wait_steps"]) >= int(st["prefill_programs"]) \
            + int(st["queue_steps"])
        assert float(st["queue_ms"]) + float(st["prefill_ms"]) \
            + float(st["lag_ms"]) == pytest.approx(float(st["ttft_ms"]),
                                                   abs=1e-3)


def test_a_window_model_queues_one_step_for_its_reservation():
    """What ``queue_steps`` is for: a closed loop whose clients equal the
    slots never waits for a SLOT, yet with window layers every request
    behind the first ones is admitted one step late — the window pool
    holds exactly slots x bound blocks and a finished sequence's
    reservation comes back with its deferred blocks an iteration on
    (PERF.md section 7).  ``queue_wait_s`` alone reads that as a few
    milliseconds of queue; counted in steps it is exactly one."""
    from torchacc_tpu.serve import Request, ServeEngine
    wmc, wparams = _window_model()
    engine = ServeEngine(TransformerLM(wmc), wparams, ta.Config(
        serve=ta.config.ServeConfig(block_size=4, num_blocks=64,
                                    max_slots=2, prefill_chunk=8)))
    rng = np.random.default_rng(0)
    todo = [Request(prompt_ids=rng.integers(1, 256, size=n).tolist(),
                    max_new_tokens=3) for n in (5, 19, 12, 9, 22, 7)]
    live = {engine.submit(todo.pop(0)) for _ in range(2)}
    results = {}
    while live:
        engine.step()
        for rid in [r for r in live if engine._all[r].finished]:
            live.remove(rid)
            results[rid] = engine.result(rid, pop=True)
            if todo:
                live.add(engine.submit(todo.pop(0)))
    engine.close()
    assert [results[rid].queue_steps for rid in range(6)] == \
        [0, 0, 1, 1, 1, 1]
    assert all(r.wait_steps >= r.queue_steps + r.prefill_programs
               for r in results.values())


# -- the idle path and the ring ------------------------------------------------

def test_span_is_the_shared_noop_with_both_sinks_idle():
    assert not tracing.enabled()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert tracing.span("serve/decode", iter=1) is tracing._NULL
    assert tracing.span("train/step") is tracing._NULL
    with tracing.span("serve/admit") as sp:
        sp.set(admitted=0)
        sp.discard()                       # the no-op takes both calls
    assert tracing.snapshot() == []


def test_discarded_span_stays_out_of_the_ring():
    tracing.configure(enabled=True)
    try:
        tracing.clear()
        with tracing.span("serve/admit", sid=1) as sp:
            sp.discard()
            with tracing.span("serve/prefill") as inner:
                pass
        with tracing.span("serve/admit", sid=2):
            pass
        names = [(s["name"], s["attrs"].get("sid"))
                 for s in tracing.snapshot()]
        assert names == [("serve/prefill", None), ("serve/admit", 2)]
        assert inner.parent is None        # a discarded span is no parent
    finally:
        tracing.configure(enabled=False)
        tracing.clear()


# -- one registry ----------------------------------------------------------------

def _doc_table(heading):
    text = open(os.path.join(ROOT, "docs", "observability.md")).read()
    section = text.split(heading, 1)[1].split("\n##", 1)[0]
    return re.findall(r"^\| `([^`]+)` \|", section, flags=re.M)


def test_docs_span_table_is_the_registry():
    assert sorted(_doc_table("### Span registry")) == \
        sorted(tracing.SPAN_NAMES)


def test_docs_scope_table_is_the_registry():
    assert sorted(_doc_table("### Device scope registry")) == \
        sorted(tracing.DEVICE_SCOPES)


def test_every_span_name_used_in_the_package_is_registered():
    used = set()
    for path in glob.glob(os.path.join(ROOT, "torchacc_tpu", "**", "*.py"),
                          recursive=True):
        if path.endswith(os.path.join("obs", "tracing.py")):
            continue                       # its docstrings show the call
        used |= set(re.findall(
            r'(?:tracing\.|\b)(?:span|record_span)\(\s*"([^"]+)"',
            open(path).read()))
    assert used == set(tracing.SPAN_NAMES)


# -- the seams the on-chip benchmark reads (chipbench/, PERF.md section 7) ----

def test_benchmark_seams_keep_their_names():
    """``chipbench/`` reads these privates and a later PR may not edit
    it: a rename has to fail here first."""
    from torchacc_tpu.serve import ServeEngine
    from torchacc_tpu.serve.scheduler import PagedDecoder, Scheduler
    from torchacc_tpu.train.trainer import Trainer

    assert callable(Trainer._batch_shardings)
    assert callable(Trainer._build_train_step)
    trainer, _ = accelerate(
        get_preset("llama-tiny", vocab_size=64, hidden_size=32,
                   num_layers=1, num_heads=2, num_kv_heads=2,
                   intermediate_size=64, dtype=jnp.float32),
        None, ta.Config(), optimizer=optax.adam(1e-3))
    assert hasattr(trainer, "_train_step") and hasattr(trainer, "blocked")
    assert hasattr(trainer, "state_shardings")

    mc = get_preset(
        "llama-tiny", dtype=jnp.float32, num_layers=1, hidden_size=64,
        num_heads=4, num_kv_heads=2, intermediate_size=128,
        vocab_size=257, max_seq_len=64)
    model = TransformerLM(mc)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    engine = ServeEngine(model, params, ta.Config(
        serve=ta.config.ServeConfig(block_size=8, num_blocks=16,
                                    max_slots=2, prefill_chunk=8)))
    sched = engine.scheduler
    assert isinstance(sched, Scheduler)
    assert isinstance(sched.decoder, PagedDecoder)
    assert sched.decoder.impl in ("pallas", "xla")
    assert len(sched.slot_seq) == 2 and sched._iter == 0
    assert sched._step_idx == 0            # counts step() calls (PR 37)
    from torchacc_tpu.serve.scheduler import Sequence
    seq = Sequence(sid=0, prompt=np.zeros((3,), np.int32), max_new=1)
    # the stamps the first token's serve/deliver attributes come from
    for name in ("step_submit", "step_admit", "step_first",
                 "t_first_dispatch", "prefill_programs", "queue_steps",
                 "wait_steps", "queue_s", "prefill_s",
                 "first_token_lag_s", "ttft_s"):
        assert hasattr(seq, name)
    assert sched.seq_lens.shape == (2,) and sched.active.shape == (2,)
    for name in ("_decode", "_prefill"):
        assert hasattr(sched.decoder, name)
    engine.close()
