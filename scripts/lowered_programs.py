"""Lower the model's programs from the checkout in the cwd and write their
text, so that a refactor can show it left them alone (PERF.md section 6,
PR 28; ROADMAP D1b is the next user).

    cd <checkout> && JAX_PLATFORMS=cpu python <this file> <outdir>

once in a copy of the parent commit (``git archive <commit> | tar -x -C
<dir>``) and once in the change, then ``diff -rq <outdir A> <outdir B>``.
Written per program: ``<name>.txt``, ``lowered.as_text()`` with the
process-wide numeric suffixes of private symbols normalised (``@name_N``);
``<name>.scope_paths``, the set of named-scope paths ops sit under, and
``<name>.scopes``, the op-name paths themselves (both from
``as_text(debug_info=True)``, traceback frames left out); for the serve
programs on the XLA path also ``<name>.compiled_ops``, the opcode counts
of the module the CPU backend compiles from it.  Programs:
``Trainer``'s train step on Mistral- and OLMo-2-shaped toys with the
benchmark's ``dense4k`` settings; ``jit(grad(loss))`` over block shapes;
the A.X-K1 toy's forward; ``PagedDecoder._decode/_prefill/_prefill_batch``
on a toy of each served family (Mistral- and A.X-K1-shaped ones from the
module's init; the dots3-, K-EXAONE- and Nemotron-shaped toys their test
files build, parameters in the benchmark's layout).  Beside them
``init_digests.json`` /
``preset_digests.json`` (parameter paths, shapes, dtypes and value sums for
a fixed key: the block shapes, and every preset at toy widths) and
``generate.<shape>.tokens``.  CPU only: it says what the programs are,
never how fast.
"""
import collections
import dataclasses
import json
import os
import re
import sys
import types

sys.path.insert(0, os.getcwd())
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_enable_compilation_cache", False)

import torchacc_tpu as ta  # noqa: E402
from chipbench import program  # noqa: E402
from torchacc_tpu.config import ServeConfig  # noqa: E402
from torchacc_tpu.models import TransformerLM, get_preset  # noqa: E402
from torchacc_tpu.models.hf import config_from_hf  # noqa: E402
from torchacc_tpu.serve.kv_cache import blocks_needed, make_pools  # noqa: E402
from torchacc_tpu.serve.scheduler import PagedDecoder  # noqa: E402
from torchacc_tpu.train.accelerate import apply_config_to_model  # noqa: E402
from torchacc_tpu.train.trainer import Trainer  # noqa: E402

assert os.path.dirname(os.path.dirname(ta.__file__)) == os.getcwd(), ta.__file__
OUT = sys.argv[1]
os.makedirs(OUT, exist_ok=True)

SUFFIX = re.compile(r"@(\w+)_[0-9]+\b")
FILE_LOC = re.compile(r'^(#loc\d*) = loc\("[^"]*":\d+', re.M)
NAME_LOC = re.compile(r'^#loc\d* = loc\("([^"]+)"\((#loc\d*)\)\)', re.M)


def op_names(dbg):
    """Op name paths (scope/.../primitive) of a debug-info module text;
    traceback frames (names whose child is a file location) left out."""
    files = set(FILE_LOC.findall(dbg))
    return sorted({SUFFIX.sub(r"@\1_N", name)
                   for name, child in NAME_LOC.findall(dbg)
                   if child not in files})


def write(name, lowered):
    text = SUFFIX.sub(r"@\1_N", lowered.as_text())
    with open(os.path.join(OUT, name + ".txt"), "w") as f:
        f.write(text)
    dbg = lowered.as_text(debug_info=True)
    scopes = op_names(dbg)
    with open(os.path.join(OUT, name + ".scopes"), "w") as f:
        f.write("\n".join(scopes) + "\n")
    paths = sorted({"/".join(n.split("/")[:i]) for n in scopes
                    for i in range(1, n.count("/") + 1)})
    with open(os.path.join(OUT, name + ".scope_paths"), "w") as f:
        f.write("\n".join(paths) + "\n")
    print(name, len(text), len(scopes), flush=True)


HLO_OP = re.compile(r"^\s*(?:ROOT )?\S+ = \S+ ([a-z][a-z0-9-]*)\(", re.M)


def write_compiled_ops(name, lowered):
    """``<name>.compiled_ops``: how many instructions of each opcode the
    CPU backend's OPTIMIZED module holds — what is left of a difference
    in the lowered text (a ``0 + n * 1`` on a loop counter, the order two
    independent values are computed in) once XLA has simplified it."""
    ops = collections.Counter(HLO_OP.findall(lowered.compile().as_text()))
    with open(os.path.join(OUT, name + ".compiled_ops"), "w") as f:
        f.write("".join(f"{op} {n}\n" for op, n in sorted(ops.items())))


def digest(params):
    return {k: [list(v.shape), str(v.dtype),
                float(np.asarray(v, np.float64).sum()),
                float(np.abs(np.asarray(v, np.float64)).sum())]
            for k, v in program.flat_paths(params).items()}


def load(path):
    with open(path) as f:
        return json.load(f)


TOY_W = dict(hidden_size=128, intermediate_size=256, num_attention_heads=4,
             vocab_size=512)

# -- (a) the train step, through the cells' own path -----------------------
traffic = load("chipbench/traffic/dense4k.json")
for cfg_file, extra in (("mistral-7b-v0.3", dict(num_key_value_heads=2)),
                        ("olmo-2-0425-1b", dict(num_key_value_heads=4))):
    pub = dict(load(f"chipbench/configs/{cfg_file}.json")["published"])
    pub.pop("head_dim", None)
    pub.update(TOY_W, **extra)
    mc = program.model_config(pub, 2, max_seq_len=128,
                              **traffic.get("model_overrides", {}))
    cfg = program.framework_config(traffic["settings"], 0)
    model = TransformerLM(apply_config_to_model(mc, cfg))
    trainer = Trainer(model, cfg,
                      optimizer=program.optimizer(traffic["optimizer"]))
    state = trainer.abstract_state()
    b = {"input_ids": jax.ShapeDtypeStruct((4, 128), jnp.int32)}
    sh = trainer._batch_shardings(b)
    b = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sh[k])
         for k, v in b.items()}
    with jax.sharding.set_mesh(trainer.mesh):
        write(f"train_step.{cfg_file}",
              trainer._build_train_step(b).lower(state, b))

# -- (a') jit(grad(loss)) over block shapes, and initial values -------------
BASE = dict(dtype=jnp.float32, num_layers=2, hidden_size=64, num_heads=4,
            num_kv_heads=2, intermediate_size=128, vocab_size=257,
            max_seq_len=128)
SHAPES = {
    "pre": {},
    "pre_bf16_remat": dict(dtype=jnp.bfloat16, remat=True,
                           remat_policy="save_attn_mlp"),
    "sub_remat": dict(remat=True, remat_cls=("Attention",)),
    "unrolled": dict(scan_layers=False),
    "olmo2": dict(norm_placement="post", qk_norm=True, qk_norm_proj=True),
    "sandwich": dict(sandwich_norms=True, norm="rmsnorm1p",
                     activation="geglu"),
    "phi": dict(parallel_block=True, norm="layernorm", qkv_bias=True,
                o_bias=True, mlp_bias=True, activation="gelu",
                partial_rotary=0.5),
    "neox": dict(parallel_block=True, parallel_block_shared_norm=False,
                 norm="layernorm", activation="gelu_exact", qkv_bias=True,
                 o_bias=True, mlp_bias=True),
    "qwen3": dict(qk_norm=True),
    "relu2": dict(activation="relu2", norm="layernorm1p"),
    "rope_scale": dict(rope_scale=4.0),
    "alibi": dict(pos_emb="alibi"),
    "quant": dict(quant="int8", dtype=jnp.bfloat16),
    "moe_dense": dict(num_experts=4, num_experts_per_tok=2),
    "moe_cap": dict(num_experts=4, num_experts_per_tok=2,
                    moe_capacity_factor=1.25),
}


def grad_program(mc, grad=True):
    model = TransformerLM(mc)
    ids = jnp.zeros((2, 16), jnp.int32)
    variables = jax.eval_shape(
        lambda k: model.init(k, ids), jax.random.PRNGKey(0))
    params = variables["params"]
    rest = {k: v for k, v in variables.items() if k != "params"}

    def loss(p, rest, ids):
        out = model.apply({"params": p, **rest}, ids,
                          mutable=list(rest) + ["intermediates"])
        return jnp.mean(out[0].astype(jnp.float32) ** 2)

    return jax.jit(jax.grad(loss) if grad else loss).lower(
        params, rest, jax.ShapeDtypeStruct(ids.shape, ids.dtype))


digests = {}
for name, kw in SHAPES.items():
    mc = get_preset("llama-tiny", **{**BASE, **kw})
    write(f"grad.{name}", grad_program(mc))
    real = TransformerLM(mc).init(jax.random.PRNGKey(0),
                                  jnp.zeros((1, 8), jnp.int32))["params"]
    digests[name] = digest(real)

# -- the A.X-K1 toy of tests/test_mla_moe.py --------------------------------
AXK1 = dict(
    model_type="axk1", hidden_size=64, intermediate_size=128,
    num_attention_heads=2, num_key_value_heads=2, vocab_size=256,
    kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, first_k_dense_replace=1,
    moe_intermediate_size=32, moe_layer_freq=1, n_routed_experts=16,
    n_shared_experts=1, n_group=4, topk_group=2, num_experts_per_tok=4,
    norm_topk_prob=True, routed_scaling_factor=2.5, scoring_func="sigmoid",
    topk_method="none", hidden_act="silu", rms_norm_eps=1e-6,
    rope_theta=10000, max_position_embeddings=4096, num_hidden_layers=61,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=32, mscale=1,
                      mscale_all_dim=1, original_max_position_embeddings=128,
                      type="yarn"),
    tie_word_embeddings=False)
axk1 = config_from_hf(types.SimpleNamespace(**AXK1), num_layers=3,
                      max_seq_len=256, param_dtype=jnp.bfloat16,
                      dtype=jnp.bfloat16)
write("fwd.axk1", grad_program(axk1, grad=False))

with open(os.path.join(OUT, "init_digests.json"), "w") as f:
    json.dump(digests, f, indent=0, sort_keys=True)

# -- every preset's block at toy widths: paths, shapes, dtypes, values --------
from torchacc_tpu.models import PRESETS  # noqa: E402


presets = {}
for pname in sorted(PRESETS):
    mc = get_preset(pname)
    kv = (4 if mc.num_kv_heads in (None, mc.num_heads)
          else 1 if mc.num_kv_heads == 1 else 2)
    small = dataclasses.replace(
        mc, hidden_size=64, num_heads=4, num_kv_heads=kv,
        head_dim=16 if mc.head_dim else None, intermediate_size=128,
        vocab_size=257, max_seq_len=128,
        num_layers=max(2, len(mc.layer_pattern or ())))
    presets[pname] = digest(TransformerLM(small).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
with open(os.path.join(OUT, "preset_digests.json"), "w") as f:
    json.dump(presets, f, indent=0, sort_keys=True)
print("presets", len(presets), flush=True)

# -- (b) the serve programs: one toy a served family ------------------------
mistral = get_preset("llama-tiny", num_layers=2, hidden_size=128, num_heads=4,
                     num_kv_heads=2, intermediate_size=256, vocab_size=512,
                     max_seq_len=256, dtype=jnp.bfloat16,
                     param_dtype=jnp.bfloat16)
SERVE = dict(block_size=16, num_blocks=64, max_slots=4, prefill_chunk=16,
             prefill_batch=2)


def module_params(mc):
    return jax.eval_shape(
        lambda k: TransformerLM(mc).init(
            k, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))


def test_toy(test_file):
    """``(ModelConfig, abstract params, serve settings)`` of the toy a
    family's test file builds: its published config through
    ``config_from_hf``, its parameters from the benchmark's weights in
    the benchmark's layout (the module's own init refuses these
    families)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        test_file[:-3], os.path.join("tests", test_file))
    toy = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(toy)
    mc = toy.model_config(toy.TOY)
    params = jax.eval_shape(
        lambda k: toy.layout.to_program_params(
            toy.weights.make(k, toy.TOY, toy.DEPTH, jnp.float32), mc),
        toy.weights.base_key(5))
    return mc, params, dict(toy.SERVE, prefill_batch=2)


FAMILIES = [
    ("mistral", mistral, module_params(mistral), SERVE),
    ("axk1", axk1, module_params(axk1), SERVE),
    ("dots3", *test_toy("test_sparse_window_serving.py")),
    ("kexaone", *test_toy("test_window_gqa_serving.py")),
    ("nemotron", *test_toy("test_ssm_serving.py")),
]
for tag, mc, params, serve in FAMILIES:
    for impl in ("xla", "pallas"):
        sc = ServeConfig(**serve)
        dec = PagedDecoder(mc, sc, impl)
        sds = jax.ShapeDtypeStruct
        pools = jax.eval_shape(lambda: make_pools(mc, sc))
        s = sc.max_slots
        mb = min(sc.num_blocks - 1,
                 blocks_needed(mc.max_seq_len + sc.decode_depth,
                               sc.block_size))
        i32, f32 = jnp.int32, jnp.float32
        pb = sc.prefill_batch
        # the steps' addressing pytree: every table by name and, in a
        # prefill of a model that keeps state by slot, the slot
        tables = ["blocks"] + ["window"] * bool(mc.layer_pattern)
        slot = bool(mc.mixer_pattern)

        def addr(*rows, prefill=True):
            return {**{name: sds(rows + (mb,), i32) for name in tables},
                    **({"slot": sds(rows, i32)} if slot and prefill else {})}

        carry = {"tok": sds((s,), i32), "key": sds((s, 2), jnp.uint32)}
        programs = {}
        for greedy in (True, False):
            programs[f"decode.greedy{int(greedy)}"] = dec._decode.lower(
                params, pools, carry, addr(s, prefill=False),
                sds((s,), i32), sds((s,), jnp.bool_), sds((s,), f32),
                sds((s,), i32), sds((s,), f32), greedy)
        for final in (False, True):
            programs[f"prefill.final{int(final)}"] = dec._prefill.lower(
                params, pools, addr(), sds((), i32),
                sds((sc.prefill_chunk,), i32), sds((), i32), final)
        programs["prefill_batch"] = dec._prefill_batch.lower(
            params, pools, addr(pb), sds((pb,), i32),
            sds((pb, sc.prefill_chunk), i32), sds((pb,), i32))
        for name, lowered in programs.items():
            write(f"serve.{tag}.{impl}.{name}", lowered)
            if impl == "xla":
                write_compiled_ops(f"serve.{tag}.{impl}.{name}", lowered)

# -- generate()'s cached programs (the dense-cache branch of Attention) -----
from torchacc_tpu.models.generate import generate  # noqa: E402

for name in ("pre", "olmo2", "neox"):
    mc = get_preset("llama-tiny", **{**BASE, **SHAPES[name]})
    model = TransformerLM(mc)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    ids = jnp.ones((2, 8), jnp.int32)
    out = np.asarray(generate(model, params, ids, max_new_tokens=8))
    with open(os.path.join(OUT, f"generate.{name}.tokens"), "w") as f:
        f.write(json.dumps(out.tolist()))
print("done")
