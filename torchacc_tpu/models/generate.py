"""Autoregressive generation: KV-cache decode (one jitted scan) with a
full-prefix-recompute fallback.

Beyond the reference (TorchAcc is training-only; its accuracy benchmark
shells out to vLLM for inference).  The cached path runs a prefill
forward that banks every layer's rotated k / raw v into the flax
``cache`` collection, then decodes all ``max_new_tokens`` steps inside
ONE ``lax.scan`` under one jit — no per-token host sync, no prefix
recompute; eos handling is pure masking inside the scan.  Ragged
batches decode via LEFT-padded prompts + ``prompt_mask``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp


def _sample(logits, rng, temperature, top_k=0, top_p=1.0):
    """Greedy (temperature 0) or temperature sampling with optional
    top-k / nucleus (top-p) truncation (standard decode controls; the
    reference is training-only and defers generation to vLLM)."""
    if temperature <= 0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    if top_k and 0 < top_k < logits.shape[-1]:
        # k-th largest as the cutoff (O(V log k), not a full sort).
        # top_k >= vocab is a no-op by definition (the k-th largest is
        # the global min, so nothing is truncated) — skip the full-width
        # lax.top_k sort entirely rather than pay O(V log V) to mask
        # nothing.  Serving replays rely on top_k=V and top_k=0 tracing
        # to the SAME program, so the sampled stream cannot drift on
        # the guard.
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        # keep the smallest prefix of descending-prob tokens with
        # cumulative probability > top_p; the argmax is ALWAYS kept
        # (top_p <= 0 must degrade to greedy, not an all--inf row)
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep_sorted = cum - probs < top_p
        keep_sorted = keep_sorted.at[..., 0].set(True)
        kth = jnp.min(jnp.where(keep_sorted, sorted_logits, jnp.inf),
                      axis=-1, keepdims=True)
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return jax.random.categorical(rng, logits)


def _prompt_geometry(prompt_ids, prompt_mask):
    """(positions, row_len, seg) for a (possibly LEFT-padded ragged)
    prompt: real tokens are right-aligned, so row i's token at column j
    sits at position j - pad_len_i, and sampling at column p-1 is every
    row's last real token."""
    b, p = prompt_ids.shape
    if prompt_mask is not None:
        mask = prompt_mask.astype(jnp.int32)
        positions = jnp.clip(jnp.cumsum(mask, axis=1) - 1, 0, None)
        return positions, jnp.sum(mask, axis=1), mask
    return None, jnp.full((b,), p, jnp.int32), None


def _drive_decode(logits, cache, step_fn, prompt_ids, row_len, rng,
                  temperature, max_new, eos_id, top_k, top_p):
    """Shared decode-scan driver for every cached path: sample the first
    token from the prefill logits, scan ``step_fn`` for the rest with
    eos freezing, and return [b, p + max_new] tokens.

    ``step_fn(cache, tok, positions1) -> (next_logits [b, V], cache)``
    is the only per-path piece (single-device flax apply vs the pp
    stage ring)."""
    b, p = prompt_ids.shape
    rng, sub = jax.random.split(rng)
    first = _sample(logits[:, p - 1], sub, temperature, top_k,
                    top_p).astype(jnp.int32)
    done0 = jnp.zeros((b,), jnp.bool_)
    if eos_id is not None:
        done0 = first == eos_id

    def step(carry, pos):
        cache, tok, done, rng = carry
        # per-row TRUE position of the token being decoded: the cache
        # slot index is uniform (pos) but row i has pad_len_i pads, so
        # its rope position is pos - pad_len_i
        positions1 = (row_len + (pos - p))[:, None]
        next_logits, cache = step_fn(cache, tok, positions1)
        rng, sub = jax.random.split(rng)
        nxt = _sample(next_logits, sub, temperature, top_k,
                      top_p).astype(jnp.int32)
        if eos_id is not None:
            nxt = jnp.where(done, jnp.int32(eos_id), nxt)
            done = done | (nxt == eos_id)
        return (cache, nxt, done, rng), nxt

    (_, _, _, _), rest = jax.lax.scan(
        step, (cache, first, done0, rng),
        jnp.arange(p, p + max_new - 1, dtype=jnp.int32))
    # the in-scan done-freezing already pins every token after a row's
    # first eos to eos
    toks = jnp.concatenate([first[:, None], rest.T.astype(jnp.int32)],
                           axis=1)
    return jnp.concatenate([prompt_ids, toks], axis=1)


@functools.partial(jax.jit, static_argnames=("model", "dec_model",
                                             "temperature", "max_new",
                                             "eos_id", "top_k", "top_p"))
def _generate_cached(model, dec_model, params, prompt_ids, prompt_mask,
                     rng, temperature, max_new, eos_id, top_k, top_p):
    positions, row_len, seg = _prompt_geometry(prompt_ids, prompt_mask)
    pre_kwargs = ({} if seg is None
                  else dict(positions=positions, segment_ids=seg))
    # prefill: logits for the whole prompt + per-layer kv cache
    logits, vars_ = model.apply({"params": params}, prompt_ids,
                                mutable=["cache"], **pre_kwargs)

    def step_fn(cache, tok, positions1):
        # ragged masking in decode is driven by the banked 'seg' cache
        # (written at prefill), not a segment_ids argument
        logits1, upd = dec_model.apply(
            {"params": params, "cache": cache}, tok[:, None],
            positions=positions1, mutable=["cache"])
        return logits1[:, 0], upd["cache"]

    return _drive_decode(logits, vars_["cache"], step_fn, prompt_ids,
                         row_len, rng, temperature, max_new, eos_id,
                         top_k, top_p)


def generate(
    model,
    params,
    prompt_ids: jax.Array,
    *,
    max_new_tokens: int = 32,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
    eos_id: Optional[int] = None,
    use_cache: bool = True,
    prompt_mask: Optional[jax.Array] = None,
    top_k: int = 0,
    top_p: float = 1.0,
    param_dtype: Optional[Any] = None,
) -> jax.Array:
    """Decode ``max_new_tokens`` after ``prompt_ids`` [b, p].

    ``use_cache=True`` (default, zoo models): prefill + single-scan
    KV-cache decode — O(n) attention reads, one compile, zero per-token
    host syncs.  ``use_cache=False`` or non-zoo models: full-prefix
    recompute fallback.

    ``prompt_mask`` [b, p] (1 = real token) enables RAGGED batches:
    prompts must be LEFT-padded (real tokens right-aligned, the standard
    decode convention).  Positions and attention masking account for
    each row's padding; outputs keep the [b, p + max_new] layout.
    Requires the model to follow the ``(input_ids, positions,
    segment_ids)`` call convention (zoo models and the custom-model
    protocol do; a bare ``(input_ids) -> logits`` model works only
    without ``prompt_mask``).

    temperature 0 = greedy; ``top_k``/``top_p`` truncate the sampling
    distribution (ignored when greedy); eos_id freezes finished rows at
    eos.

    ``param_dtype`` (e.g. ``jnp.bfloat16``): cast floating params ONCE
    before decoding.  Training keeps f32 master weights, so without the
    cast every decode step re-reads the full f32 param set from HBM;
    bf16 storage halves that traffic — decode is memory-bound, so this
    is ~the standard serving-precision speedup.  Applied before every
    dispatch (pp stage-ring, layer_pattern, cp, recompute) so all decode
    paths benefit.  None (default) leaves params untouched.
    """
    b, p = prompt_ids.shape
    if param_dtype is not None:
        params = jax.tree.map(
            lambda x: x.astype(param_dtype)
            if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
            else x, params)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    if prompt_mask is not None:
        m = jnp.asarray(prompt_mask)
        if m.shape != (b, p):
            raise ValueError(f"prompt_mask shape {m.shape} != {(b, p)}")
        try:  # host-side sanity when concrete: left-padded = non-decreasing
            import numpy as _np
            mm = _np.asarray(m).astype(_np.int32)
            if not (_np.diff(mm, axis=1) >= 0).all():
                raise ValueError(
                    "prompt_mask must be LEFT-padded (real tokens "
                    "right-aligned): found a 0 after a 1")
            if not mm[:, -1].all():
                raise ValueError("prompt_mask: last column must be real "
                                 "(left-padding)")
        except jax.errors.TracerArrayConversionError:
            pass
        prompt_mask = m
    cfg = getattr(model, "cfg", None)
    # quantized matmuls are a TRAIN-step feature (delayed-scaling state
    # threads through the train step); decode runs in the compute dtype
    # — strip quant so a quant-trained model generates unmodified (the
    # param layout is identical either way)
    if cfg is not None and getattr(cfg, "quant", "none") != "none":
        from torchacc_tpu.models.transformer import TransformerLM
        cfg = dataclasses.replace(cfg, quant="none")
        if isinstance(model, TransformerLM):
            model = TransformerLM(cfg)
    # window/ALiBi decode runs through the cache branch (q_offset aligns
    # the decode-row geometry).  pp decode runs the stage-ring cached
    # path (_generate_cached_pp — cache stays stage-local, one ring pass
    # per token).  cp decode runs the NORMAL cached path: prefill banks
    # k/v through the cp-attention forward with the cache's slot dim
    # sharded over ('sp','spu') (models/transformer.py), and the decode
    # step's single-token attention over the sharded slots partitions
    # via GSPMD — no full-prefix recompute in either case.
    def _mesh_extent(*axes):
        mesh = jax.sharding.get_abstract_mesh()
        shape = getattr(mesh, "shape", None) or {}
        ext = 1
        for a in axes:
            ext *= int(shape.get(a, 1) or 1)
        return ext

    # the pp stage ring needs a live 'pp' mesh axis of the configured
    # extent AND the zoo param layout; otherwise (e.g. a pp-trained cfg
    # loaded on one host with no mesh) DEMOTE to a pp_size=1 view — the
    # stacked param layout is identical, so single-device execution is
    # exact
    pp_live = (cfg is not None and getattr(cfg, "pp_size", 1) > 1
               and _mesh_extent("pp") == cfg.pp_size
               and isinstance(params, dict) and "layers" in params
               # the pp stage ring applies ScanBlock uniformly — a
               # layer_pattern model must take the pattern path instead
               # (correct per-layer windows; GSPMD still resolves the
               # pp-sharded param slices)
               and not getattr(cfg, "layer_pattern", None))
    if (cfg is not None and getattr(cfg, "pp_size", 1) > 1
            and not pp_live):
        from torchacc_tpu.models.transformer import TransformerLM
        cfg = dataclasses.replace(cfg, pp_size=1, pp_num_micro=1)
        if isinstance(model, TransformerLM):
            model = TransformerLM(cfg)
    cp_cfg = cfg is not None and getattr(cfg, "context_parallel", False)
    can_cache = use_cache and cfg is not None
    if max_new_tokens <= 0:
        return prompt_ids
    total = p + max_new_tokens
    if (can_cache and cfg.pos_emb == "learned"
            and total > cfg.max_seq_len):
        # only a learned position table genuinely caps the length: the
        # cache is sized to `total`, and rope/ALiBi extrapolate
        raise ValueError(
            f"prompt + max_new_tokens = {total} exceeds the learned "
            f"position table max_seq_len {cfg.max_seq_len}")
    lr = getattr(cfg, "rope_longrope", None) if can_cache else None
    if lr is not None and p <= int(lr[2]) < p + max_new_tokens - 1:
        # Phi-3.5/4 longrope CACHE REBUILD at the original-context
        # crossing: keys banked under the short factors become invalid
        # once the sequence exceeds original_max — phi3's intended
        # behaviour (Phi3ForCausalLM.prepare_inputs_for_generation
        # invalidates past_key_values at input length original_max+1)
        # is to re-run the whole prefix under the LONG factors and
        # continue from that cache.  Decode up to the boundary, then
        # recurse with the tokens so far as the prompt: the re-prefill's
        # seq_len exceeds original_max, so it banks long-roped keys.
        # Hoisted ABOVE the pp / layer_pattern dispatches so every
        # cached path gets the rebuild (each phase re-enters the full
        # dispatch).  (transformers 4.57.6's own rebuild runs with a
        # stale single-element cache_position whose causal mask
        # degenerates to full attention over the re-fed prefix —
        # verified acausal; we implement the INTENDED semantics, which
        # equal HF's correct full forward at every step.)
        old_len = int(lr[2])
        n1 = old_len + 1 - p
        rng, r1, r2 = jax.random.split(rng, 3)
        first = generate(model, params, prompt_ids, max_new_tokens=n1,
                         temperature=temperature, rng=r1, eos_id=eos_id,
                         use_cache=True, prompt_mask=prompt_mask,
                         top_k=top_k, top_p=top_p)
        mask2 = None
        if prompt_mask is not None:
            mask2 = jnp.concatenate(
                [jnp.asarray(prompt_mask, jnp.int32),
                 jnp.ones((b, n1), jnp.int32)], axis=1)
        out = generate(model, params, first,
                       max_new_tokens=max_new_tokens - n1,
                       temperature=temperature, rng=r2, eos_id=eos_id,
                       use_cache=True, prompt_mask=mask2,
                       top_k=top_k, top_p=top_p)
        if eos_id is not None:
            # rows frozen at eos in phase 1 (their last token is eos:
            # freezing pins everything after the first eos) must stay
            # frozen — phase 2 has no done-state and would resume them
            done1 = first[:, -1] == eos_id
            tail = jnp.where(done1[:, None], jnp.int32(eos_id),
                             out[:, p + n1:])
            out = jnp.concatenate([out[:, :p + n1], tail], axis=1)
        return out

    if cfg is not None and getattr(cfg, "mixer_pattern", None):
        # layers of one mixer each exist on the serving layout alone:
        # states and caches thread through a raw per-layer loop
        if prompt_mask is not None or not use_cache:
            raise NotImplementedError(
                "generate() runs a mixer_pattern model from its states "
                "and caches, on whole prompts (no prompt_mask, no "
                "use_cache=False: the module has no forward to recompute)")
        return _generate_mixers(cfg, params, prompt_ids, rng,
                                float(temperature), int(max_new_tokens),
                                eos_id, int(top_k), float(top_p))
    if (can_cache and pp_live
            and (not cp_cfg or _mesh_extent("sp", "spu") > 1)):
        # pp x cp composes: the cp attention shard_map nests inside the
        # pp stage ring exactly as in the training path, and the cache's
        # slot sharding rides through the stage-local layout
        return _generate_cached_pp(cfg, params, prompt_ids, prompt_mask,
                                   rng, float(temperature),
                                   int(max_new_tokens), eos_id,
                                   int(top_k), float(top_p))
    if (can_cache and getattr(cfg, "layer_pattern", None)
            and not pp_live and not cp_cfg
            and isinstance(params, dict) and "layers" in params):
        # layer_pattern models cannot decode through model.apply (the
        # scan path cannot vary the per-layer window; TransformerLM
        # rejects pattern+cache) — use the per-layer pattern loop
        return _generate_cached_pattern(
            cfg, params, prompt_ids, prompt_mask, rng,
            float(temperature), int(max_new_tokens), eos_id,
            int(top_k), float(top_p))
    # a cp cfg without a live sp/spu mesh axis falls back to recompute
    # (the cp attention shard_map needs the axes)
    can_cache = (can_cache and not pp_live
                 and getattr(cfg, "pp_size", 1) == 1
                 and not getattr(cfg, "layer_pattern", None)
                 and (not cp_cfg or _mesh_extent("sp", "spu") > 1))
    if can_cache:
        from torchacc_tpu.models.transformer import TransformerLM

        # cache_len=total: short generations allocate (and attend over)
        # prompt+new positions, not a max_seq_len-sized cache
        pre_model = TransformerLM(dataclasses.replace(cfg, cache_len=total))
        dec_model = TransformerLM(dataclasses.replace(cfg, decode=True,
                                                      cache_len=total))
        return _generate_cached(pre_model, dec_model, params, prompt_ids,
                                prompt_mask, rng, float(temperature),
                                int(max_new_tokens), eos_id,
                                int(top_k), float(top_p))
    return _generate_recompute(model, params, prompt_ids,
                               prompt_mask=prompt_mask,
                               max_new_tokens=max_new_tokens,
                               temperature=temperature, rng=rng,
                               eos_id=eos_id, top_k=int(top_k),
                               top_p=float(top_p))


# ---------------------------------------------------------------------------
# pipeline-parallel KV-cache decode (VERDICT r3 next-7)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "cfg", "temperature", "max_new", "eos_id", "top_k", "top_p"))
def _generate_cached_pp(cfg, params, prompt_ids, prompt_mask, rng,
                        temperature, max_new, eos_id, top_k, top_p):
    """KV-cache decode under pipeline parallelism: the banked cache
    stays STAGE-LOCAL (sharded over 'pp' on the layer-chunk dim); each
    token costs one pass over the stage ring (pp.py
    pp_forward_with_cache) — no full-prefix recompute."""
    from torchacc_tpu.models.transformer import embed_ids, head_logits
    from torchacc_tpu.parallel.pp import pp_forward_with_cache

    b, p = prompt_ids.shape
    total = p + max_new
    # the block cfgs run OUTSIDE the pipeline dispatch (pp_size=1): the
    # pipeline structure lives in pp_forward_with_cache itself
    blk_pre = dataclasses.replace(cfg, decode=False, cache_len=total, pp_size=1)
    blk_dec = dataclasses.replace(cfg, decode=True, cache_len=total, pp_size=1)

    positions, row_len, seg = _prompt_geometry(prompt_ids, prompt_mask)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(p), (b, p))

    x = embed_ids(cfg, params, prompt_ids, positions)
    y, cache = pp_forward_with_cache(
        blk_pre, params["layers"], None, x, positions, seg, cfg.pp_size)
    logits = head_logits(cfg, params, y)

    def step_fn(cache, tok, positions1):
        x1 = embed_ids(cfg, params, tok[:, None], positions1)
        y1, cache = pp_forward_with_cache(
            blk_dec, params["layers"], cache, x1, positions1, None,
            cfg.pp_size)
        return head_logits(cfg, params, y1)[:, 0], cache

    return _drive_decode(logits, cache, step_fn, prompt_ids, row_len,
                         rng, temperature, max_new, eos_id, top_k,
                         top_p)


# ---------------------------------------------------------------------------
# heterogeneous-layer (gemma2-style) KV-cache decode
# ---------------------------------------------------------------------------

def _pattern_layers_with_cache(cfg, params, cache, x, positions, seg):
    """Raw per-layer loop threading the kv cache through the stacked
    layout (models/transformer.layer_tree: the canonical [L, ...] stack,
    leading dense layers, the serving layout of a period), with each
    layer's own pattern cfg — the scan path cannot vary a static window
    per layer.  ``cache=None`` (prefill) creates the banked cache."""
    from torchacc_tpu.models.transformer import ScanBlock, layer_tree

    new_layers = []
    for i in range(cfg.num_layers):
        tree, block_cfg = layer_tree(cfg, params, i)
        variables = {"params": tree}
        if cache is not None:
            variables["cache"] = jax.tree.map(
                lambda a, i=i: a[i], cache)
        (carry, _), vs = ScanBlock(block_cfg).apply(
            variables, (x, positions, seg), None, mutable=["cache"])
        x = carry[0]
        new_layers.append(vs["cache"])
    new_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *new_layers)
    return x, new_cache


@functools.partial(jax.jit, static_argnames=(
    "cfg", "temperature", "max_new", "eos_id", "top_k", "top_p"))
def _generate_cached_pattern(cfg, params, prompt_ids, prompt_mask, rng,
                             temperature, max_new, eos_id, top_k, top_p):
    """KV-cache decode for layer_pattern models: same scaffold as the
    other cached paths, with the per-layer pattern loop as forward."""
    from torchacc_tpu.models.transformer import embed_ids, head_logits

    b, p = prompt_ids.shape
    total = p + max_new
    blk_pre = dataclasses.replace(cfg, decode=False, cache_len=total)
    blk_dec = dataclasses.replace(cfg, decode=True, cache_len=total)

    positions, row_len, seg = _prompt_geometry(prompt_ids, prompt_mask)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(p), (b, p))

    x = embed_ids(cfg, params, prompt_ids, positions)
    y, cache = _pattern_layers_with_cache(
        blk_pre, params, None, x, positions, seg)
    logits = head_logits(cfg, params, y)

    def step_fn(cache, tok, positions1):
        x1 = embed_ids(cfg, params, tok[:, None], positions1)
        y1, cache = _pattern_layers_with_cache(
            blk_dec, params, cache, x1, positions1, None)
        return head_logits(cfg, params, y1)[:, 0], cache

    return _drive_decode(logits, cache, step_fn, prompt_ids, row_len,
                         rng, temperature, max_new, eos_id, top_k,
                         top_p)


# ---------------------------------------------------------------------------
# layers of one mixer each (mixer_pattern): state-space layers carry a
# state, attention layers a k/v cache, expert layers nothing
# ---------------------------------------------------------------------------

def _mixer_layers(cfg, params, cache, x, positions, pos):
    """The layers of a ``mixer_pattern`` model on the serving layout
    (models/transformer.layer_tree: ``layers/<kind>`` stacks), each
    ``x + mixer(ln(x))`` (models/block.mixer_block).  ``cache`` is None
    for the prompt (``x`` [b, p, H]; returns the caches it leaves: an
    attention layer's ``(k, v)`` of ``cfg.cache_len`` positions, a
    state-space layer's ``(conv, ssm)``) or the list those calls
    returned, for one token at cache position ``pos``."""
    from torchacc_tpu.models import block, mamba2
    from torchacc_tpu.models.moe import moe_ffn
    from torchacc_tpu.models.transformer import layer_kinds, layer_tree
    from torchacc_tpu.ops.attention import attention_reference

    b, s, _ = x.shape
    new_cache = []
    for i, kind in enumerate(layer_kinds(cfg)):
        tree, _ = layer_tree(cfg, params, i)
        p, kept = tree["block"], None

        def attention(h, p=p, i=i):
            proj = block.tree_proj(cfg, p["attn"])
            q, k, v = block.qkv(cfg, h, positions, proj,
                                block.tree_norm(cfg, p["attn"]))
            if cache is None:
                room = ((0, 0), (0, cfg.cache_len - s), (0, 0), (0, 0))
                banked = (jnp.pad(k, room), jnp.pad(v, room))
                out = attention_reference(q, k, v, causal=True,
                                          scale=cfg.query_scale)
            else:
                banked = tuple(jax.lax.dynamic_update_slice(
                    c, t.astype(c.dtype), (0, pos, 0, 0))
                    for c, t in zip(cache[i], (k, v)))
                out = attention_reference(
                    q, *banked, causal=True, scale=cfg.query_scale,
                    q_offset=pos - (banked[0].shape[1] - s))
            return proj("o_proj", out), banked

        def experts(h, p=p):
            y = moe_ffn(cfg, p["moe"], h.reshape(b * s, -1))[0]
            return y.reshape(b, s, -1), None

        def mamba(h, p=p, i=i):
            if cache is None:
                return mamba2.mixer_sequence(cfg, p["mixer"], h)
            conv, ssm = cache[i]
            out, conv, ssm = mamba2.mixer_step(
                cfg, p["mixer"], h, conv[None], ssm[None], 0,
                jnp.ones((b,), bool))
            return out, (conv[0], ssm[0])

        def mixer(h, fn={"attention": attention, "moe": experts,
                         "mamba": mamba}[kind]):
            nonlocal kept
            out, kept = fn(h)
            return out

        x = block.mixer_block(cfg, x, block.tree_norm(cfg, p), mixer,
                              routed=kind == "moe")
        new_cache.append(kept)
    return x, new_cache


@functools.partial(jax.jit, static_argnames=(
    "cfg", "temperature", "max_new", "eos_id", "top_k", "top_p"))
def _generate_mixers(cfg, params, prompt_ids, rng, temperature, max_new,
                     eos_id, top_k, top_p):
    """Cached decode for ``mixer_pattern`` models: the prompt through
    the whole-sequence mixers, then one token a step from the states
    and caches they left."""
    from torchacc_tpu.models.transformer import embed_ids, head_logits

    b, p = prompt_ids.shape
    cfg = dataclasses.replace(cfg, cache_len=p + max_new)
    positions = jnp.broadcast_to(jnp.arange(p), (b, p))
    x = embed_ids(cfg, params, prompt_ids, positions)
    y, cache = _mixer_layers(cfg, params, None, x, positions, 0)
    logits = head_logits(cfg, params, y)

    def step_fn(cache, tok, positions1):
        x1 = embed_ids(cfg, params, tok[:, None], positions1)
        y1, cache = _mixer_layers(cfg, params, cache, x1, positions1,
                                  positions1[0, 0])
        return head_logits(cfg, params, y1)[:, 0], cache

    return _drive_decode(logits, cache, step_fn, prompt_ids,
                         jnp.full((b,), p, jnp.int32), rng, temperature,
                         max_new, eos_id, top_k, top_p)


# ---------------------------------------------------------------------------
# fallback: full-prefix recompute (works for any (input_ids)->logits model)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("model", "temperature",
                                             "top_k", "top_p"))
def _decode_step(model, params, tokens, mask_full, cur, rng, temperature,
                 top_k, top_p):
    """One full-prefix recompute step over the fixed [b, total] buffer.

    Positions of slots past the live prefix CLAMP to the current
    position: those slots are causally invisible to the logits read at
    ``cur - 1``, and clamping keeps length-dependent rope variants
    (longrope's short/long regime switch keys off ``max(positions)``)
    seeing the CURRENT sequence length instead of the padded buffer —
    HF full-forward semantics."""
    b, total = tokens.shape
    if mask_full is not None:
        positions = jnp.clip(jnp.cumsum(mask_full, axis=1) - 1, 0, None)
        # per-row cap at the position of the newest live slot (positions
        # are non-decreasing along the row)
        cap = jnp.take_along_axis(
            positions, (cur - 1)[None, None].repeat(b, 0), axis=1)
        positions = jnp.minimum(positions, cap)
        logits = model.apply({"params": params}, tokens,
                             positions=positions, segment_ids=mask_full)
    elif getattr(model, "cfg", None) is not None:
        positions = jnp.minimum(jnp.arange(total), cur - 1)
        positions = jnp.broadcast_to(positions[None], (b, total))
        logits = model.apply({"params": params}, tokens,
                             positions=positions)
    else:
        # bare (input_ids) -> logits models take no positions kwarg
        # (and have no length-dependent rope to clamp for)
        logits = model.apply({"params": params}, tokens)
    # logits at position cur-1 predict token cur
    next_logits = jnp.take_along_axis(
        logits, (cur - 1)[None, None, None].repeat(b, 0), axis=1)[:, 0]
    rng, sub = jax.random.split(rng)
    nxt = _sample(next_logits, sub, temperature, top_k, top_p)
    return tokens.at[:, cur].set(nxt.astype(jnp.int32)), rng


def _generate_recompute(model, params, prompt_ids, *, max_new_tokens,
                        temperature, rng, eos_id, prompt_mask=None,
                        top_k=0, top_p=1.0):
    b, p = prompt_ids.shape
    total = p + max_new_tokens
    tokens = jnp.zeros((b, total), jnp.int32)
    tokens = tokens.at[:, :p].set(prompt_ids)
    mask_full = None
    if prompt_mask is not None:
        # generated tokens are always real
        mask_full = jnp.concatenate(
            [prompt_mask.astype(jnp.int32),
             jnp.ones((b, max_new_tokens), jnp.int32)], axis=1)

    done = jnp.zeros((b,), jnp.bool_)
    for i in range(max_new_tokens):
        cur = jnp.asarray(p + i)
        new_tokens, rng = _decode_step(model, params, tokens, mask_full,
                                       cur, rng, temperature, top_k, top_p)
        if eos_id is not None:
            prev = tokens
            new_col = new_tokens[:, p + i]
            new_col = jnp.where(done, eos_id, new_col)
            done = done | (new_col == eos_id)
            tokens = prev.at[:, p + i].set(new_col)
            if bool(done.all()):
                break
        else:
            tokens = new_tokens
    return tokens
