"""Pallas flash attention vs the plain-XLA reference attention.

Mirrors the reference's op-correctness strategy (tests/ops/
test_flash_attn.py:41-100 — parametrized grids comparing the XLA custom
call against upstream flash_attn CUDA).  Here the trusted baseline is
ops/attention.py and the kernel runs in interpret mode on CPU.
"""

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchacc_tpu.ops import flash_attention as fa
from torchacc_tpu.ops.attention import attention_reference
from torchacc_tpu.ops.flash_attention import (
    flash_attention,
    segment_ids_from_positions,
)


def _make_qkv(b, sq, sk, hq, hk, d, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, sq, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, sk, hk, d), dtype)
    v = jax.random.normal(ks[2], (b, sk, hk, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("hq,hk", [(4, 4), (4, 2), (4, 1)])
def test_fwd_matches_reference(causal, hq, hk):
    q, k, v = _make_qkv(2, 128, 128, hq, hk, 64)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_fwd_lse_matches_reference():
    q, k, v = _make_qkv(1, 128, 128, 2, 2, 64)
    out, lse = flash_attention(q, k, v, causal=True, return_lse=True,
                               block_q=64, block_k=64)
    ref, ref_lse = attention_reference(q, k, v, causal=True, return_lse=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               atol=2e-5, rtol=2e-5)


def test_uneven_seq_padding():
    q, k, v = _make_qkv(1, 100, 100, 2, 2, 64, seed=3)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_sliding_window():
    q, k, v = _make_qkv(1, 128, 128, 2, 2, 64, seed=4)
    out = flash_attention(q, k, v, causal=True, window=(32, -1),
                          block_q=32, block_k=32)
    ref = attention_reference(q, k, v, causal=True, window=(32, -1))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_segment_ids_varlen():
    """Packed sequences must not attend across boundaries."""
    q, k, v = _make_qkv(1, 128, 128, 2, 2, 64, seed=5)
    seg = jnp.concatenate([jnp.zeros((1, 48), jnp.int32),
                           jnp.ones((1, 80), jnp.int32)], axis=1)
    out = flash_attention(q, k, v, causal=True, q_segment_ids=seg,
                          kv_segment_ids=seg, block_q=64, block_k=64)
    ref = attention_reference(q, k, v, causal=True, q_segment_ids=seg,
                              kv_segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    # independence: computing the second sequence alone gives the same
    sub = flash_attention(q[:, 48:], k[:, 48:], v[:, 48:], causal=True,
                          block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out[:, 48:]), np.asarray(sub),
                               atol=2e-5)


def test_position_ids_to_segments():
    pos = jnp.array([[0, 1, 2, 0, 1, 0, 1, 2]])
    seg = segment_ids_from_positions(pos)
    np.testing.assert_array_equal(np.asarray(seg),
                                  [[0, 0, 0, 1, 1, 2, 2, 2]])


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("hq,hk", [(4, 4), (4, 2)])
def test_grads_match_reference(causal, hq, hk):
    q, k, v = _make_qkv(1, 128, 128, hq, hk, 64, seed=6)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=64, block_k=64) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=1e-3, err_msg=f"d{name}")


def test_grads_with_segments_and_window():
    q, k, v = _make_qkv(1, 96, 96, 2, 2, 64, seed=7)
    seg = jnp.concatenate([jnp.zeros((1, 40), jnp.int32),
                           jnp.ones((1, 56), jnp.int32)], axis=1)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, window=(24, -1), q_segment_ids=seg,
            kv_segment_ids=seg, block_q=32, block_k=32) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(attention_reference(
            q, k, v, causal=True, window=(24, -1), q_segment_ids=seg,
            kv_segment_ids=seg) ** 2)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=1e-3, err_msg=f"d{name}")


@pytest.mark.parametrize("causal", [True, False])
def test_alibi_matches_reference(causal):
    q, k, v = _make_qkv(2, 128, 128, 4, 2, 64, seed=11)
    slopes = jnp.asarray([0.25, 0.0625, 0.015625, 0.00390625], jnp.float32)
    out = flash_attention(q, k, v, causal=causal, alibi_slopes=slopes,
                          block_q=64, block_k=64)
    ref = attention_reference(q, k, v, causal=causal, alibi_slopes=slopes)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_alibi_grads_match_reference():
    q, k, v = _make_qkv(1, 96, 96, 4, 4, 64, seed=12)
    slopes = jnp.asarray([0.5, 0.125, 0.03125, 0.0078125], jnp.float32)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       alibi_slopes=slopes,
                                       block_q=32, block_k=32) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True,
                                           alibi_slopes=slopes) ** 2)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=1e-3, err_msg=f"d{name}")


def test_alibi_with_segments():
    q, k, v = _make_qkv(1, 64, 64, 2, 2, 64, seed=13)
    slopes = jnp.asarray([0.25, 0.0625], jnp.float32)
    seg = jnp.concatenate([jnp.zeros((1, 24), jnp.int32),
                           jnp.ones((1, 40), jnp.int32)], axis=1)
    out = flash_attention(q, k, v, causal=True, alibi_slopes=slopes,
                          q_segment_ids=seg, kv_segment_ids=seg,
                          block_q=32, block_k=32)
    ref = attention_reference(q, k, v, causal=True, alibi_slopes=slopes,
                              q_segment_ids=seg, kv_segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_alibi_cross_attention_alignment():
    """sq != sk: bottom-right alignment — last query aligns with last key."""
    q, k, v = _make_qkv(1, 32, 96, 2, 2, 64, seed=14)
    slopes = jnp.asarray([0.25, 0.0625], jnp.float32)
    out = flash_attention(q, k, v, causal=False, alibi_slopes=slopes,
                          block_q=32, block_k=32)
    ref = attention_reference(q, k, v, causal=False, alibi_slopes=slopes)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_causal_cross_attention_bottom_right():
    """causal with sq != sk: bottom-right aligned (flash-attn semantics) —
    the LAST query sees ALL keys, the first query sees sk-sq+1 keys."""
    q, k, v = _make_qkv(1, 16, 48, 2, 2, 64, seed=16)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    # last query row attends everything -> differs from a sk-truncated call
    full_row = attention_reference(q[:, -1:], k, v, causal=False)
    np.testing.assert_allclose(np.asarray(ref[:, -1:]),
                               np.asarray(full_row), atol=1e-5)
    # alibi + causal cross-attention agree between backends too
    slopes = jnp.asarray([0.25, 0.0625], jnp.float32)
    out_a = flash_attention(q, k, v, causal=True, alibi_slopes=slopes,
                            block_q=16, block_k=16)
    ref_a = attention_reference(q, k, v, causal=True, alibi_slopes=slopes)
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(ref_a),
                               atol=2e-5)


def test_alibi_slopes_not_trainable_consistently():
    """Both backends treat slopes as constants: zero gradient from each."""
    q, k, v = _make_qkv(1, 32, 32, 2, 2, 64, seed=15)
    slopes = jnp.asarray([0.25, 0.0625], jnp.float32)

    g1 = jax.grad(lambda s: jnp.sum(flash_attention(
        q, k, v, causal=True, alibi_slopes=s, block_q=32, block_k=32)
        .astype(jnp.float32) ** 2))(slopes)
    g2 = jax.grad(lambda s: jnp.sum(attention_reference(
        q, k, v, causal=True, alibi_slopes=s).astype(jnp.float32) ** 2))(slopes)
    np.testing.assert_array_equal(np.asarray(g1), 0.0)
    np.testing.assert_array_equal(np.asarray(g2), 0.0)


def test_bf16_fwd_close():
    q, k, v = _make_qkv(1, 128, 128, 2, 2, 64, dtype=jnp.bfloat16, seed=8)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=2e-2, rtol=2e-2)


# ---------------------------------------------------------------------------
# attention dropout (reference ops/flash_attn.py:418-423) + global offsets
# ---------------------------------------------------------------------------

def test_dropout_pallas_matches_xla_exactly():
    """Same seed -> bit-identical mask on both backends (the stateless
    coordinate hash), so outputs agree to numerics."""
    q, k, v = _make_qkv(2, 128, 128, 4, 4, 64, seed=7)
    out = flash_attention(q, k, v, causal=True, dropout_p=0.3,
                          dropout_seed=17, block_q=64, block_k=64)
    ref = attention_reference(q, k, v, causal=True, dropout_p=0.3,
                              dropout_seed=17)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


def test_dropout_zero_is_identity():
    q, k, v = _make_qkv(1, 128, 128, 2, 2, 64, seed=8)
    a = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    b = flash_attention(q, k, v, causal=True, dropout_p=0.0,
                        dropout_seed=5, block_q=64, block_k=64)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dropout_seed_changes_output_deterministically():
    q, k, v = _make_qkv(1, 128, 128, 2, 2, 64, seed=9)
    f = functools.partial(flash_attention, causal=True, dropout_p=0.5,
                          block_q=64, block_k=64)
    a1 = f(q, k, v, dropout_seed=1)
    a1b = f(q, k, v, dropout_seed=1)
    a2 = f(q, k, v, dropout_seed=2)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a1b))
    assert np.abs(np.asarray(a1) - np.asarray(a2)).max() > 1e-3


def test_dropout_seed_is_traced_not_compiled():
    """Seed arrives via SMEM scalars: stepping the seed must not trigger
    a recompile (one jit trace, many seeds)."""
    q, k, v = _make_qkv(1, 128, 128, 2, 2, 64, seed=10)
    traces = 0

    @jax.jit
    def f(q, k, v, seed):
        nonlocal traces
        traces += 1
        return flash_attention(q, k, v, causal=True, dropout_p=0.2,
                               dropout_seed=seed, block_q=64, block_k=64)

    outs = [f(q, k, v, jnp.int32(s)) for s in range(3)]
    assert traces == 1
    assert np.abs(np.asarray(outs[0]) - np.asarray(outs[1])).max() > 1e-4


@pytest.mark.parametrize("hq,hk", [(4, 4), (4, 2)])
def test_dropout_grads_match_xla(hq, hk):
    """The custom-VJP dropped-softmax backward (dS = P-tilde dP - P delta)
    against jax autodiff through the dense XLA path with the SAME mask."""
    q, k, v = _make_qkv(1, 128, 128, hq, hk, 32, seed=11)

    def f_pallas(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, dropout_p=0.25,
                                       dropout_seed=3, block_q=64,
                                       block_k=64) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True,
                                           dropout_p=0.25,
                                           dropout_seed=3) ** 2)

    gp = jax.grad(f_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-4, rtol=3e-4)


def test_global_offsets_match_full_attention():
    """flash(q_chunk, k_chunk, q_offset, k_offset) must equal the
    corresponding tile of full attention — the contract the CP ring is
    built on (causal geometry + ALiBi + dropout all keyed globally)."""
    b, s, h, d = 1, 256, 2, 32
    q, k, v = _make_qkv(b, s, s, h, h, d, seed=12)
    slopes = jnp.asarray([0.25, 0.5], jnp.float32)

    # full lse for the merged comparison
    full, full_lse = attention_reference(q, k, v, causal=True,
                                         alibi_slopes=slopes,
                                         return_lse=True)
    half = s // 2
    # second q chunk attends to both kv chunks: merge two offset calls
    from torchacc_tpu.ops.context_parallel.merge import merge_attention
    from torchacc_tpu.ops._common import NEG_INF
    q2 = q[:, half:]
    o_a, lse_a = flash_attention(q2, k[:, :half], v[:, :half], causal=True,
                                 q_offset=half, k_offset=0,
                                 return_lse=True, block_q=64, block_k=64,
                                 alibi_slopes=slopes)
    o_b, lse_b = flash_attention(q2, k[:, half:], v[:, half:], causal=True,
                                 q_offset=half, k_offset=half,
                                 return_lse=True, block_q=64, block_k=64,
                                 alibi_slopes=slopes)
    out0 = jnp.zeros(o_a.shape, jnp.float32)
    lse0 = jnp.full(lse_a.shape, NEG_INF, jnp.float32)
    out, lse = merge_attention(out0, lse0, o_a.astype(jnp.float32), lse_a)
    out, lse = merge_attention(out, lse, o_b.astype(jnp.float32), lse_b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full[:, half:]),
                               atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(full_lse[:, :, half:]),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("window", [(-1, -1), (32, -1)])
def test_logit_softcap_matches_reference(window):
    """Gemma2 attention-score soft-capping in the kernel: forward AND
    gradients (the hand-written bwd must chain 1 - tanh^2 through the
    recomputed scores) match the XLA reference, with and without a
    sliding window."""
    q, k, v = _make_qkv(2, 128, 128, 4, 2, 64, seed=7)
    cap = 20.0

    out = flash_attention(q, k, v, causal=True, window=window,
                          logit_softcap=cap, block_q=64, block_k=64)
    ref = attention_reference(q, k, v, causal=True, window=window,
                              logit_softcap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

    def loss_pl(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, window=window, logit_softcap=cap,
            block_q=64, block_k=64).astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(
            q, k, v, causal=True, window=window,
            logit_softcap=cap).astype(jnp.float32) ** 2)

    g_pl = jax.grad(loss_pl, argnums=(0, 1, 2))(q, k, v)
    g_rf = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_pl, g_rf, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name}")
    # capping actually changes the math (the test is not vacuous)
    base = flash_attention(q, k, v, causal=True, window=window,
                           block_q=64, block_k=64)
    assert not np.allclose(np.asarray(out), np.asarray(base), atol=1e-3)

    # the standalone fwd(return_lse)+bwd pair (the CP-ring contract)
    # honors the cap too
    from torchacc_tpu.ops.flash_attention import flash_attention_bwd
    o2, lse = flash_attention(q, k, v, causal=True, window=window,
                              logit_softcap=cap, return_lse=True,
                              block_q=64, block_k=64)
    do = (2.0 * o2.astype(jnp.float32)).astype(q.dtype)
    dq, dk, dv = flash_attention_bwd(
        q, k, v, o2, lse, do, causal=True, window=window,
        logit_softcap=cap, block_q=64, block_k=64)
    for a, b, name in zip((dq, dk, dv), g_rf, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5,
                                   err_msg=f"standalone d{name}")


# ---------------------------------------------------------------------------
# only the work the mask leaves: dead steps, diagonal tiles, the dk/dv
# kernel's [k, q] tiles
# ---------------------------------------------------------------------------

_GEOMETRIES = [
    # block_q, block_k, causal, window, shift, nq, nk
    (256, 256, True, (-1, -1), 0, 4, 4),        # the cells' shape, scaled
    (256, 256, True, (-1, -1), 512, 2, 4),      # sq < sk, bottom-right
    (256, 256, True, (-1, -1), -512, 4, 2),     # sq > sk: rows with no key
    (128, 128, True, (200, -1), 0, 4, 4),       # a left window
    (128, 256, True, (300, -1), 128, 5, 3),     # ragged blocks and shift
    (64, 128, False, (100, 30), 0, 6, 3),       # a band without causality
    (64, 64, False, (-1, 40), 64, 4, 5),        # a right window alone
    (128, 128, False, (-1, -1), 0, 3, 3),       # no positional mask
]


@pytest.mark.parametrize("geometry", _GEOMETRIES,
                         ids=[f"g{i}" for i in range(len(_GEOMETRIES))])
def test_live_range_agrees_with_block_should_run(geometry):
    """``_live_range`` (what the index maps clamp to) and
    ``_block_should_run`` (what lets a body run) are one rule: on every
    (qi, ki), from the q side and from the kv side."""
    bq, bk, causal, window, shift, nq, nk = geometry
    runs = np.array([[bool(fa._block_should_run(
        qi * bq, ki * bk, bq, bk, causal, window, shift))
        for ki in range(nk)] for qi in range(nq)])
    for qi in range(nq):
        lo, hi = fa._live_range(qi, bq, bk, causal, window, shift, nk)
        assert [lo <= ki <= hi for ki in range(nk)] == list(runs[qi]), qi
        # traced, as an index map calls it
        tlo, thi = jax.jit(lambda i: fa._live_range(
            i, bq, bk, causal, window, shift, nk))(jnp.int32(qi))
        assert (int(tlo), int(thi)) == (lo, hi)
    for ki in range(nk):
        lo, hi = fa._live_range(ki, bq, bk, causal, window, shift, nq,
                                of_kv_block=True)
        assert [lo <= qi <= hi for qi in range(nq)] == list(runs[:, ki]), ki
    # a dead step names a block its row's live steps hold (where any is)
    for qi, ki in itertools.product(range(nq), range(nk)):
        named = int(fa._live_block(ki, qi, bq, bk, causal, window, shift, nk))
        assert 0 <= named < nk
        if runs[qi, ki]:
            assert named == ki
        elif runs[qi].any():
            assert runs[qi, named]
    plan = fa.tile_plan(nq * bq, nk * bk, bq, bk, causal, window, shift)
    assert plan["steps"] == nq * nk and plan["live"] == runs.sum()
    # only a q row or a kv column with no live step at all can still
    # copy a block in vain (its own q block, its own kv block)
    assert plan["dead_fetching"] <= ((~runs.any(axis=1)).sum()
                                     + (~runs.any(axis=0)).sum())


def test_tile_plan_at_the_cells_shape():
    """seq 4096 in 1024x1024 blocks under a causal mask (all three train
    cells): 16 steps, 10 live, no dead step copies a block, the four
    diagonal tiles leave out their masked quarter.  With traced offsets
    (the ring) the band is not known when the kernel is built."""
    assert fa._block_sizes(4096, 4096) == (1024, 1024)
    assert fa.tile_plan(4096, 4096, 1024, 1024, True, (-1, -1), 0) == dict(
        steps=16, live=10, dead_fetching=0, diagonal_split=4)
    assert fa.tile_plan(4096, 4096, 1024, 1024, True, (-1, -1), 0,
                        has_seg=True)["diagonal_split"] == 0
    assert fa.tile_plan(4096, 4096, 1024, 1024, True, (-1, -1), None) == dict(
        steps=16, live=None, dead_fetching=None, diagonal_split=0)


@pytest.mark.parametrize("unclamped", ["kv_side", "q_side"])
def test_tile_plan_reads_the_index_maps_the_kernels_are_given(monkeypatch,
                                                              unclamped):
    """``dead_fetching`` is walked off the BlockSpecs the three
    ``pallas_call``s take, not off the range function: index maps that
    name a dead step's own block again — flash_fwd / flash_dq's kv side,
    or flash_dkv's q side — read the six copies in vain the parent made."""
    clamp = fa._live_block

    def own_block(j, *args, of_kv_block=False, **kw):
        if of_kv_block == (unclamped == "q_side"):
            return j
        return clamp(j, *args, of_kv_block=of_kv_block, **kw)

    monkeypatch.setattr(fa, "_live_block", own_block)
    assert fa.tile_plan(4096, 4096, 1024, 1024, True, (-1, -1), 0) == dict(
        steps=16, live=10, dead_fetching=6, diagonal_split=4)


def _segments(b, s, cuts):
    """[b, s] packed segment ids with boundaries at ``cuts``."""
    ids = np.zeros((b, s), np.int32)
    for c in cuts:
        ids[:, c:] += 1
    return jnp.asarray(ids)


@pytest.mark.parametrize("feature", ["alibi", "dropout", "softcap"])
@pytest.mark.parametrize("group", [1, 4], ids=["mha", "gqa4"])
@pytest.mark.parametrize("sk", [512, 1024], ids=["sq_eq_sk", "sq_lt_sk"])
@pytest.mark.parametrize("segments", [False, True],
                         ids=["dense", "packed"])
@pytest.mark.parametrize("window", [(-1, -1), (300, -1)],
                         ids=["causal", "left300"])
def test_masked_work_is_skipped_not_changed(window, segments, sk, group,
                                            feature):
    """Forward, dq, dk and dv against the XLA reference where the grids
    hold dead steps and diagonal tiles: 256-blocks over 512 queries (the
    halves of a diagonal tile still tile the lanes, so the causal dense
    cases take the split; windows and segment ids stand aside)."""
    sq, hk, d = 512, 1, 32
    q, k, v = _make_qkv(1, sq, sk, hk * group, hk, d, seed=21)
    kw = dict(causal=True, window=window)
    if segments:
        kw.update(q_segment_ids=_segments(1, sq, (200, 390)),
                  kv_segment_ids=_segments(1, sk, (sk - sq + 200,
                                                   sk - sq + 390)))
    if feature == "alibi":
        kw["alibi_slopes"] = jnp.asarray(
            [2.0 ** -(i + 2) for i in range(hk * group)], jnp.float32)
    elif feature == "dropout":
        kw.update(dropout_p=0.2, dropout_seed=5)
    else:
        kw["logit_softcap"] = 15.0
    plan = fa.tile_plan(sq, sk, 256, 256, True, window, sk - sq,
                        has_seg=segments)
    assert plan["live"] < plan["steps"] and plan["dead_fetching"] == 0
    assert plan["diagonal_split"] == (
        2 if window == (-1, -1) and not segments else 0)

    def loss(fn, **blocks):
        return lambda q, k, v: jnp.sum(fn(q, k, v, **kw, **blocks) ** 2)

    out = flash_attention(q, k, v, block_q=256, block_k=256, **kw)
    ref = attention_reference(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)
    g_flash = jax.grad(loss(flash_attention, block_q=256, block_k=256),
                       argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(attention_reference), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=1e-3, err_msg=f"d{name}")
