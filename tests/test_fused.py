"""Fused linear+CE: numerical equivalence (loss AND grads) with the
naive logits path, plus trainer integration (reference analogue: Liger
fused-linear-cross-entropy parity, ops/liger.py)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchacc_tpu as ta
from torchacc_tpu.models import get_preset
from torchacc_tpu.models.transformer import loss_sum_count
from torchacc_tpu.ops.fused import fused_linear_cross_entropy
from torchacc_tpu.train import accelerate


def _naive(hidden, w, labels):
    logits = hidden.astype(jnp.float32) @ w.astype(jnp.float32)
    return loss_sum_count(logits, labels)


def test_fused_ce_matches_naive_loss_and_grads():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    hidden = jax.random.normal(ks[0], (2, 24, 32))
    w = jax.random.normal(ks[1], (32, 101)) * 0.1
    labels = jax.random.randint(ks[2], (2, 24), 0, 101)
    labels = labels.at[:, -5:].set(-100)

    def f_fused(h, w):
        l, c = fused_linear_cross_entropy(h, w, labels, chunk_rows=16)
        return l / c

    def f_naive(h, w):
        l, c = _naive(h, w, labels)
        return l / c

    lf, ln = f_fused(hidden, w), f_naive(hidden, w)
    np.testing.assert_allclose(float(lf), float(ln), rtol=1e-6)

    gf = jax.grad(f_fused, argnums=(0, 1))(hidden, w)
    gn = jax.grad(f_naive, argnums=(0, 1))(hidden, w)
    for a, b, name in zip(gf, gn, ("dh", "dw")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6, err_msg=name)


def test_fused_ce_all_masked():
    hidden = jnp.ones((1, 8, 16))
    w = jnp.ones((16, 32))
    labels = jnp.full((1, 8), -100)
    l, c = fused_linear_cross_entropy(hidden, w, labels, chunk_rows=4)
    assert float(l) == 0.0 and float(c) == 0.0


@pytest.mark.parametrize("tie", [False, True])
def test_trainer_fused_matches_unfused(devices, tie):
    """fused_kernels on/off must produce identical training losses."""
    import optax
    mc = get_preset("llama-tiny", vocab_size=128, hidden_size=64,
                    num_layers=2, num_heads=4, num_kv_heads=2,
                    intermediate_size=128, tie_embeddings=tie,
                    dtype=jnp.float32)
    rng = np.random.default_rng(0)
    data = rng.integers(0, 128, size=(4, 32))
    batches = [{"input_ids": data[rng.integers(0, 4, size=8)].astype(np.int32)}
               for _ in range(3)]

    losses = {}
    for fused in (True, False):
        cfg = ta.Config(compute=ta.ComputeConfig(fused_kernels=fused))
        t, _ = accelerate(mc, None, cfg, optimizer=optax.adam(1e-3))
        t.init()
        losses[fused] = [float(t.step(b)["loss"]) for b in batches]
    np.testing.assert_allclose(losses[True], losses[False], rtol=2e-4)


def test_scan_free_chunk_never_unrolls_tiny_divisors():
    """ADVICE r3 medium: prime/near-prime row counts must not pick a tiny
    divisor (which would unroll n/d python chunks at trace time)."""
    from torchacc_tpu.ops.fused import _scan_free_chunk

    # prime n: only divisors are {1, n}; must fall back to n (one chunk),
    # never 1 (n chunks)
    assert _scan_free_chunk(4099, 2048) == 4099
    # 2 * prime: {1, 2, p, n}; 2 would unroll ~4k chunks — must pick >= n/2
    assert _scan_free_chunk(2 * 4099, 2048) in (4099, 2 * 4099)
    # composite n keeps the tuned size
    assert _scan_free_chunk(8192, 2048) == 2048
    # awkward-but-composite picks the nearest in-band divisor
    assert _scan_free_chunk(4106, 2048) == 2053
    # n smaller than the band floor: one chunk of n rows
    assert _scan_free_chunk(13, 2048) == 13
    # chunk count stays bounded in all cases
    for n in (4099, 2 * 4099, 3 * 1361, 8192, 4106, 13, 6 * 4099):
        d = _scan_free_chunk(n, 2048)
        assert n % d == 0 and n // d <= 64, (n, d)


def _rule_case(name):
    """(hidden, w_head, labels, kwargs) for one case of the custom_vjp."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    hidden = jax.random.normal(ks[0], (2, 24, 32))
    w = jax.random.normal(ks[1], (32, 101)) * 0.1
    labels = jax.random.randint(ks[2], (2, 24), 0, 101)
    kw = dict(chunk_rows=16)
    if name == "some_rows_ignored":
        labels = labels.at[0, 3:9].set(-100).at[1, -5:].set(-100)
    elif name == "every_row_ignored":
        labels = jnp.full_like(labels, -100)
    elif name == "softcap":
        w, kw = w * 20.0, dict(chunk_rows=16, logit_softcap=5.0)
    elif name == "tied_head":
        w = w.T                 # the embedding [V, H]; the head is its .T
    elif name == "pad_path":
        kw = dict(chunk_rows=20)            # 48 rows: 3 chunks, 12 padded
    elif name == "scan_free":
        kw = dict(chunk_rows=16, scan_free=True)
    elif name == "bf16_hidden_f32_weight":
        hidden = hidden.astype(jnp.bfloat16)
    elif name == "bf16_hidden_bf16_weight":   # dW summed in bf16
        hidden, w = hidden.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    return hidden, w, labels, kw


@pytest.mark.parametrize("case", [
    "plain", "some_rows_ignored", "every_row_ignored", "softcap",
    "tied_head", "pad_path", "scan_free", "bf16_hidden_f32_weight",
    "bf16_hidden_bf16_weight", "upstream_cotangent"])
def test_fused_ce_rule_matches_autodiff_of_naive(case):
    """The hand-written rule (dlogits, d(hidden), dW formed in the
    forward's chunk loop, scaled in the backward) against ``jax.grad``
    of the materialised-logits head."""
    from torchacc_tpu.models.transformer import softcap
    hidden, w, labels, kw = _rule_case(case)
    tied = case == "tied_head"
    scale = 3.0 if case == "upstream_cotangent" else 1.0

    def mean(l, c):
        return scale * l / jnp.maximum(c, 1.0)

    def f_fused(h, w):
        return mean(*fused_linear_cross_entropy(
            h, w.T if tied else w, labels, **kw))

    def f_naive(h, w):
        # the fused head rounds the weight to the hidden's dtype
        wh = (w.T if tied else w).astype(h.dtype).astype(jnp.float32)
        logits = softcap(h.astype(jnp.float32) @ wh,
                         kw.get("logit_softcap", 0.0))
        return mean(*loss_sum_count(logits, labels))

    lf, gf = jax.value_and_grad(f_fused, argnums=(0, 1))(hidden, w)
    ln, gn = jax.value_and_grad(f_naive, argnums=(0, 1))(hidden, w)
    bf16 = hidden.dtype == jnp.bfloat16
    np.testing.assert_allclose(float(lf), float(ln),
                               rtol=2e-3 if bf16 else 1e-6)
    for a, b, name in zip(gf, gn, ("d_hidden", "d_w")):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        # bf16: d(hidden) is rounded to the hidden's dtype, and a bf16
        # head's dW is summed over the chunks in bf16
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=3e-2 if bf16 else 1e-5, atol=2e-3 if bf16 else 1e-6,
            err_msg=name)
    if case == "every_row_ignored":
        assert float(lf) == 0.0
        assert not np.asarray(gf[0]).any() and not np.asarray(gf[1]).any()


def test_fused_ce_forms_its_gradient_in_the_forward_loop():
    """The mechanism, not the numbers: under ``jax.grad`` the head holds
    ONE chunk loop with three head-sized matmuls in its body (logits,
    d(hidden), dW), nothing rematerialised, and no second loop in the
    backward — which only scales the two saved gradients."""
    rows, h, v, chunks = 16, 32, 128, 4
    hidden = jnp.zeros((1, rows * chunks, h))
    w = jnp.zeros((h, v))
    labels = jnp.zeros((1, rows * chunks), jnp.int32)

    def loss(hid, w):
        l, c = fused_linear_cross_entropy(hid, w, labels, chunk_rows=rows)
        return l / c

    grad = jax.jit(jax.grad(loss, argnums=(0, 1)))
    text = grad.lower(hidden, w).compile().as_text()
    head_ops = [ln for ln in text.splitlines()
                if re.search(r'op_name="[^"]*fused_ce', ln)]
    assert head_ops and not any(
        "rematted_computation" in ln or "checkpoint" in ln
        for ln in head_ops)
    dots = [ln for ln in head_ops if " dot(" in ln]
    assert all("jvp(fused_ce)/while/body" in ln for ln in dots), dots
    assert sorted(re.search(r"= (f32\[[\d,]+\])", ln).group(1)
                  for ln in dots) == sorted([
        f"f32[{rows},{v}]",         # logits
        f"f32[{rows},{h}]",         # d(hidden)
        f"f32[{h},{v}]"]), dots     # dW
    assert sum(" while(" in ln for ln in text.splitlines()) == 1
    # loss-only (eval): the loop alone, one matmul a chunk
    fwd = jax.jit(loss).lower(hidden, w).compile().as_text()
    assert sum(" dot(" in ln for ln in fwd.splitlines()) == 1


# -- the rows kept where they lie: the head under data axes ------------------

def _data_mesh(dp, fsdp):
    from jax.sharding import Mesh
    devs = np.asarray(jax.devices()[:dp * fsdp]).reshape(dp, fsdp)
    return Mesh(devs, ("dp", "fsdp"))


def _rows_case(name):
    """(hidden, w_head, labels, kwargs, differentiate) for one case of
    the head under a data mesh.  8 sequences of 24 rows: two a shard
    under fsdp=4 (3 chunks of 16), so rows 0-1 are one shard's all."""
    batch = {"batch_not_divided": 7, "batch_divided_by_dp_alone": 6}.get(
        name, 8)
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    hidden = jax.random.normal(ks[0], (batch, 24, 32))
    w = jax.random.normal(ks[1], (32, 128)) * 0.1
    labels = jax.random.randint(ks[2], (batch, 24), 0, 128)
    kw = dict(chunk_rows=16)
    if name == "one_shard_all_ignored":
        labels = (labels.at[:2].set(-100).at[3, 5:20].set(-100)
                  .at[6, -3:].set(-100))
    elif name == "softcap":
        w, kw = w * 20.0, dict(chunk_rows=16, logit_softcap=5.0)
    elif name == "bf16_head":               # dW summed in bf16 a shard
        hidden, w = hidden.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    return hidden, w, labels, kw, name != "eval"


@pytest.mark.parametrize("dp,fsdp", [(1, 4), (2, 2)],
                         ids=["fsdp4", "dp2_fsdp2"])
@pytest.mark.parametrize("case", [
    "one_shard_all_ignored", "softcap", "bf16_head", "float32_head",
    "batch_not_divided", "batch_divided_by_dp_alone", "eval"])
def test_fused_ce_under_data_axes_matches_one_device(devices, dp, fsdp,
                                                     case):
    """Under a mesh whose data axes shard the batch the chunk loop runs
    per shard inside one ``shard_map`` (the head weight whole, the sums
    ``psum``-ed, dW reduced once): loss_sum, count, d(hidden) and dW are
    the one-device path's.  A batch the data extent does not divide
    takes the one-device path itself."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from torchacc_tpu.ops.fused import head_row_axes
    hidden, w, labels, kw, differentiate = _rows_case(case)
    batch = hidden.shape[0]
    want_axes = tuple(a for a, n in (("dp", dp), ("fsdp", fsdp)) if n > 1)
    if batch % (dp * fsdp):
        want_axes = ("dp",) if dp > 1 and batch % dp == 0 else ()

    def sums(h, w):             # (loss_sum, count): value and aux
        return fused_linear_cross_entropy(h, w, labels, **kw)

    f = (jax.value_and_grad(sums, argnums=(0, 1), has_aux=True)
         if differentiate else sums)
    # no mesh: the rows whole.  A bf16 head is held to the one-device
    # path that sums dW in float32 (the same bf16 values, a float32
    # head): two bf16 sums in different orders differ by both their
    # roundings
    want = f(hidden, w.astype(jnp.float32))
    assert head_row_axes(batch) == ()
    mesh = _data_mesh(dp, fsdp)
    with jax.sharding.set_mesh(mesh):
        assert head_row_axes(batch) == want_axes
        args = (jax.device_put(hidden, NamedSharding(mesh, P(want_axes))),
                jax.device_put(w, NamedSharding(mesh, P("fsdp"))))
        assert ("shard_map" in str(jax.make_jaxpr(f)(*args))) == bool(
            want_axes)
        got = jax.jit(f)(*args)
    bf16 = hidden.dtype == jnp.bfloat16
    for a, b, name in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                          ("loss_sum", "count", "d_hidden", "d_w")):
        assert a.shape == b.shape, name
        assert a.dtype == (w.dtype if name == "d_w" else b.dtype), name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        atol = 2e-3 if bf16 else 1e-6
        if bf16 and name == "d_w":
            # a shard's partial sums are rounded to bf16 at THEIR size
            # (three chunks a shard, half an ulp each) and cancel to an
            # entry that may be far smaller: four ulps (2^-8) of the
            # largest entry, over 192 rows where the rule's own bf16
            # case sums 48
            atol = 4 * 2.0 ** -8 * np.abs(b).max()
        np.testing.assert_allclose(a, b, rtol=3e-2 if bf16 else 1e-5,
                                   atol=atol, err_msg=name)
    if differentiate and "fsdp" in want_axes:
        # dW lands where the head parameter's shards lie
        assert got[1][1].sharding.spec == P("fsdp")


# -- the chunk as Pallas kernels (interpret mode here) ------------------------

def _as_on_tpu(monkeypatch):
    """The selection asks ``on_tpu()``; the kernels themselves still ask
    ``_common.interpret_mode()`` and run interpreted on the CPU."""
    from torchacc_tpu.ops import fused
    monkeypatch.setattr(fused, "on_tpu", lambda: True)


def _kernel_case(name):
    """(hidden, w_head, labels, kwargs) for one case of the kernel path:
    128 hidden columns, a vocabulary of 384 = three 128-column tiles."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    batch, seq, h, v = 2, 256, 128, 384
    kw = dict(chunk_rows=256)
    if name == "rows_need_the_pad":
        seq = 200                           # 400 rows: 2 chunks, 112 padded
    elif name == "row_tiles":
        kw = dict(chunk_rows=2048)          # 2048 rows: two row tiles of 1024
        batch, seq = 1, 2048
    hidden = jax.random.normal(ks[0], (batch, seq, h))
    w = jax.random.normal(ks[1], (h, v)) * 0.1
    labels = jax.random.randint(ks[2], (batch, seq), 0, v)
    if name == "some_rows_ignored":
        labels = labels.at[0, 3:90].set(-100).at[1, -5:].set(-100)
    elif name == "one_chunk_all_ignored":
        labels = labels.at[0].set(-100)
    elif name == "every_row_ignored":
        labels = jnp.full_like(labels, -100)
    elif name == "bf16_hidden_f32_weight":
        hidden = hidden.astype(jnp.bfloat16)
    elif name == "bf16_head":                # dW summed in bf16
        hidden, w = hidden.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    elif name == "tied_head":
        w = w.T
    elif name == "scan_free":
        kw = dict(chunk_rows=256, scan_free=True)
    return hidden, w, labels, kw


@pytest.mark.parametrize("case", [
    "float32", "some_rows_ignored", "one_chunk_all_ignored",
    "every_row_ignored", "bf16_head", "bf16_hidden_f32_weight",
    "rows_need_the_pad", "row_tiles", "tied_head", "scan_free"])
def test_head_kernels_match_the_xla_body(monkeypatch, case):
    """``head_fwd`` / ``head_dx`` / ``head_dw`` against ``_head_chunk``'s
    XLA body through the whole ``custom_vjp``: loss sum, count,
    d(hidden) and dW."""
    from torchacc_tpu.ops import fused
    hidden, w, labels, kw = _kernel_case(case)
    tied = case == "tied_head"

    def grad():     # a new function each time: a trace is cached by it
        def sums(h, w):
            return fused_linear_cross_entropy(h, w.T if tied else w,
                                              labels, **kw)
        return jax.value_and_grad(sums, argnums=(0, 1), has_aux=True)

    f = grad()
    assert "head_fwd" not in str(jax.make_jaxpr(f)(hidden, w))
    want = f(hidden, w)
    _as_on_tpu(monkeypatch)
    f = grad()
    jaxpr = str(jax.make_jaxpr(f)(hidden, w))
    assert all(k in jaxpr for k in ("head_fwd", "head_dx", "head_dw"))
    assert fused.head_impl(hidden, w.T if tied else w, **kw) == "pallas"
    got = jax.jit(f)(hidden, w)
    bf16 = hidden.dtype == jnp.bfloat16
    for a, b, name in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                          ("loss_sum", "count", "d_hidden", "d_w")):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        atol = 2e-3 if bf16 else 2e-6
        if name == "d_w":
            # to the size of the largest entry: the kernel rounds dlogits
            # to the model dtype (the CPU's default precision does not)
            # and a bf16 sum once where the XLA body rounds each chunk's
            # dW before a bf16 add; float32 sums in another order
            atol = (2.0 ** -8 if bf16 else 2e-6) * max(1, np.abs(b).max())
        np.testing.assert_allclose(a, b, rtol=2e-2 if bf16 else 2e-5,
                                   atol=atol, err_msg=name)
    if case == "every_row_ignored":
        assert float(got[0][0]) == 0.0 and float(got[0][1]) == 0.0
        assert not np.asarray(got[1][0]).any()
        assert not np.asarray(got[1][1]).any()


def test_head_kernels_add_to_the_dw_sum_in_place(monkeypatch):
    """``head_dw`` takes the chunks' sum in and out through one buffer
    (``input_output_aliases``) and rounds the float32 sum once."""
    from torchacc_tpu.ops import fused
    hidden, w, labels, _ = _kernel_case("bf16_head")
    x, y = hidden.reshape(-1, 128)[:256], labels.reshape(-1)[:256]
    before = jnp.full(w.shape, 0.5, jnp.bfloat16)
    tiles = fused._head_tiles(256, 128, 384, 2, 2)
    (_, _, after), _ = fused._head_chunk_kernels(x, y, w, before, tiles)
    (_, _, dw), _ = fused._head_chunk(x, y, w, 0.0, True)
    want = np.asarray(0.5 + dw, np.float32)
    np.testing.assert_allclose(np.asarray(after, np.float32), want,
                               rtol=2.0 ** -8, atol=2.0 ** -8 * want.max())
    jaxpr = str(jax.make_jaxpr(
        lambda acc: fused._head_chunk_kernels(x, y, w, acc, tiles))(
            before))
    call = jaxpr.split("pallas_call[")[-1]
    assert "name=head_dw" in call
    assert "input_output_aliases=((4, 0),)" in call.split("name=head_dw")[0]


@pytest.mark.parametrize("case,want", [
    ("one_device", "pallas"),
    ("cpu_backend", "xla"),
    ("fsdp4", "pallas"),
    ("dp2_fsdp2", "pallas"),
    ("fsdp2_tp2", "xla"),
    ("tp4", "xla"),
    ("batch_not_divided", "xla"),
    ("partly_manual_region", "xla"),
    ("softcap", "xla"),
    ("vocab_not_tiled", "xla"),
    ("float16", "xla"),
    ("eval", "xla"),
])
def test_head_kernels_are_chosen_by_what_the_call_shows(
        devices, monkeypatch, case, want):
    """The kernels where the backend is a TPU, the arrays at the call are
    one device's (one device, or the head's ``shard_map`` manual over the
    whole mesh), the dtype and the tiles fit; the XLA body elsewhere.
    ``head_impl`` (the trainer's word) says what the traced program
    holds."""
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from torchacc_tpu.ops import fused
    if case != "cpu_backend":
        _as_on_tpu(monkeypatch)
    batch = 7 if case == "batch_not_divided" else 8
    v = 200 if case == "vocab_not_tiled" else 256
    dtype = jnp.float16 if case == "float16" else jnp.float32
    hidden = jnp.zeros((batch, 128, 128), dtype)
    w = jnp.zeros((128, v), dtype)
    labels = jnp.zeros((batch, 128), jnp.int32)
    kw = dict(chunk_rows=128)
    if case == "softcap":
        kw["logit_softcap"] = 5.0
    shape = {"fsdp4": (1, 4, 1), "dp2_fsdp2": (2, 2, 1),
             "fsdp2_tp2": (1, 2, 2), "tp4": (1, 1, 4),
             "batch_not_divided": (1, 4, 1),
             "partly_manual_region": (2, 2, 1)}.get(case)

    def sums(h, w, y):
        return fused_linear_cross_entropy(h, w, y, **kw)

    f = sums if case == "eval" else jax.value_and_grad(
        sums, argnums=(0, 1), has_aux=True)

    def read():
        return (fused.head_impl(hidden, w, **kw),
                str(jax.make_jaxpr(f)(hidden, w, labels)))

    if shape is None:
        word, jaxpr = read()
    else:
        mesh = Mesh(np.asarray(devices[:4]).reshape(shape),
                    ("dp", "fsdp", "tp"))
        with jax.sharding.set_mesh(mesh):
            if case == "partly_manual_region":
                # as the 1F1B tick: a region manual over one axis only
                said = []

                def region(h, w, y):
                    said.append(fused.head_impl(h, w, **kw))
                    return f(h, w, y)[0]

                jaxpr = str(jax.make_jaxpr(jax.shard_map(
                    region, mesh=mesh, in_specs=(P("dp"), P(), P("dp")),
                    out_specs=P(), axis_names=frozenset({"dp"}),
                    check_vma=False))(hidden, w, labels))
                word = said[0]
            else:
                word, jaxpr = read()
    if case == "eval":          # the loss alone stays the XLA loop
        assert "head_fwd" not in jaxpr
        return
    assert word == want
    assert ("head_fwd" in jaxpr) == (want == "pallas")
    if shape is not None and case != "partly_manual_region":
        # the head's shard_map: manual over the whole mesh where the row
        # axes are all that is sharded, else over the row axes alone
        manual = re.findall(r"manual_axes=frozenset\(\{([^}]*)\}\)", jaxpr)
        whole = [m for m in manual if m.count("'") == 6]
        assert bool(whole) == (want == "pallas"), manual


@pytest.mark.parametrize("rows,h,v,itemsize,sum_itemsize,want", [
    (2048, 2048, 100352, 2, 2, (1024, 1024, 1024, 1024, 512)),  # OLMo-2
    (2048, 4096, 32768, 2, 2, (1024, 1024, 1024, 1024, 512)),   # Mistral
    (2048, 4096, 32768, 2, 4, (1024, 1024, 1024, 1024, 256)),   # f32 sum
    (2048, 4096, 128256, 2, 2, (1024, 256, 1024, 256, 256)),    # 2^8 x 501
    (256, 128, 384, 4, 4, (256, 128, 256, 128, 128)),
    (2048, 4096, 32768, 4, 4, None),    # head_dw's rows whole: 64 MiB
    (8192, 4096, 32768, 2, 2, None),    # a scan_free chunk of 8192 rows
    (2048, 4096, 32000, 2, 2, None),    # 32000 = 128 x 250: tiles; below
    (2000, 4096, 32768, 2, 2, None),    # rows the lanes do not tile
])
def test_head_tiles_come_from_the_geometry(rows, h, v, itemsize,
                                           sum_itemsize, want):
    """Tiles are the largest 128-multiples that divide (rows, vocab)
    and fit the VMEM budget at (hidden, itemsize); None sends the chunk
    to the XLA body."""
    from torchacc_tpu.ops import fused
    got = fused._head_tiles(rows, h, v, itemsize, sum_itemsize)
    if (rows, v) == (2048, 32000):
        # 128 divides 32000: tiled by 256-column tiles (32000 = 2^8 x 125)
        assert got == (1024, 256, 1024, 256, 256)
        return
    assert got == want
    if got:
        fr, fv, xr, xv, wv = got
        assert rows % fr == rows % xr == v % fv == v % xv == v % wv == 0


def test_trainer_says_which_head_it_traced(devices):
    """``traced the loss … head=whole|sharded kernels=pallas|xla``, once
    a program, and ``Trainer.head_impl`` beside ``head_rows``."""
    import logging

    import optax

    from torchacc_tpu.utils.logger import logger
    mc = get_preset("llama-tiny", vocab_size=128, hidden_size=64,
                    num_layers=1, num_heads=4, num_kv_heads=2,
                    intermediate_size=128, dtype=jnp.float32)
    lines = []
    handler = logging.Handler(level=logging.INFO)
    handler.emit = lambda record: lines.append(record.getMessage())
    level = logger.level
    logger.addHandler(handler)
    logger.setLevel(logging.INFO)
    try:
        t, _ = accelerate(mc, None, ta.Config(), optimizer=optax.sgd(0.1))
        assert t.head_impl is None and t.head_rows is None
        t.init()
        batch = {"input_ids": np.zeros((8, 32), np.int32)}
        t.step(batch)
        t.step(batch)
    finally:
        logger.removeHandler(handler)
        logger.setLevel(level)
    said = [ln for ln in lines if ln.startswith("traced the loss")]
    assert len(said) == 1 and said[0].endswith(
        f"head={t.head_rows} kernels=xla"), said
    assert t.head_impl == "xla"             # the CPU backend
