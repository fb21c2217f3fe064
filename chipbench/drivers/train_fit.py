"""Driver ``train_fit``: ``ta.accelerate()`` -> ``Trainer.fit`` on a seeded feed.

One trainer object is built from the benchmark's seeded weights, driven
through its first ``check_steps`` steps by ``Trainer.fit`` on the feed's
first batches (this is also the warm-up: the step compiles here), read
for the correctness comparison, and handed to the window, which is one
more ``Trainer.fit`` call on the same feed, ended by the feed when the
clock passes the deadline.  The window is closed by
``block_until_ready`` on the last step's state.  After it the program's
state is freed and the plain reference follows the same first steps from
the same weights and batches.
"""

from __future__ import annotations

import re
import time

import numpy as np

from chipbench import program


class Feed:
    """The loader the trainer iterates: ``distinct`` seeded batches of
    uniform token ids, every row different, cycled.  ``serve`` sets how
    many batches the next iteration yields: a count (the check steps) or
    a deadline on the clock (the window)."""

    def __init__(self, seed, vocab, batch, seq, distinct):
        rng = np.random.default_rng(seed)
        self.batches = [
            {"input_ids": rng.integers(0, vocab, size=(batch, seq),
                                       dtype=np.int64).astype(np.int32)}
            for _ in range(distinct)]
        self.cursor = 0
        self._count = 0
        self._deadline = None

    def serve(self, count=None, deadline=None):
        self._count, self._deadline = count, deadline

    def __iter__(self):
        n = 0
        while True:
            if self._deadline is not None:
                if time.perf_counter() >= self._deadline:
                    return
            elif n >= self._count:
                return
            n += 1
            batch = self.batches[self.cursor % len(self.batches)]
            self.cursor += 1
            yield batch


def _kernel_names(hlo_text: str):
    """Instruction names of the step's Pallas kernels, from the compiled
    program's own text."""
    return re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
        hlo_text)


def _adam_mu(opt_state):
    """The first-moment tree inside an optax state, wherever it sits."""
    found = []

    def visit(node):
        if hasattr(node, "mu") and hasattr(node, "nu"):
            found.append(node.mu)
            return
        if isinstance(node, (tuple, list)):
            for child in node:
                visit(child)
    visit(opt_state)
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return found[0]


def _norms(tree):
    import jax
    import jax.numpy as jnp
    flat = program.flat_paths(tree)
    out = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for k, x in t.items()})(flat)
    return {k: float(v) for k, v in out.items()}


def run(ctx):
    import jax
    import jax.numpy as jnp

    import torchacc_tpu as ta

    cell, traffic = ctx.cell, ctx.cell.traffic
    weights, layout = cell.weights(), cell.layout()
    published, depth = cell.published, cell.depth
    batch, seq = traffic["batch"], traffic["seq"]
    opt = traffic["optimizer"]
    check_steps = traffic["check_steps"]

    mc = program.model_config(published, depth, max_seq_len=seq,
                              **traffic.get("model_overrides", {}))
    cfg = program.framework_config(traffic["settings"], ctx.seed)
    feed = Feed(ctx.seed, published["vocab_size"], batch, seq,
                traffic["distinct_batches"])
    trainer, loader = ta.accelerate(mc, feed, cfg,
                                    optimizer=program.optimizer(opt))
    if len(trainer.mesh.devices.flat) != cell.chips:
        raise SystemExit(f"chipbench: the trainer's mesh has "
                         f"{len(trainer.mesh.devices.flat)} devices, the "
                         f"cell asks for {cell.chips}")

    # weights: made here from the seed, in one jitted call, straight
    # into the trainer's own shardings
    trainer.resolve_shardings()
    key = weights.base_key(ctx.seed)
    make = jax.jit(
        lambda k: layout.to_program_params(
            weights.make(k, published, depth, jnp.float32), mc),
        out_shardings=trainer.state_shardings.params)
    with jax.sharding.set_mesh(trainer.mesh):
        params = make(key)
    trainer.init_from_params(params)
    del params
    n_params = weights.param_count(published, depth)
    ctx.note(f"[train] depth={depth} params={n_params / 1e6:.1f}M "
             f"mesh={dict(trainer.mesh.shape)} batch={batch} seq={seq} "
             f"scan_layers={mc.scan_layers}")

    # -- the first steps, through the window's own call and feed ----------
    losses = []
    feed.serve(count=1)
    losses += [r["loss"] for r in trainer.fit(loader, log_every=1)]
    ctx.note("[train] first step done (the step is compiled)")
    mu_norms = _norms(_adam_mu(trainer.state.opt_state))
    feed.serve(count=check_steps - 1)
    losses += [r["loss"] for r in trainer.fit(loader, log_every=1)]
    t_check = time.perf_counter()
    # the parameters' change, leaf by leaf against the remade start
    names = layout.canonical_names(mc)
    diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    delta_norms = {}
    with jax.sharding.set_mesh(trainer.mesh):
        for path, leaf in program.flat_paths(trainer.state.params).items():
            remake = jax.jit(
                lambda k, n=names[path]: layout.leaf_to_program(
                    n, weights.make_leaf(k, published, depth, n), mc)[1],
                out_shardings=leaf.sharding)
            delta_norms[path] = float(diff(leaf, remake(key)))
    got = dict(
        losses=[float(x) for x in losses],
        grad_norms={names[p]: v / (1.0 - opt["b1"])
                    for p, v in mu_norms.items()},
        delta_norms={names[p]: v for p, v in delta_norms.items()})
    ctx.exclude_from_setup(time.perf_counter() - t_check)
    ctx.note(f"[train] {check_steps} steps through fit, losses "
             f"{[round(x, 4) for x in got['losses']]}")

    # the kernels, by the names the compiled step itself gives them
    sample = {k: jax.device_put(v, trainer._batch_shardings(
        {k: v})[k]) for k, v in feed.batches[0].items()}
    with jax.sharding.set_mesh(trainer.mesh):
        hlo = trainer._train_step.lower(trainer.state, sample) \
            .compile().as_text()
    kernels = _kernel_names(hlo)
    if not ctx.rehearse and len(kernels) < traffic["min_kernels"]:
        raise SystemExit(
            f"chipbench: the compiled step holds {len(kernels)} "
            f"tpu_custom_call (at least {traffic['min_kernels']} expected):"
            f" attention took another path")
    del hlo, sample

    # -- the window ---------------------------------------------------------
    step0 = int(trainer.state.step)    # the state's own counter
    trainer.blocked.take_ms()
    with ctx.window() as win:
        feed.serve(deadline=win.t0 + ctx.window_seconds)
        history = trainer.fit(loader, log_every=traffic["log_every"])
        jax.block_until_ready(trainer.state)
    steps = int(trainer.state.step) - step0
    blocked_ms = (sum(r.get("host_blocked_ms", 0.0) for r in history)
                  + trainer.blocked.take_ms())
    window_losses = [float(r["loss"]) for r in history]
    tokens = steps * batch * seq
    ctx.note(f"[train] window {win.seconds:.3f}s steps={steps} "
             f"step_s={win.seconds / max(steps, 1):.4f} "
             f"losses first/last {window_losses[:1]} {window_losses[-1:]}")
    peak = ctx.memory_peak()

    # -- free the program's state, then follow it with the reference -------
    trainer.state = None
    del trainer, loader, history
    ctx.free_device_memory()
    ref = cell.reference()
    sizes = ref.sizes_of(published)
    ref_batches = [feed.batches[i]["input_ids"] for i in range(check_steps)]
    make_ref = ctx.reference_weights_maker(published, depth)
    t_ref = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        want = ref.train_readings(make_ref, sizes, ref_batches, opt)
    ctx.note(f"[train] reference followed {check_steps} steps in "
             f"{time.perf_counter() - t_ref:.1f}s, losses "
             f"{[round(x, 4) for x in want['losses']]}")
    limits = cell.config["limits"]["train"]
    checks = compare(got, want, limits)
    for which in ctx.controls():
        with jax.default_matmul_precision("highest"):
            ctrl = ref.train_readings(make_ref, sizes, ref_batches, opt,
                                      ref.lower_precision_dot(which))
        for reading in compare(ctrl, want, limits):
            ctx.control_reading(which, *reading)
    finite = all(np.isfinite(window_losses)) and steps > 0
    falling = (not window_losses
               or float(np.mean(window_losses)) < got["losses"][0])
    checks.append(("window_losses_finite_and_below_first",
                   0.0 if (finite and falling) else 1.0, 0.5))
    return dict(
        checks=checks, attempted=steps + check_steps,
        failed=0 if finite else steps,
        memory_peak_bytes=peak,
        end_to_end={"train_tokens_per_s_per_chip":
                    tokens / win.seconds / cell.chips},
        observed=dict(kind="train", steps=steps, tokens=tokens,
                      window_s=win.seconds, chips=cell.chips,
                      host_blocked_ms=blocked_ms, kernel_names=kernels,
                      batch=batch, seq=seq, depth=depth,
                      published=published,
                      family=cell.config["family"]))


def compare(got, want, limits):
    """Each number compared, beside its limit: (name, value, limit).

    Losses: relative gap per step.  Norms: by the worst leaf, the gap
    between the program's norm and the reference's (not the norm of the
    difference) over the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    out = [(f"loss_step{i + 1}_rel", abs(a - b) / abs(b), limits["loss_rel"])
           for i, (a, b) in enumerate(zip(got["losses"], want["losses"]))]

    def worst(a, b):
        med = float(np.median(list(b.values())))
        return max(abs(a[k] - b[k]) / max(b[k], med) for k in b)

    out.append(("grad_norm_worst_leaf_rel",
                worst(got["grad_norms"], want["grad_norms"]),
                limits["grad_norm_rel"]))
    out.append(("param_change_norm_worst_leaf_rel",
                worst(got["delta_norms"], want["delta_norms"]),
                limits["delta_norm_rel"]))
    return out
