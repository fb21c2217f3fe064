"""What the rooflines and readers of the ``ssm_attn_moe_decoder`` family
share: the state-space layers of the cut model, the sizes of one slot's
state, and the program's own counts of its decode steps."""

from chipbench import program_trace


def layers(observed, kind="M"):
    """Layers of ``kind`` ('M' state-space, 'E' experts, '*' attention)
    among the cell's first ``depth``."""
    pattern = observed["published"].get("hybrid_override_pattern", "")
    return pattern[:observed["depth"]].count(kind)


def state_values(observed):
    """Values of one slot's recurrent state in one layer: heads x
    channels a head x state size."""
    pub = observed["published"]
    return (pub["mamba_num_heads"] * pub["mamba_head_dim"]
            * pub["ssm_state_size"])


def conv_values(observed):
    """Values of one slot's carried convolution rows in one layer."""
    pub = observed["published"]
    width = (pub["mamba_num_heads"] * pub["mamba_head_dim"]
             + 2 * pub["n_groups"] * pub["ssm_state_size"])
    return (pub["conv_kernel"] - 1) * width


def decode_counts(observed, span="serve/deliver"):
    """Sums over the window's DECODE steps of what the program puts on
    its ``serve/deliver`` spans: ``state_bytes`` (recurrent state read and
    written), ``ctx_attended`` (positions an attention layer's queries
    attended), and ``slot_steps`` — the (slot, step) pairs behind them,
    from ``state_bytes`` over what one slot's state takes in
    ``ssm_layers`` layers.  Empty where the program has no such counts
    (the parent, another family)."""
    parsed = program_trace.get(observed)
    total = {}
    if parsed is None or "mamba_num_heads" not in observed["published"]:
        return total
    a_slot = 2.0 * (4 * state_values(observed) + 2 * conv_values(observed))
    lo, hi = parsed["lo"], parsed["hi"]
    for name, start, _, stats in parsed["host"]:
        if (name == span and lo <= start <= hi and "state_bytes" in stats
                and stats.get("kind") == "decode"):
            total["state_bytes"] = (total.get("state_bytes", 0)
                                    + int(stats["state_bytes"]))
            total["ctx_attended"] = (total.get("ctx_attended", 0)
                                     + int(stats.get("ctx_attended", 0)))
            total["slot_steps"] = (
                total.get("slot_steps", 0.0) + int(stats["state_bytes"])
                / (a_slot * max(int(stats["ssm_layers"]), 1)))
    return total
