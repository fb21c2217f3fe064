"""Recurrent-state bytes over state + key/value bytes of the window's
decode steps, in percent: how much of what a decode step moves for its
context is the fixed-size state.  From the counts the program puts on
its ``params.span`` spans (``state_bytes``: the state its state-space
layers read and wrote; ``ctx_attended``: the positions one attention
layer's queries attended) and the configuration's layer kinds and k/v
row.  None where the program has no such counts."""

from chipbench.rooflines import ssm_common as common


def read(observed, params):
    if observed.get("kind") != params["kind"]:
        return None
    total = common.decode_counts(observed, params["span"])
    if not total.get("state_bytes"):
        return None
    pub = observed["published"]
    kv = (total["ctx_attended"] * common.layers(observed, "*")
          * 2 * observed["kv_heads"] * pub["head_dim"] * 2)
    print(f"[state_bytes] over the window's decode steps: "
          f"{total['state_bytes'] / 1e9:.3f} GB of state, "
          f"{kv / 1e9:.3f} GB of keys and values "
          f"({total['ctx_attended']} positions attended a layer)",
          flush=True)
    return 100.0 * total["state_bytes"] / (total["state_bytes"] + kv)
