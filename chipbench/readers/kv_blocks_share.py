"""Cache blocks held, by layer kind, over what the same sequences would
hold if every layer kept its whole context, in percent, averaged over
the window's admissions.  From the counts the program puts on its
``params.span`` spans (``blocks_full``: blocks of the global layers'
pools in use, each one block in every global layer; ``blocks_window``:
blocks of the sliding layers' pools, each one block in every sliding
layer) and the configuration's layer kinds.  None where the program has
no such counts."""

from chipbench import program_trace


def read(observed, params):
    if observed.get("kind") != params["kind"]:
        return None
    parsed = program_trace.get(observed)
    if parsed is None:
        return None
    kinds = observed["published"].get("layer_types", [])[:observed["depth"]]
    sliding = sum(k == "sliding_attention" for k in kinds)
    full = len(kinds) - sliding
    lo, hi = parsed["lo"], parsed["hi"]
    held = whole = n = 0
    for name, start, _, stats in parsed["host"]:
        if name == params["span"] and lo <= start <= hi \
                and "blocks_window" in stats and int(stats["blocks_full"]):
            held += (full * int(stats["blocks_full"])
                     + sliding * int(stats["blocks_window"]))
            whole += (full + sliding) * int(stats["blocks_full"])
            n += 1
    if not whole:
        return None
    print(f"[kv_blocks] over {n} admissions in the window: {held} block-"
          f"layers held of {whole} with every layer global ({full} global, "
          f"{sliding} sliding layers)", flush=True)
    return 100.0 * held / whole
