"""A scope's share of its roofline: the least time the chip could take
for the operations and bytes the algorithm requires
(``rooflines/<params.roofline>.required(observed)``, per chip over the
window) over the device time under ``params.scopes``, worst device."""

from chipbench import spec
from chipbench.readers import scope_time_share


def read(observed, params):
    per_device = scope_time_share.scope_time(observed, params)
    if per_device is None or observed.get("peaks") is None:
        return None
    scope_s = max(t for t, _ in per_device) * 1e-9
    if not scope_s:
        return None
    need = spec.roofline(params["roofline"]).required(observed)
    peaks = observed["peaks"]
    t_flops = need["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = need["bytes"] / peaks["hbm_bytes_per_s"]
    least = max(t_flops, t_bytes)
    print(f"[roofline] {params['roofline']}: "
          f"{'compute' if t_flops >= t_bytes else 'bandwidth'}-bound; "
          f"required {need['flops'] / 1e12:.3f} TFLOP, "
          f"{need['bytes'] / 1e9:.3f} GB a chip in the window; least "
          f"{least * 1e3:.3f} ms, scopes {params['scopes']} "
          f"{scope_s * 1e3:.3f} ms", flush=True)
    return 100.0 * least / scope_s
