"""A kernel's share of its roofline: the least time the chip could take
for the operations and bytes the algorithm requires (``rooflines/<params.
roofline>.required(observed)`` -> per-chip flops and bytes over the
window) over the kernel's device time.  Prints which bound holds."""

from chipbench import spec
from chipbench.readers import kernel_common


def read(observed, params):
    per_device = kernel_common.kernel_and_busy(observed, params)
    if per_device is None or observed.get("peaks") is None:
        return None
    need = spec.roofline(params["roofline"]).required(observed)
    peaks = observed["peaks"]
    t_flops = need["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = need["bytes"] / peaks["hbm_bytes_per_s"]
    least = need.get("least_s", max(t_flops, t_bytes))
    kernel_s = max(k for k, _ in per_device) * 1e-9
    print(f"[roofline] {params['roofline']}: "
          f"{'compute' if t_flops >= t_bytes else 'bandwidth'}-bound; "
          f"required {need['flops'] / 1e12:.3f} TFLOP, "
          f"{need['bytes'] / 1e9:.3f} GB a chip in the window; least "
          f"{least * 1e3:.3f} ms, kernel {kernel_s * 1e3:.3f} ms",
          flush=True)
    return 100.0 * least / kernel_s
