"""A kernel's device time over the device's busy time, worst device."""

from chipbench.readers import kernel_common


def read(observed, params):
    per_device = kernel_common.kernel_and_busy(observed, params)
    if per_device is None:
        return None
    return max(100.0 * k / b for k, b in per_device if b)
