"""The decode step's update of the slots' recurrent state."""

from chipbench.rooflines import ssm_common as common


def required(observed):
    """Per chip over the window: every decoding slot's float32 state is
    read and written once a state-space layer a step (the (slot, step)
    pairs from the program's own counts), and a state value costs 5
    FLOPs (decay, the outer product's term, its sum; the readout's
    product and sum).  The convolution's rows are the ``ssm_conv``
    scope's, not this one's."""
    pairs = common.decode_counts(observed).get("slot_steps", 0.0)
    if not pairs:
        return {"flops": 0.0, "bytes": 0.0}
    values = pairs * common.layers(observed) * common.state_values(observed)
    return {"flops": 5.0 * values, "bytes": 2 * 4.0 * values}
