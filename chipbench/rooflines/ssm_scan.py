"""The chunked-scan kernel of the state-space layers' prefill chunks."""

from chipbench.rooflines import ssm_common as common
from chipbench.rooflines.sparse_select_common import chunks, summed

SUB_CHUNK = 128


def required(observed):
    """Per chip over the window, chunk by chunk (the driver's record of
    the window's prefill chunks, ``n`` real positions each).  A chunk
    takes ``ceil(n / 128)`` sub-chunks of Q = 128 positions; per
    sub-chunk and head: ``(C B^T)`` shared by the group's heads
    (2 Q Q N / heads a group), the masked product with the inputs
    (2 Q Q P), the state's readout (2 Q N P) and its update (2 Q P N).
    Bytes a chunk a layer, what the algorithm has to move and no more:
    the inputs ``x``, ``B`` and ``C`` once and the output once in the
    compute dtype (bf16), and the slot's float32 state read and written
    once.  (The kernel reads ``x`` in two layouts and writes its output
    in float32; counted so, it read 110% of the HBM's peak — my chip run,
    PR 42: XLA keeps a chunk's 4 MiB operands in VMEM between the fusion
    that makes them and the kernel, so only the state and the output
    cross the HBM at its pace.)"""
    pub = observed["published"]
    if "mamba_num_heads" not in pub:
        return {"flops": 0.0, "bytes": 0.0, "least_s": 0.0}
    hm, p, n_ = (pub["mamba_num_heads"], pub["mamba_head_dim"],
                 pub["ssm_state_size"])
    g, q = pub["n_groups"], SUB_CHUNK
    a_head = 2.0 * q * q * n_ * g / hm + 2.0 * q * q * p + 4.0 * q * n_ * p
    layers = common.layers(observed)

    def call(n):
        subs = -(-n // q)
        rows = subs * q
        return (layers * subs * hm * a_head,
                layers * (rows * 2.0 * (2 * hm * p + 2 * g * n_)
                          + 2 * 4.0 * common.state_values(observed)))

    return summed((call(n) for _, n in chunks(observed)), observed["peaks"])
