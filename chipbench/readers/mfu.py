"""Model FLOP/s utilisation: the operations forward and backward
require for a token (``rooflines/<the configuration's family>``;
recomputation not counted) x tokens a second a chip / the chip's peak."""

from chipbench import spec


def read(observed, params):
    if observed.get("kind") != "train" or observed.get("peaks") is None:
        return None
    per_token = spec.roofline(observed["family"]).train_flops_per_token(
        observed["published"], observed["depth"], observed["seq"])
    rate = observed["tokens"] / observed["window_s"] / observed["chips"]
    return 100.0 * per_token * rate / observed["peaks"]["bf16_flops_per_s"]
