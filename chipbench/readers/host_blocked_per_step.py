"""Host-blocked milliseconds a step, from the trainer's own meter
(``Trainer.blocked``), summed over the window by the driver."""


def read(observed, params):
    if observed.get("kind") != "train" or not observed["steps"]:
        return None
    return observed["host_blocked_ms"] / observed["steps"]
