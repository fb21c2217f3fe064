"""torchacc_tpu — a TPU-native training-acceleration framework.

Brand-new JAX/XLA/Pallas implementation of the capabilities of the
reference framework (AlibabaPAI/torchacc): one ``Config`` describing
compute/memory/data/parallelism, a named device mesh mapping strategy axes
onto the ICI/DCN topology, an ``accelerate()`` entry point that returns a
ready-to-train sharded step function, Pallas flash-attention kernels with
context parallelism (Ulysses / Ring / 2D), pipeline parallelism inside
jit, and sharded checkpointing with offline consolidate/reshard.

Where the reference monkeypatches torch (``patch_fa``, autocast patches,
LazyTensor graph cuts — torchacc/__init__.py:135-138), JAX gives the same
by construction: jit is the trace boundary, dtype policy is explicit, and
optimizers run inside the compiled program (no syncfree variants needed).
"""

__version__ = "0.1.0"

from torchacc_tpu import data, errors, models, ops, parallel, resilience
from torchacc_tpu.config import (
    ComputeConfig,
    Config,
    ConfigError,
    DataConfig,
    DistConfig,
    DPConfig,
    EPConfig,
    FSDPConfig,
    MemoryConfig,
    ObsConfig,
    PerfConfig,
    PPConfig,
    ResilienceConfig,
    ServeConfig,
    SPConfig,
    TPConfig,
)
from torchacc_tpu.utils.logger import logger

__all__ = [
    "Config",
    "ConfigError",
    "ComputeConfig",
    "MemoryConfig",
    "DataConfig",
    "DistConfig",
    "DPConfig",
    "TPConfig",
    "FSDPConfig",
    "PPConfig",
    "SPConfig",
    "EPConfig",
    "ObsConfig",
    "PerfConfig",
    "ResilienceConfig",
    "ServeConfig",
    "accelerate",
    "errors",
    "logger",
    "ops",
    "parallel",
    "resilience",
]


def accelerate(*args, **kwargs):
    """Entry point (reference: ``torchacc.accelerate`` accelerate.py:49-149).
    Imported lazily to keep ``import torchacc_tpu`` light."""
    try:
        from torchacc_tpu.train.accelerate import accelerate as _accelerate
    except ModuleNotFoundError as e:
        raise NotImplementedError(
            "torchacc_tpu.train is not available in this build"
        ) from e
    return _accelerate(*args, **kwargs)
