"""On-chip checks: run on the REAL accelerator, not the CPU mesh.

The main suite (tests/) runs on an 8-device emulated CPU mesh, so Pallas
kernels run in interpret mode and host-offload placement never executes.
This directory is the complement to ``chip_smoke.py``: a handful of fast
checks of paths only visible on hardware that the smoke does not drive —
window and softcap flash kernels, pinned_host offload placement, the tp
fused-CE manual-collective lowering, one small train step and a cached
decode.  Run it through the chip tool, after ``chip_smoke.py`` passes:

    python -m pytest tests_tpu -q

No chip is a failure here, not a skip.  The driver never runs this
directory; it is outside tier-1.
"""

import pytest

from torchacc_tpu.utils.compile_cache import enable_compile_cache

enable_compile_cache()


def pytest_configure(config):
    config.addinivalue_line("markers", "tpu_smoke: on-chip checks")


@pytest.fixture(scope="session")
def chip():
    """The real accelerator device."""
    import jax

    dev = jax.devices()[0]
    assert dev.platform == "tpu", (
        f"tests_tpu needs the chip; jax.devices()[0] is {dev.platform!r}")
    return dev
