"""chip_smoke.py's body at a tiny size on the CPU, with the Pallas kernels
in interpret mode: every check of the chip run except the device."""
import jax

import chip_smoke
from repro.configs import get_config, reduced
from repro.kernels import ops


def test_serve_smoke_reduced(monkeypatch):
    monkeypatch.setattr(ops, "_mode", lambda force: force or "interpret")
    # kernels dispatch when traced: drop traces made under the real _mode
    # before, and those made under the patch after
    jax.clear_caches()
    try:
        report = chip_smoke.serve_smoke(
            reduced(get_config(chip_smoke.ARCH)), log=lambda s: None)
    finally:
        jax.clear_caches()
    assert report["impl"]["chunk codec"] == "interpret"
    assert report["impl"]["decode attention"].startswith("jnp")
    assert set(report["reference"]) == set(range(chip_smoke.N_CONTEXTS))
    assert report["paged"]["calls"] == (chip_smoke.N_CONTEXTS
                                        * chip_smoke.CALLS_PER_CONTEXT)
    assert report["slot"]["pipelined_restores"] > 0
