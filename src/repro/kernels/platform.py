"""The one test of where the Pallas kernels run compiled.

On a TPU every kernel wrapper lowers its kernel through Mosaic to the chip.
Elsewhere (the CPU test host) the wrappers take their jnp lowering or the
Pallas interpreter instead.  Every wrapper and call site asks this module,
so a chip run and a CPU run differ in exactly one place.
"""

from __future__ import annotations

import jax


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"
