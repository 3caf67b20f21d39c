"""Where the Pallas kernels run: the one place that decides.

* TPU: every kernel runs compiled (Mosaic), and the stats producers always
  take the Gram kernel — nothing falls back to interpret mode or to the jnp
  reference.
* CPU (the test backend): kernels run in the Pallas interpreter, and the
  stats producers default to the jnp reference, the oracle that the CPU
  golden tests pin.  ``use_pallas=True`` asks for the interpreted kernel.
* Any other backend has no path here and raises.
"""

from __future__ import annotations

import jax


def interpret() -> bool:
    """``interpret=`` for every ``pallas_call`` of this package."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"no Pallas path for backend {backend!r}: the kernels run compiled "
        f"on a TPU, or in interpret mode on the CPU for tests"
    )


def stats_use_pallas(use_pallas: bool | None) -> bool:
    """Resolve a stats producer's ``use_pallas``.

    ``None`` (the producers' default) is the compiled kernel on a TPU and
    the jnp reference on the CPU; ``True`` on the CPU is the interpreted
    kernel.  ``False`` on a TPU is refused: there the stats pass is the
    kernel.
    """
    if interpret():
        return bool(use_pallas)
    if use_pallas is False:
        raise ValueError(
            "use_pallas=False selects the jnp reference, a CPU test path; "
            "on a TPU the stats pass runs the compiled Gram kernel"
        )
    return True
