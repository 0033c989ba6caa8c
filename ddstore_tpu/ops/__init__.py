"""TPU kernels and compute ops (Pallas + XLA fallbacks)."""

import jax


def on_chip() -> bool:
    """Whether the kernels run compiled in this process (a TPU backend), or
    interpreted, beside the XLA references, elsewhere. Asked at trace time."""
    return jax.default_backend() == "tpu"
