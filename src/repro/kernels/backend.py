"""The one place that asks JAX which platform the kernels run on.

``default_impl`` (``kernels/ops.py``) and every Pallas kernel's
``interpret`` flag derive from ``on_tpu``: on the TPU the kernels compile
with Mosaic; anywhere else they run in the Pallas interpreter. A missing
backend raises — it is never read as "CPU".
"""

from __future__ import annotations

from typing import Optional

import jax


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Pallas ``interpret`` flag: an explicit choice wins, otherwise
    interpret everywhere but the TPU."""
    return (not on_tpu()) if interpret is None else bool(interpret)
