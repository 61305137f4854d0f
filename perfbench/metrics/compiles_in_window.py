"""Programs JAX lowered inside the measured window (compiled, or loaded from
the persistent cache): 0 when set-up warmed every shape. Moves
``itl_p95_ms``."""


def read(view):
    n = view.served.compiles
    return float(n) if n >= 0 else None
