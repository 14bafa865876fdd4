"""Closed loop: ``clients`` sessions start at once
(``{"kind": "clients", "clients": n}``), and each sends its next request
the moment its last one has ended."""

import numpy as np

CLOSED = True


def starts(spec, seconds: float, rng: np.random.Generator) -> np.ndarray:
    return np.zeros(int(spec["clients"]))
