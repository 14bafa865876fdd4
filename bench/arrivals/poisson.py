"""Open loop: sessions start as a Poisson process of ``rate`` sessions/s
(``{"kind": "poisson", "rate": r}``), one sample path drawn from the
mix's fixed stream, so every seed sends at the same times."""

import numpy as np

CLOSED = False


def starts(spec, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """Start times in [0, seconds): exponential gaps of mean 1 / rate."""
    mean = 1.0 / float(spec["rate"])
    t = np.cumsum(rng.exponential(mean, int(seconds / mean * 2) + 16))
    while t[-1] < seconds:
        t = np.concatenate([t, t[-1] + np.cumsum(rng.exponential(
            mean, len(t)))])
    return t[t < seconds]
