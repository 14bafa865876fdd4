"""repro — hlslib-style library abstractions on jax/Pallas."""
