"""repro: DPS (dynamic precision scaling) training system in JAX."""
