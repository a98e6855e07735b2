"""``jax_compiles.steady`` in the PLAID cell, where it moves ``qps``."""

import layers

read = layers.reader("jax_compiles.steady")
