"""``device_idle.steady`` in the live cell."""

import layers

read = layers.reader("device_idle.steady")
