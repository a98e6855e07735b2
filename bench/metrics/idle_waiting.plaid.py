"""``idle_waiting.steady`` in the PLAID cell, where it moves ``qps``:
PLAID's ``p50_ms`` swings too widely at 0.8× its knee to be judged
(PERF.md §2)."""

import layers

read = layers.reader("idle_waiting.steady")
