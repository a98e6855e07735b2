"""``h2d_kb`` in the PLAID cell, where it moves ``qps``: PLAID's
``p50_ms`` swings too widely at 0.8× its knee to be judged (PERF.md §2)."""

import layers

read = layers.reader("h2d_kb")
