"""The chip's published peaks, keyed by JAX's ``device_kind``."""

from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def lookup(kind: str, path=PEAKS) -> dict:
    """Peaks of one device kind; a kind the table does not hold is an
    error, never a default."""
    table = json.loads(pathlib.Path(path).read_text())["kinds"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(table)}")
    return table[kind]
