"""Published per-chip peaks, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


def peaks_for(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The peaks of one chip; a kind missing from the table is an error."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; known: {sorted(table)}")
    return table[device_kind]
