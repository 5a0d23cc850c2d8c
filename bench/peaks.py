"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

The table is ``peaks.json`` beside this file, each entry naming its source.
A device that is not in the table is an error, never a default."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str, table: Path = TABLE) -> dict:
    peaks = json.loads(table.read_text())
    if device_kind not in peaks:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(peaks)}")
    return peaks[device_kind]
