"""The work a batch needs, from shapes, and the chip's peaks.

A forward's arithmetic is its model plug-in's ``forward_flops``
(``models/<name>.py``).  ``gather_bytes`` is the least HBM traffic of a
feature gather: every row gathered is read once and written once, and every
prefetched miss row is read from its staged pack and written into place
once.

``peaks`` reads ``peaks.json``; a device kind that is not in the table is
an error, never a default.
"""

from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str, path: pathlib.Path = PEAKS) -> dict:
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in {path.name}")
    return table[device_kind]


def gather_bytes(gathered_rows: int, prefetched_rows: int, row_bytes: int) -> float:
    return 2.0 * (gathered_rows + prefetched_rows) * row_bytes
