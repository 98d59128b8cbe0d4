"""Chip peaks and the least work of one labelling.

``peak(kind)`` reads ``peaks.json``, keyed by ``device_kind`` as JAX
reports it; a kind missing from the table is an error, never a default.
``least_solve_bytes`` is what any labelling of a graph must move: every
edge read once (two int32 endpoints) and every label written once. It
depends on |V| and |E| alone, so it prices the same work whatever
implements it.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    """The device kind has no row in ``peaks.json``."""


def peak(kind: str, path: Path = PEAKS) -> dict:
    table = json.loads(Path(path).read_text())
    if kind not in table:
        raise UnknownDevice(f"no peaks for device kind {kind!r} in "
                            f"{path.name}; have {sorted(table)}")
    return table[kind]


def least_solve_bytes(num_nodes: int, num_edges: int) -> int:
    return 8 * int(num_edges) + 4 * int(num_nodes)
