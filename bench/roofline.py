"""Work counts and peaks for roofline shares.

A roofline share is the least time the chip could take over the time it
took. Counts here are the least the algorithm must move, whatever
implements it, so a faster implementation cannot change the yardstick.
"""
from __future__ import annotations

import json
import pathlib

POINT_BYTES = 12    # float32 x, y, z
LABEL_BYTES = 4     # int32
CORE_BYTES = 1      # bool


def eps_pass_bytes(n: int, neighbor_total: int) -> int:
    """Least HBM bytes of one eps min-label pass over n query points: read
    each query point and write its result, and for each of the
    ``neighbor_total`` (query, neighbor) pairs, the neighbor itself
    included, read the neighbor's point, label and core flag."""
    return (int(n) * (POINT_BYTES + LABEL_BYTES)
            + int(neighbor_total) * (POINT_BYTES + LABEL_BYTES + CORE_BYTES))


def peak(device_kind: str) -> dict:
    """The chip's published peaks; a device kind not in the table is an
    error, never a default."""
    table = json.loads((pathlib.Path(__file__).with_name("peaks.json"))
                       .read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table[device_kind]
