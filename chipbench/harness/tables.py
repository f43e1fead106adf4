"""The price table (chipbench/tables/prices.json), each entry with its
source. A device kind that is not in the table is an error, not a default."""
from __future__ import annotations

import json
import os

TABLES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tables")


class UnknownDevice(LookupError):
    pass


def _load(name: str, root: str) -> dict:
    with open(os.path.join(root, name)) as f:
        return json.load(f)


def prices(device_kind: str, root: str = TABLES) -> dict:
    """{'chip_hour', 'get_per_1000', 'put_per_1000'} in USD."""
    table = _load("prices.json", root)
    chip = table["chip_hour_usd"]
    if device_kind not in chip:
        raise UnknownDevice(f"device kind {device_kind!r} is not in the "
                            f"price table ({sorted(chip)})")
    return {
        "chip_hour": chip[device_kind]["usd"],
        "get_per_1000": table["durable_get_per_1000_usd"]["usd"],
        "put_per_1000": table["durable_put_per_1000_usd"]["usd"],
    }
