"""The least work the verdict scan needs, from shapes alone - the same
whatever implements the scan - and the chip's published peaks. Kept
with the benchmark so that no PR that claims a gain can change the
yardstick.

The work of one REQUEST is what its own bytes ask for, whatever the
program pads them to:
bytes: every byte of its string fields crosses HBM at least once on
       its way to the scan.
ops:   every rule that reads a string field looks at each byte of that
       field at least once: one 8-bit compare per (rule, byte).
The work of a PADDED batch is the same count over all the rows and the
staged widths the program chose: what it made the device do, not what
the requests needed. A share of the roofline is taken from the first;
the second stands beside it so that padding shows as the gap.
The least time is the larger of bytes over the published HBM bandwidth
and ops over the published int8 peak; `bound` says which.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
STRING_FIELDS = ("url", "path", "user_agent", "host", "method")


def peaks_for(device_kind: str) -> dict:
    """The published peaks of this device; an unknown device is an
    error, never a default."""
    with open(os.path.join(_HERE, "peaks.json"), encoding="utf-8") as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}")
    return table[device_kind]


def rules_per_field(sources: list) -> dict:
    """How many rules read each string field of the request."""
    return {field: sum(f"http_request.{field}" in src for _, src in sources)
            for field in STRING_FIELDS}


def request_work(template: dict, per_field: dict) -> tuple:
    """(bytes, ops) one request needs: see the module's text."""
    url = template["url"]
    lengths = {"url": len(url), "path": len(url.split("?", 1)[0]),
               "user_agent": len(template["user_agent"]),
               "host": len(template["host"]),
               "method": len(template["method"])}
    return (sum(lengths.values()),
            sum(per_field[f] * lengths[f] for f in STRING_FIELDS))


def padded_work(rows: int, field_widths: dict, per_field: dict) -> tuple:
    """(bytes, ops) of a batch of `rows` rows staged at `field_widths`."""
    return (rows * sum(field_widths.get(f, 0) for f in STRING_FIELDS),
            rows * sum(per_field[f] * field_widths.get(f, 0)
                       for f in STRING_FIELDS))


def least_seconds(n_bytes: float, ops: float, device_kind: str) -> dict:
    peaks = peaks_for(device_kind)
    t_bytes = n_bytes / peaks["hbm_bytes_per_s"]
    t_ops = ops / peaks["int8_ops"]
    return {"bytes": n_bytes, "ops": ops, "seconds": max(t_bytes, t_ops),
            "bound": "hbm" if t_bytes >= t_ops else "int8"}
