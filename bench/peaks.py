"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports (``peaks.json``, with its source).  A device that
is not in the table is an error, never a default."""

from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class UnknownDevice(LookupError):
    """The device kind has no published peaks in ``peaks.json``."""


def table(path: str = PATH) -> dict:
    with open(path) as f:
        return json.load(f)


def for_device(kind: str, path: str = PATH) -> dict:
    devices = table(path)["devices"]
    if kind not in devices:
        raise UnknownDevice(f"no published peaks for device kind {kind!r} in "
                            f"{os.path.basename(path)} (known: {sorted(devices)})")
    return devices[kind]
