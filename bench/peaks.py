"""Published peaks of one chip, keyed by JAX's ``device_kind``
(``peaks.json``, which names its source).  A device that is not in the
table is an error, never a default."""
import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def peak(device_kind: str) -> dict:
    devices = json.loads(TABLE.read_text())["devices"]
    if device_kind not in devices:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {TABLE.name}")
    return devices[device_kind]
