"""One of the run's numbers as it stands. ``args``: ``path`` (dotted, as in
``ratio.py``), optional ``scale``. Nothing there: no value."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
from ratio import lookup  # noqa: E402


def read(context: dict, args: dict):
    value = lookup(context, args["path"])
    if value is None:
        return None
    return args.get("scale", 1.0) * value
