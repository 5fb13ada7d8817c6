"""A kernel's share of its roofline, in percent: the least time the chip
could take for the work the traced stretch asked of the kernel (token steps
from the window's records, ``roofline.py``) over the summed device time of
the kernel's XLA modules in the trace. ``args``: ``modules``, the substrings
that name the kernel's modules. No trace, no such module, or no work: no
value — never 0."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
import roofline  # noqa: E402


def read(context: dict, args: dict):
    trace = context.get("trace")
    if not trace:
        return None
    kernel_s = sum(seconds for name, seconds in trace["module_s"].items()
                   if any(part in name for part in args["modules"]))
    steps = context["token_steps_per_s"] * trace["window_s"]
    if kernel_s <= 0 or steps <= 0:
        return None
    least = roofline.least_seconds(steps, context["max_fanout"],
                                   context["device_kind"])
    return 100.0 * least / kernel_s
