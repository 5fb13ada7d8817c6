"""A ratio of two of the run's numbers, named by dotted paths into the run's
context (``counts.<counter>`` are the program's counters over the window,
``child.<key>`` the load generator's counts, ``trace.<key>`` the reduced
trace). ``args``: ``numerator``, ``denominator``, optional ``scale`` and
``one_minus`` (``scale * (1 - n/d)``). Nothing to read, or a zero
denominator: no value."""


def lookup(context: dict, path: str):
    value = context
    for part in path.split("."):
        if not isinstance(value, dict) or value.get(part) is None:
            return None
        value = value[part]
    return value


def read(context: dict, args: dict):
    numerator = lookup(context, args["numerator"])
    denominator = lookup(context, args["denominator"])
    if numerator is None or not denominator:
        return None
    ratio = numerator / denominator
    if args.get("one_minus"):
        ratio = 1.0 - ratio
    return args.get("scale", 1.0) * ratio
