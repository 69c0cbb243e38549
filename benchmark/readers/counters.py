"""Readers over the program's own counters, as ``Pipeline.counters``
flattens them; each value is the growth over the window. A counter the
cell's pipeline does not have gives nothing."""


def value(readings, counter: str):
    return readings["counters"].get(counter)


def ratio(readings, num: str, den: str, scale: float = 1.0):
    c = readings["counters"]
    if num not in c or not c.get(den):
        return None
    return scale * c[num] / c[den]
