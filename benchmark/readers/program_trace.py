"""Readers over the device planes of the run's trace, by the names the
program gives its device programs (``jit_grep_scan_S690_k3`` on ``XLA
Modules``), and over the device's idle gaps set against the program's
own spans. The table comes from ``program_spans.py``; without a trace
file, or with a program that names nothing so, they give ``None``."""

from lookup import load_py


def _table(readings):
    return load_py("readers", "program_spans").table(readings)


def module_ms_per_launch(readings, module: str, lane: str):
    """Device milliseconds of the modules whose name holds ``module``,
    for each launch the lane counted in the traced interval."""
    t, trace = _table(readings), readings["trace"]
    if t is None or trace is None:
        return None
    mine = [s for name, s in t["modules"].items() if module in name]
    n = trace["counters"].get(f"lane.{lane}.launches")
    if not mine or not n:
        return None
    return 1e3 * sum(mine) / n


def idle_unattributed_share(readings):
    """Share of the first device's idle time that lies under none of the
    program's spans, in per cent."""
    t = _table(readings)
    if t is None or not t["idle_s"]:
        return None
    return 100.0 * t["idle_by_span"]["unattributed"] / t["idle_s"]
