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
    for each launch the lane counted in the traced interval. Where the
    lane launched and the device ran modules, none of them by this
    name, the kernel served nothing: 0, not nothing to read — a PR that
    moves a rule to another kernel sees it here."""
    t, trace = _table(readings), readings["trace"]
    if t is None or trace is None or not t["modules"]:
        return None
    n = trace["counters"].get(f"lane.{lane}.launches")
    if not n:
        return None
    return 1e3 * sum(s for name, s in t["modules"].items()
                     if module in name) / n


def idle_unattributed_share(readings):
    """Share of the first device's idle time that lies under none of the
    program's spans, in per cent."""
    t = _table(readings)
    if t is None or not t["idle_s"]:
        return None
    return 100.0 * t["idle_by_span"]["unattributed"] / t["idle_s"]
