"""The bytes bound of a count-only flux absorb, reckoned from shapes
alone, over the device time its module took a launch. What goes in is
the ``[B]`` i32 segment ids and the ``[B]`` i32 validity of a chunk's
rows; the ``[slots]`` i32 count table is written as zeros, scattered
into and read back out (in and out: twice its size). ``B`` is the rows
a launch carried (records flux absorbed over the lane's launches in the
traced interval: less than the padded shape, which only lowers the
share), ``slots`` the metric's: the one table shape a state without a
register stack launches. A scatter-add of a few thousand rows is a
chain of read-modify-writes on one small table, so this is the bytes
bound only, reads far under 1 % and says that bytes are not the limit.
Without a trace, with a program whose flux filter does not count its
absorbs (the parent of the PR that added this), or where no module of
the name ran, it gives nothing."""

import kernel_cost
from lookup import load_py


def count_absorb_bytes(rows: float, slots: int) -> float:
    """Bytes one count-only absorb has to move through HBM at least
    once."""
    return 2 * 4 * rows + 2 * 4 * slots


def count_absorb_roofline_share(readings, plugin: str, lane: str,
                                module: str, slots: int):
    t = readings["trace"]
    if t is None:
        return None
    c = t["counters"]
    n = c.get(f"lane.{lane}.launches")
    rows = c.get(f"filter.{plugin}.records_total")
    if not n or not rows or not c.get(f"filter.{plugin}.fused_absorbs"):
        return None
    ms = load_py("readers", "program_trace").module_ms_per_launch(
        readings, module, lane)
    if not ms:
        return None
    need = count_absorb_bytes(rows / n, slots)
    peak = kernel_cost.peaks(readings["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / (ms / 1e3)
