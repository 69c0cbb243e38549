"""The plain reference of ``grep-tenants-x4``: ``grep-tenants``' own
(``reference/grep-tenants.py``, loaded and not copied: the same 50
``Exclude`` lines of the same pipeline file, the same corpus, the same
three verdicts a record that must agree, the same counters that say the
device did the matching) **plus** what the configuration adds, the
layout — every launch sharded over the four chips of the host, none
served on one chip or on the host.

A sharded launch and an unsharded one give the same verdicts and the
same bytes at the sink, so no comparison of outputs can tell four chips
from one: the program's own counters must. ``filter_grep.staged_match``
counts, where a launch staged for the mesh is dispatched,
``mesh_launches`` (it went out sharded), ``mesh_devices`` (over how many
devices, summed) and ``unsharded_launches`` (the lane's mesh was gone
and the planes went out on one device); the lane counts the launches
that ended well (``ok``); and each child of the 50-rule program says
which axis its mesh handle shards (``GrepProgram.decision()``:
``mesh_children``). All over the whole run, warm-up included. A program
without these counters (the parent of the PR that brought them) reads
``None`` and fails the checks: it cannot say how its launches were laid
out. ``run.py`` holds the main sink to the kept bodies byte for byte, as
in the one-chip cell.
"""

from lookup import load_py

CHIPS = 4
VARIANT = "batch"       # rows sharded, every table replicated a chip


def layout_checks(counters: dict, decisions: list) -> dict:
    """The named verdicts on the layout, from ``filter.grep.*`` and
    ``lane.grep.ok`` over the run and the programs' ``decision()``."""
    sharded = counters.get("filter.grep.mesh_launches")
    devices = counters.get("filter.grep.mesh_devices")
    unsharded = counters.get("filter.grep.unsharded_launches")
    ended = counters.get("lane.grep.ok")
    children = [ch for d in decisions for ch in d.get("mesh_children", [])]
    rules = sum(len(d["rules"]) for d in decisions)
    return {
        "sharded_launches_some_and_no_fewer_than_lane_ok":
            bool(sharded) and ended is not None and sharded >= ended > 0,
        "four_devices_every_sharded_launch":
            bool(sharded) and devices == CHIPS * sharded,
        "no_launch_served_unsharded": unsharded == 0,
        "every_child_shards_rows_over_four_devices":
            bool(children)
            and sum(ch["rules"] for ch in children) == rules
            and all(ch["variant"] == VARIANT and ch["devices"] == CHIPS
                    for ch in children),
    }


def checks(run: dict) -> dict:
    tenants = load_py("reference", "grep-tenants")
    got = tenants.checks(run)
    decisions = [p.decision()
                 for p in tenants.programs_of(run["pipe"].filters)]
    layout = layout_checks(run["counters"], decisions)
    if run["rehearse"]:
        got["skipped"] = sorted(got["skipped"] + list(layout))
    else:
        got["checks"].update(layout)
    got["info"]["layout"] = {
        **{name: run["counters"].get(name) for name in (
            "filter.grep.mesh_launches", "filter.grep.mesh_devices",
            "filter.grep.unsharded_launches", "lane.grep.ok")},
        "mesh_children": [d.get("mesh_children") for d in decisions]}
    return got
