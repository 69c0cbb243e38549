"""The plain reference of NEXmark's Query 5 for tier-1: ``count(*)`` by
``auction`` over the bids of the panes a window holds, in stdlib Python.
Shares no code with ``fluentbit_tpu.flux`` or ``.stream_processor``
(the benchmark has a copy of its own, ``benchmark/reference/
nexmark-q5.py``, which decides ``correct`` there)."""

from collections import Counter


def is_bid(record: dict) -> bool:
    return record.get("event_type") == "bid"


def bids_by_auction(records) -> Counter:
    return Counter(r["auction"] for r in records if is_bid(r))


def window_counts(panes: list, k: int, n_panes: int) -> Counter:
    """What the close of pane ``k`` (0-based) emits: the counts over the
    last ``n_panes`` panes, pane ``k`` among them."""
    out = Counter()
    for pane in panes[max(0, k - n_panes + 1):k + 1]:
        out.update(bids_by_auction(pane))
    return out


def drain_counts(panes: list, open_pane, ring: int) -> Counter:
    """What the drain at stop emits: the open pane, and with it the
    ``ring`` closed panes a hopping window still holds (a tumbling
    window holds none)."""
    out = bids_by_auction(open_pane)
    for pane in panes[len(panes) - ring:] if ring else ():
        out.update(bids_by_auction(pane))
    return out


def rows_of(counts: Counter) -> dict:
    """``{auction: num}`` without the auctions nobody bid on."""
    return {a: n for a, n in counts.items() if n}


def hot_items(counts: Counter) -> list:
    """Query 5's answer: the auctions with the most bids."""
    top = max(counts.values(), default=0)
    return sorted(a for a, n in counts.items() if n == top and n)
