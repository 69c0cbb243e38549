"""Batched filter dispatch support — the chunk view filters see on the
raw fast path, plus the double-buffered staging pipeline.

``RawChunk`` wraps one append's encoded bytes as they move through a
chain of batch-capable filters (``FilterPlugin.process_batch``): the
record count one stage discovers travels to the next as its walk hint
(skipping the counting pre-pass), and ``src`` carries the appending
input instance so filters with a hidden emitter (rewrite_tag) can
recognise their own re-entered records without touching the
engine-global ``_ingest_src`` (which the parallel raw path must not
share across inputs).

``double_buffered`` is the depth-2 dispatch pipeline INSIDE one chunk
of the engine's batched filter path: host msgpack extraction (staging)
of segment N+1 overlaps the in-flight device kernel of segment N, and
each result is forced one segment behind its dispatch. On a real
accelerator the overlap hides the host staging walk behind the DFA
scan; on the CPU backend it degrades to the sequential order at no
extra cost. A chunk of one segment has nothing to overlap inside it:
ACROSS chunks the same overlap comes from ``RawChunk.begun`` — the
input begins the next chunk's launch while this one is collected and
committed (``plugins/net_forward.py``, "Launch beside commit").

The hook contract (machine-checked by fbtpu-lint's batch-exactness
pack, ``fluentbit_tpu.analysis.batch`` — see ANALYSIS.md):

- ``None`` (or any raise) from ``process_batch`` DECLINES the chunk:
  the engine re-runs the chain per-record from this filter onward, so
  a decline must be dominated by ZERO committed side effects (counter
  incs, emitter appends, tag rewrites) — commit last, or guard the
  committing call and succeed;
- a hook that commits side effects declares ``stateful_batch = True``
  on its class, which switches a downstream decline from a full-chain
  restart to the decoded-tail continuation;
- span-gather re-emits preserve FIRST-SEEN record order (the
  per-record path's pending-dict insertion order): group by first
  contributing record index, never iterate a set.
"""

from __future__ import annotations

from typing import Any, Callable, Iterable, List, Optional

__all__ = ["RawChunk", "double_buffered", "segment_bounds"]


class RawChunk:
    """One append's raw chunk bytes on the batched filter chain.

    data    : bytes (memoryviews from a previous filter's arena are
              materialized on first use)
    tag     : the append's routing tag
    n       : record count, or None until a stage discovers it
    src     : the appending InputInstance (emitter re-entry guard)
    engine  : the owning engine (metrics, emitter access)
    begun   : the FIRST filter's launch over ``data``, begun before the
              append had its turn (``FilterPlugin.begin_batch`` through
              ``Engine.input_log_prelaunch``), or None; the filter
              takes it with ``take_begun()``
    """

    __slots__ = ("data", "tag", "n", "src", "engine", "begun")

    def __init__(self, data, tag: str, n: Optional[int] = None,
                 src=None, engine=None, begun=None):
        self.data = data
        self.tag = tag
        self.n = n
        self.src = src
        self.engine = engine
        self.begun = begun

    def take_begun(self):
        """The launch begun on this chunk ahead of its turn, once: it
        is the taker's to finish or drop from here on."""
        begun, self.begun = self.begun, None
        return begun

    def replace(self, data, n: Optional[int]) -> None:
        """Swap in a filter's output (count may be unknown again)."""
        self.data = data
        self.n = n

    def as_bytes(self) -> bytes:
        """The chunk as ``bytes`` (ctypes-callable); materializes a
        previous stage's arena view exactly once."""
        if not isinstance(self.data, bytes):
            self.data = bytes(self.data)
        return self.data


def segment_bounds(n: int, seg_records: int) -> List[tuple]:
    """Split ``n`` records into [start, end) segments of at most
    ``seg_records`` (the double-buffer grain)."""
    if seg_records <= 0 or n <= seg_records:
        return [(0, n)]
    return [(s, min(s + seg_records, n))
            for s in range(0, n, seg_records)]


def double_buffered(stage_iter: Iterable[Any],
                    dispatch: Callable[[Any], Any],
                    collect: Optional[Callable[[Any], Any]] = None,
                    depth: int = 2) -> List[Any]:
    """Staging/kernel pipeline with ``depth`` segments in flight
    (default 2 — the classic double buffer).

    ``stage_iter`` performs the host-side extraction work lazily (each
    ``__next__`` stages one segment); ``dispatch`` launches the device
    kernel for a staged segment and must return without forcing the
    result (jax dispatch is asynchronous); ``collect`` forces a
    dispatched result (default ``np.asarray``). The loop dispatches
    segment i, stages segment i+1 while i's kernel is in flight, then
    forces the oldest in-flight segment once ``depth`` are alive — so
    host extraction and device execution overlap with at most ``depth``
    segments live. The mesh path runs depth 2 per *sharded* launch
    (one launch already spans every device); deeper pipelines serve
    backends whose dispatch queue rewards more in-flight work.
    """
    from collections import deque

    import numpy as np

    if collect is None:
        collect = np.asarray
    if depth < 2:
        depth = 2
    out: List[Any] = []
    pending: deque = deque()
    for staged in stage_iter:
        pending.append(dispatch(staged))
        if len(pending) >= depth:
            out.append(collect(pending.popleft()))
    while pending:
        out.append(collect(pending.popleft()))
    return out
