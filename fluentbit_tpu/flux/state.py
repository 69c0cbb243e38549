"""Device-resident per-tenant flux state — sketches + window aggregates.

One :class:`FluxState` is the analytics state behind one flux consumer
(a configured ``filter_flux`` instance, or one sketch-eligible
stream-processor query).  Per group key (the tenant/tag labels, or
integer ids: a GROUP BY field is typed row by row, :class:`KeyCol`,
FLUX.md "Typed group keys") it maintains:

- **HLL cardinality** per distinct-column (``ops.sketch.HyperLogLog``,
  registers device-resident once the backend attaches; cross-chip merge
  is ``lax.pmax`` via ``sharded_hll_update``),
- **count-min top-k** over a state-wide CMS keyed by composite
  ``group␟value`` bytes with a bounded per-group candidate set
  (``sharded_cms_update`` psum merge on a mesh),
- **window aggregates** — count/sum/min/max/avg per numeric column over
  tumbling or hopping windows.  Counts run through the segment
  scatter-add kernel (psum-merged on the mesh lane, integer-exact);
  float sums/mins/maxs accumulate host-side in IEEE doubles.

Exactness model (the differential-test contract, FLUX.md):

- the batched absorb (:meth:`absorb_batch`, fed by the native column
  stagers) and the per-record twin (:meth:`absorb_events`) are
  **bit-identical** — same grouping, same float addition ORDER (the
  running sum is threaded through ``np.bincount``'s sequential
  accumulation, continuing from the pane's stored sum exactly like the
  Python evaluation path's ``sums[n] += v``), same min/max
  representative selection (first row attaining the extremum);
- count/sum/min/max/avg therefore reproduce
  ``stream_processor._Agg`` bit-for-bit for map-bodied records;
- ``COUNT(DISTINCT k)`` is approximate with the standard HLL error
  (σ ≈ 1.04/√(2^p)); top-k estimates carry the count-min
  over-estimation bound (ε ≈ e/width with prob 1-δ, δ = e^-depth).

Windowing matches ``stream_processor.SPTask.tick`` in processing-time
mode (whole-period boundary advance, hopping pane ring of
``round(size/advance)`` panes, drain-on-shutdown).  Event-time tumbling
mode (per-record path only) assigns records to ``floor(ts/size)``
windows, closes on watermark advance, and counts late records instead
of corrupting closed panes.
"""

from __future__ import annotations

import math
import os
import pickle
import tempfile
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import failpoints as _fp
from ..core.spans import span
from ..ops.batch import assemble, bucket_size
from ..ops.sketch import CountMin, HyperLogLog
from . import kernels

__all__ = ["WindowSpec", "FluxSpec", "FluxState", "KeyCol",
           "SNAPSHOT_VERSION", "TIMING_KEYS", "render_key_part"]

SNAPSHOT_VERSION = 1

#: composite separator for top-k keys: group fields join with \x1e,
#: group|value with \x1f (both outside normal label alphabets)
_FIELD_SEP = b"\x1e"
_VALUE_SEP = b"\x1f"

#: cap on distinct group keys tracked for top-k candidates (LRU-ish;
#: the CMS itself is fixed-size — only the nomination sets need a bound)
_MAX_CANDIDATE_GROUPS = 4096

#: what a flux filter's ``raw_timings`` holds: ``absorb_s`` host seconds
#: of the absorbs outside the lane's launch, ``group_s`` the part of it
#: in ``_group_rows``; ``fused_absorbs`` / ``host_absorbs`` absorbs whose
#: counts came from the fused device program / from the host twin;
#: ``close_s`` seconds of the closing ticks (pane roll, merge, rows,
#: emit), ``closes`` their number, ``closed_groups`` the groups they
#: merged, ``emitted_rows`` the rows they and the drain handed on
TIMING_KEYS = ("absorb_s", "group_s", "fused_absorbs", "host_absorbs",
               "close_s", "closes", "closed_groups", "emitted_rows")

#: kinds of one row's GROUP BY key (:class:`KeyCol`; the codes
#: ``native.stage_field_i64`` writes)
KEY_NULL, KEY_STR, KEY_INT, KEY_OBJ = 0, 1, 2, 3


def _is_i64(v) -> bool:
    return type(v) is int and -(1 << 63) <= v < (1 << 63)


class _Opaque:
    """A GROUP BY value that cannot sit in a key tuple as itself: a
    nested value (unhashable) or a msgpack ``bin`` (its ``bytes`` would
    collide with a string key's). Equal where the values are equal."""

    __slots__ = ("value",)

    def __init__(self, value):
        self.value = value

    def __eq__(self, other):
        return isinstance(other, _Opaque) and type(self.value) is \
            type(other.value) and self.value == other.value

    def __hash__(self):
        return hash(repr(self.value))

    def __repr__(self):
        return f"_Opaque({self.value!r})"


def render_key_part(part):
    """One part of a group key tuple as a window row carries it: the
    value in its own Python type, as the exact path's rows do."""
    if isinstance(part, bytes):
        return part.decode("utf-8", "replace")
    if isinstance(part, _Opaque):
        return part.value
    return part


class KeyCol:
    """One GROUP BY field of one chunk, typed row by row.

    kind   u8 [n]: 0 missing, 1 string, 2 integer, 3 object
    ints   i64 [n] or None: the integer of a kind-2 row, the index into
           ``objs`` of a kind-3 row, 0 elsewhere
    sbatch u8 [n, L], slen i32 [n], or None: the bytes and length of a
           kind-1 row (``slen`` < 0 elsewhere)
    objs   the distinct other values of a decoded chunk (floats, bools,
           integers past int64, nested values): the per-record twin
           only; a staged chunk holding one declines before any commit
    """

    __slots__ = ("kind", "ints", "sbatch", "slen", "objs")

    def __init__(self, kind, ints=None, sbatch=None, slen=None,
                 objs=None):
        self.kind = kind
        self.ints = ints
        self.sbatch = sbatch
        self.slen = slen
        self.objs = objs

    @classmethod
    def of_strings(cls, sbatch: np.ndarray, slen: np.ndarray) -> "KeyCol":
        """A column of strings and missing values, as staged."""
        return cls((slen >= 0).astype(np.uint8), None, sbatch, slen)

    def take(self, rows: np.ndarray) -> "KeyCol":
        return KeyCol(
            self.kind[rows],
            None if self.ints is None else self.ints[rows],
            None if self.sbatch is None else self.sbatch[rows],
            None if self.slen is None else self.slen[rows],
            self.objs)

    def values(self, rows: np.ndarray) -> list:
        """The key parts of ``rows``: None, bytes, int, or the object."""
        kinds = self.kind[rows].tolist()
        ints = None if self.ints is None else self.ints[rows].tolist()
        out: list = []
        for j, k in enumerate(kinds):
            if k == KEY_INT:
                out.append(ints[j])
            elif k == KEY_STR:
                r = rows[j]
                out.append(self.sbatch[r, :self.slen[r]].tobytes())
            elif k == KEY_OBJ:
                out.append(self.objs[ints[j]])
            else:
                out.append(None)
        return out


class WindowSpec:
    """Window shape: ``None`` kind = unwindowed running state."""

    __slots__ = ("kind", "size", "advance")

    def __init__(self, kind: Optional[str] = None, size: float = 0.0,
                 advance: Optional[float] = None):
        if kind not in (None, "tumbling", "hopping"):
            raise ValueError(f"unknown window kind {kind!r}")
        if kind is not None and size <= 0:
            raise ValueError("window size must be positive")
        self.kind = kind
        self.size = float(size)
        self.advance = float(advance) if advance else self.size

    @classmethod
    def parse(cls, text: Optional[str]) -> "WindowSpec":
        """``"tumbling 60"`` | ``"hopping 60 10"`` | ``"none"``/empty."""
        if not text or str(text).strip().lower() in ("none", "off"):
            return cls(None)
        parts = str(text).split()
        kind = parts[0].lower()
        size = float(parts[1]) if len(parts) > 1 else 0.0
        advance = float(parts[2]) if len(parts) > 2 else None
        return cls(kind, size, advance)

    @property
    def n_panes(self) -> int:
        if self.kind != "hopping":
            return 1
        return max(1, int(round(self.size / self.advance)))


class FluxSpec:
    """Immutable shape of one flux state."""

    __slots__ = ("name", "group_by", "distinct", "numeric", "topk_field",
                 "topk", "window", "hll_p", "cms_depth", "cms_width",
                 "max_len", "event_time", "mesh")

    def __init__(self, name: str,
                 group_by: Sequence[str] = (),
                 distinct: Sequence[str] = (),
                 numeric: Sequence[str] = (),
                 topk_field: Optional[str] = None,
                 topk: int = 10,
                 window: Optional[WindowSpec] = None,
                 hll_p: int = 12,
                 cms_depth: int = 4,
                 cms_width: int = 16384,
                 max_len: int = 256,
                 event_time: bool = False,
                 mesh: bool = False):
        self.name = name
        self.group_by = tuple(group_by)
        self.distinct = tuple(distinct)
        self.numeric = tuple(numeric)
        self.topk_field = topk_field
        self.topk = int(topk)
        self.window = window or WindowSpec(None)
        self.hll_p = int(hll_p)
        self.cms_depth = int(cms_depth)
        self.cms_width = int(cms_width)
        self.max_len = int(max_len)
        self.event_time = bool(event_time)
        self.mesh = bool(mesh)
        if self.event_time and self.window.kind != "tumbling":
            # fail at CONFIG time: event-time assignment divides by the
            # window size, so a missing/hopping window must not surface
            # as a per-append crash later
            raise ValueError(
                "event-time windows require a tumbling window "
                "(hopping panes are processing-time; see FLUX.md)")

    def shape(self) -> dict:
        """Structural identity for snapshot compatibility checks.
        MUST include the sketch geometry: restoring p=12 registers into
        a p=14 state would hand the C HLL kernel a 4× undersized buffer
        (out-of-bounds write), and a changed CMS width silently hashes
        into the wrong columns. max_len is an exactness parameter too
        (it decides which values leave the sketch)."""
        return {
            "group_by": self.group_by,
            "distinct": self.distinct,
            "numeric": self.numeric,
            "topk_field": self.topk_field,
            "event_time": self.event_time,
            "window": (self.window.kind, self.window.size,
                       self.window.advance),
            "hll_p": self.hll_p,
            "cms_depth": self.cms_depth,
            "cms_width": self.cms_width,
            "max_len": self.max_len,
        }

    @property
    def sketch_fields(self) -> Tuple[str, ...]:
        """Columns the sketches hash as string bytes (distinct and
        top-k), in staging order."""
        out: List[str] = []
        for f in self.distinct:
            if f not in out:
                out.append(f)
        if self.topk_field and self.topk_field not in out:
            out.append(self.topk_field)
        return tuple(out)

    @property
    def string_fields(self) -> Tuple[str, ...]:
        """Columns staged as string bytes, in staging order: the GROUP
        BY fields (their string rows) and the sketch columns."""
        out: List[str] = list(self.group_by)
        out += [f for f in self.sketch_fields if f not in out]
        return tuple(out)


class _ColStat:
    """Per-(group, numeric column) running aggregate — the flux twin of
    one column's slice of ``stream_processor._Agg``."""

    __slots__ = ("has", "sum", "min", "max", "min_int", "max_int")

    def __init__(self):
        self.has = False
        self.sum = 0.0
        self.min = 0.0
        self.max = 0.0
        # representative int-ness: the exact path returns the ORIGINAL
        # min/max value (int stays int); kind 1 rows reconstruct as int
        self.min_int = False
        self.max_int = False

    def merge(self, other: "_ColStat") -> None:
        if not other.has:
            return
        if not self.has:
            self.has = True
            # 0.0 + s: same float sequence as _Agg.merge's
            # ``sums.get(n, 0.0) + v``
            self.sum = 0.0 + other.sum
            self.min, self.min_int = other.min, other.min_int
            self.max, self.max_int = other.max, other.max_int
            return
        self.sum = self.sum + other.sum
        if other.min < self.min:
            self.min, self.min_int = other.min, other.min_int
        if other.max > self.max:
            self.max, self.max_int = other.max, other.max_int

    def min_value(self):
        if not self.has:
            return None
        return int(self.min) if self.min_int else self.min

    def max_value(self):
        if not self.has:
            return None
        return int(self.max) if self.max_int else self.max


class _FluxGroup:
    """One group key's accumulators inside one window pane."""

    __slots__ = ("count", "cols", "hlls")

    def __init__(self, spec: FluxSpec):
        self.count = 0
        self.cols: Dict[str, _ColStat] = {f: _ColStat()
                                          for f in spec.numeric}
        self.hlls: Dict[str, HyperLogLog] = {
            f: HyperLogLog(p=spec.hll_p) for f in spec.distinct
        }

    def merge(self, other: "_FluxGroup") -> None:
        self.count += other.count
        for f, st in other.cols.items():
            self.cols[f].merge(st)
        for f, h in other.hlls.items():
            self.hlls[f].merge_registers(
                h.registers if isinstance(h.registers, np.ndarray)
                else np.asarray(h.registers))


def _platform_of(arr) -> Optional[str]:
    """The platform an array of a launch's result lives on (None for a
    numpy array: the host twin's)."""
    devices = getattr(arr, "devices", None)
    if devices is None:
        return None
    return next(iter(devices())).platform


def _seq_sum(start: float, values: np.ndarray) -> float:
    """``((start + v0) + v1) + ...`` with C-double sequential adds —
    np.bincount accumulates its weights in input order, which is
    exactly the Python evaluation path's running ``+=``."""
    w = np.concatenate([np.asarray([start], dtype=np.float64),
                        values.astype(np.float64, copy=False)])
    return float(np.bincount(np.zeros(w.size, dtype=np.intp),
                             weights=w, minlength=1)[0])


class FluxState:
    """Mutable analytics state (see module docstring).  All mutation
    happens under the engine's ingest lock — the flux filter is not
    ``thread_safe_raw`` and the SP window tick runs under the same
    lock, so no locking lives here."""

    def __init__(self, spec: FluxSpec, now=None):
        self.spec = spec
        self._now = now or time.time
        self._mesh = kernels.flux_mesh() if spec.mesh else None
        self._lane = None  # fbtpu-armor flux DeviceLane (lazy)
        # processing-time pane machinery (SPTask twin)
        self._groups: Dict[tuple, _FluxGroup] = {}
        self._panes: List[Dict[tuple, _FluxGroup]] = []
        self._window_start = self._now()
        # event-time machinery (tumbling only, per-record path)
        self._event_windows: Dict[int, Dict[tuple, _FluxGroup]] = {}
        self._watermark: Optional[float] = None
        self._pending_closed: List[Tuple[float,
                                         List[Tuple[tuple, _FluxGroup]]]] = []
        # state-lifetime top-k: one CMS + bounded per-group candidates
        self.cms: Optional[CountMin] = None
        self._candidates: Dict[tuple, Dict[bytes, None]] = {}
        if spec.topk_field:
            self.cms = CountMin(depth=spec.cms_depth,
                                width=spec.cms_width)
        # counters (exported as fluentbit_flux_*)
        self.records_total = 0
        self.late_records_total = 0
        self.window_emits_total = 0
        self.batches_total = 0
        #: the owning plugin's ``raw_timings`` (``absorb_s``: host work
        #: of an absorb outside the lane's guarded launch); None for a
        #: state nobody times
        self.timings = None
        self._launch_s = 0.0  # lane.run seconds of the absorb under way
        #: the platform the last fused absorb's counts came back from
        self.counts_platform: Optional[str] = None
        self.window_closes_total = 0

    # ------------------------------------------------------------ absorb

    def absorb_batch(self, n: int,
                     strcols: Dict[str, Tuple[np.ndarray, np.ndarray]],
                     numcols: Dict[str, Tuple[np.ndarray, np.ndarray]],
                     keycols: Optional[Dict[str, KeyCol]] = None,
                     ) -> int:
        """Absorb one staged chunk (processing-time mode).

        strcols  : field → (batch u8 [n, L], lengths i32 [n]); lengths
                   < 0 = missing/non-string/oversize
        numcols  : field → (values f64 [n], kinds u8 [n]); kind 0 =
                   missing/non-numeric, 1 = integer, 2 = float
        keycols  : GROUP BY field → :class:`KeyCol`; a field left out
                   is keyed by its ``strcols`` entry (strings only)

        EVERY record counts — the codec coerces non-map bodies to empty
        dicts at decode (codec.events._to_event), so the Python
        evaluation path counts them with all columns missing, and the
        batched path must do exactly the same (the native stagers
        return missing for non-map rows already).
        """
        if self.spec.event_time:
            raise RuntimeError("event-time state has no batched path")
        self.batches_total += 1
        if n <= 0:
            return 0
        t0 = time.perf_counter()
        self._absorb_rows(self._groups, n, strcols, numcols,
                          self._keycols(strcols, keycols))
        self._note_absorb(t0)
        self.records_total += n
        return n

    def _keycols(self, strcols, keycols) -> Dict[str, KeyCol]:
        out = dict(keycols or ())
        for f in self.spec.group_by:
            if f not in out:
                out[f] = KeyCol.of_strings(*strcols[f])
        return out

    def absorb_events(self, events: list) -> int:
        """Per-record twin of :meth:`absorb_batch` — converts decoded
        events to the same column layout and runs the same math, so the
        two paths are bit-identical."""
        n = len(events)
        if n == 0:
            return 0
        t0 = time.perf_counter()
        # the decode-side coercion: non-dict bodies become empty maps
        # (all columns missing, row still counts) — parity with both
        # the codec's _to_event and the native stagers' non-map rows
        bodies = [ev.body if isinstance(ev.body, dict) else {}
                  for ev in events]
        strcols = {
            f: self._str_column(bodies, f)
            for f in self.spec.sketch_fields
        }
        numcols = {
            f: self._num_column(bodies, f) for f in self.spec.numeric
        }
        keycols = {
            f: self._key_column(bodies, f) for f in self.spec.group_by
        }
        self.batches_total += 1
        if self.spec.event_time:
            ts = np.asarray([ev.ts_float for ev in events],
                            dtype=np.float64)
            absorbed = self._absorb_event_time(ts, strcols, numcols,
                                               keycols)
        else:
            self._absorb_rows(self._groups, n, strcols, numcols, keycols)
            absorbed = n
        self._note_absorb(t0)
        self.records_total += absorbed
        return absorbed

    def _note_absorb(self, t0: float) -> None:
        """One absorb's host seconds onto the plugin's ``raw_timings``
        (engine thread, under the ingest lock like all of this)."""
        launch, self._launch_s = self._launch_s, 0.0
        if self.timings is not None:
            self.timings.add("absorb_s",
                             time.perf_counter() - t0 - launch)

    def _str_column(self, bodies: List[dict], field: str):
        vals: List[Optional[bytes]] = []
        for b in bodies:
            v = b.get(field)
            if isinstance(v, str):
                vb = v.encode("utf-8")
                # oversize → missing, exactly like the stager's -2 rows
                vals.append(vb if len(vb) <= self.spec.max_len else None)
            else:
                vals.append(None)
        batch = assemble(vals, self.spec.max_len)
        ln = batch.lengths.copy()
        ln[ln == -2] = -1  # collapse oversize into plain missing
        return batch.batch, ln

    def _key_column(self, bodies: List[dict], field: str) -> KeyCol:
        """One GROUP BY field of decoded records, keyed as the exact
        path's ``_Agg`` dict keys it: a string by its bytes, an integer
        int64 holds by its value, and every other value — a float, a
        bool, a longer integer, a nested value — as an object, equal
        where Python says so (``1 == 1.0 == True`` is ONE group, named
        by whichever came first); None and a missing key are the null
        group. A string longer than ``max_len`` is missing (FLUX.md)."""
        n = len(bodies)
        kind = np.zeros((n,), dtype=np.uint8)
        ints = np.zeros((n,), dtype=np.int64)
        svals: List[Optional[bytes]] = [None] * n
        any_str = False
        others: List[Tuple[int, Any]] = []   # rows of non-string values
        any_obj = False
        for i, b in enumerate(bodies):
            v = b.get(field)
            if v is None:
                continue
            if isinstance(v, str):
                vb = v.encode("utf-8")
                if len(vb) <= self.spec.max_len:
                    svals[i] = vb
                    kind[i] = KEY_STR
                    any_str = True
                continue
            if _is_i64(v):
                kind[i] = KEY_INT
                ints[i] = v
            else:
                any_obj = True
            others.append((i, v))
        objs: Optional[list] = None
        if any_obj:
            # Python equality across the non-string values, first seen
            # names the group: what a dict keyed on the values does
            objs = []
            first: Dict[Any, Tuple[int, int]] = {}
            for i, v in others:
                part = v
                if isinstance(v, (bytes, bytearray)):
                    part = _Opaque(v)
                else:
                    try:
                        hash(v)
                    except TypeError:
                        part = _Opaque(v)
                seen = first.get(part)
                if seen is None:
                    if _is_i64(part):
                        seen = (KEY_INT, part)
                    else:
                        seen = (KEY_OBJ, len(objs))
                        objs.append(part)
                    first[part] = seen
                kind[i], ints[i] = seen
        sbatch = slen = None
        if any_str:
            batch = assemble(svals, self.spec.max_len)
            sbatch, slen = batch.batch, batch.lengths.copy()
            slen[slen == -2] = -1
        return KeyCol(kind, ints if others else None, sbatch, slen, objs)

    def _num_column(self, bodies: List[dict], field: str):
        vals = np.zeros((len(bodies),), dtype=np.float64)
        kinds = np.zeros((len(bodies),), dtype=np.uint8)
        for i, b in enumerate(bodies):
            v = b.get(field)
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                continue
            vals[i] = float(v)
            kinds[i] = 1 if isinstance(v, int) else 2
        return vals, kinds

    # -- grouping ------------------------------------------------------

    def _group_rows(self, n_rows: int, keycols: Dict[str, KeyCol]
                    ) -> Tuple[np.ndarray, List[tuple]]:
        """Segment ids (first-seen order) + group key tuples: ONE
        ``np.unique`` over a fixed-width key — a kind byte a field,
        eight bytes where the column holds integers, and the string
        plane with its length only where it holds strings."""
        gb = self.spec.group_by
        if not gb:
            return np.zeros((n_rows,), dtype=np.int64), [()]
        mats = []
        for f in gb:
            kc = keycols[f]
            mats.append(kc.kind.reshape(-1, 1))
            if kc.ints is not None:
                mats.append(np.ascontiguousarray(
                    kc.ints, dtype="<i8").view(np.uint8).reshape(-1, 8))
            if kc.sbatch is not None:
                b, L = kc.sbatch, kc.sbatch.shape[1]
                ln2 = np.where(kc.kind == KEY_STR, kc.slen,
                               np.int32(-1)).astype(np.int32)
                bz = np.ascontiguousarray(b, dtype=np.uint8).copy()
                # zero pad bytes so the void view compares by value;
                # the length column disambiguates embedded-NUL prefixes
                mask = np.arange(L)[None, :] >= \
                    np.clip(ln2, 0, None)[:, None]
                bz[mask] = 0
                mats.append(bz)
                mats.append(ln2.astype("<i4").view(np.uint8)
                            .reshape(-1, 4))
        keyed = np.ascontiguousarray(np.concatenate(mats, axis=1))
        void = keyed.view(f"V{keyed.shape[1]}").reshape(-1)
        _, first_idx, inv = np.unique(void, return_index=True,
                                      return_inverse=True)
        order = np.argsort(first_idx, kind="stable")
        remap = np.empty(order.size, dtype=np.int64)
        remap[order] = np.arange(order.size)
        seg = remap[np.asarray(inv).reshape(-1)]
        rows = first_idx[order]
        keys = list(zip(*(keycols[f].values(rows) for f in gb)))
        return seg, keys

    # -- the shared core ----------------------------------------------

    #: fused-absorb group ceiling: the [Gp, m] register stack scales
    #: with the padded group count, so a pathological high-cardinality
    #: GROUP BY batch (thousands of groups in ONE chunk) absorbs
    #: through the bit-identical host twins instead of staging a
    #: multi-hundred-MB snapshot stack
    _FUSED_MAX_GROUPS = 512

    def _absorb_rows(self, pane: Dict[tuple, _FluxGroup], n_rows: int,
                     strcols, numcols, keycols) -> None:
        tm = self.timings
        with (tm.timed("group_s", "flux.group", rows=n_rows)
              if tm is not None else span("flux.group", rows=n_rows)):
            seg, keys = self._group_rows(n_rows, keycols)
        n_groups = len(keys)
        single = n_groups == 1
        order = bounds = None
        if not single:
            # one stable sort instead of a per-group full-batch scan
            # (O(N log N), not O(groups × rows) — GROUP BY a
            # high-cardinality key must not go quadratic inside the
            # ingest lock); stability keeps each group's row indices
            # ascending, which the sequential-sum exactness needs
            order = np.argsort(seg, kind="stable")
            bounds = np.searchsorted(seg[order],
                                     np.arange(n_groups + 1))

        def gslice(gid, b, ln):
            if single:
                return b, ln
            gidx = order[bounds[gid]:bounds[gid + 1]]
            return b[gidx], ln[gidx]

        groups: List[_FluxGroup] = []
        for key in keys:
            g = pane.get(key)
            if g is None:
                g = pane[key] = _FluxGroup(self.spec)
            groups.append(g)
        # top-k composites: host-built per group (prefix + value) in
        # group order, absorbed below as ONE concatenated batch; the
        # candidate nomination reads the host rows and is independent
        # of how (or whether) the sketch update launches
        comp = comp_len = None
        if self.spec.topk_field:
            tb, tl = strcols[self.spec.topk_field]
            parts = []
            for gid, key in enumerate(keys):
                gb, gl = gslice(gid, tb, tl)
                built = self._topk_composites(key, gb, gl)
                if built is None:
                    continue
                c, cl, plen = built
                self._topk_nominate(key, c, cl, plen)
                parts.append((c, cl))
            if parts:
                W = self.spec.max_len
                comp = np.concatenate([c for c, _ in parts])
                comp_len = np.concatenate([cl for _, cl in parts])
                Bc = bucket_size(comp.shape[0], max_len=W)
                if Bc > comp.shape[0]:
                    comp = np.concatenate(
                        [comp, np.zeros((Bc - comp.shape[0], W),
                                        dtype=np.uint8)])
                    comp_len = np.concatenate(
                        [comp_len, np.full((Bc - comp_len.shape[0],),
                                           -1, dtype=np.int32)])
        fuse = (self._mesh is not None or self._use_device()) \
            and n_groups <= self._FUSED_MAX_GROUPS
        if fuse:
            # ONE device launch for the whole absorb — counts + every
            # group's HLL registers + the count-min table in a single
            # fused program (the cashed fbtpu-fuseplan merge)
            counts = self._fused_absorb(groups, seg, strcols, comp,
                                        comp_len, gslice)
        else:
            self._note_counts(fused=False)
            if single:
                counts = np.asarray([n_rows], dtype=np.int32)
            else:
                ones = np.ones((seg.shape[0],), dtype=np.int32)
                counts = kernels.host_segment_counts(seg, ones,
                                                     n_groups)
            for f in self.spec.distinct:
                b, ln = strcols[f]
                for gid, g in enumerate(groups):
                    gb, gl = gslice(gid, b, ln)
                    g.hlls[f].host_update(gb, gl)
            if comp is not None:
                self.cms.host_update(comp, comp_len)
        for gid, g in enumerate(groups):
            g.count += int(counts[gid])
            for f in self.spec.numeric:
                vals, kinds = numcols[f]
                if not single:
                    gidx = order[bounds[gid]:bounds[gid + 1]]
                    vals, kinds = vals[gidx], kinds[gidx]
                self._update_col(g.cols[f], vals, kinds)

    def _fused_absorb(self, groups: List[_FluxGroup], seg: np.ndarray,
                      strcols, comp, comp_len, gslice) -> np.ndarray:
        """Dispatch the fused absorb program through the flux lane —
        the snapshot-in/commit-on-finish protocol of
        :meth:`_hll_absorb`, for the whole fused region at once: the
        launch computes counts, the per-group register stacks and the
        count-min table from explicit pre-launch snapshots, and the
        caller commits after ``lane.run`` resolves.  Any failure
        resolves to the bit-identical host twins re-materialized from
        the same snapshots."""
        spec = self.spec
        lane = self._flux_lane()
        mesh_on = self._mesh is not None
        n_groups = len(groups)
        fields = list(spec.distinct)
        # a state without a register stack pays four bytes a slot of
        # the count table: ONE table shape for every chunk, so a group
        # count that crosses a power of two compiles nothing mid-run
        n_seg = n_groups if fields else self._FUSED_MAX_GROUPS
        regs0 = [[g.hlls[f].registers for g in groups]
                 for f in fields]
        table0 = self.cms.table if comp is not None else None
        n_dev = self._mesh.devices.size if mesh_on else 1
        B = seg.shape[0]
        # bucket the batch axis so jit sees a small set of stable
        # shapes (pad rows: segment 0 with valid 0, lengths -1 — every
        # kernel treats them as no-ops)
        Bp = bucket_size(B, max_len=spec.max_len or 1,
                         multiple_of=n_dev)
        seg32 = seg.astype(np.int32)
        valid = np.ones((B,), dtype=np.int32)
        if Bp > B:
            seg32 = np.concatenate(
                [seg32, np.zeros((Bp - B,), dtype=np.int32)])
            valid = np.concatenate(
                [valid, np.zeros((Bp - B,), dtype=np.int32)])
        fcols = []
        for f in fields:
            b, ln = strcols[f]
            if Bp > b.shape[0]:
                b = np.concatenate(
                    [b, np.zeros((Bp - b.shape[0], b.shape[1]),
                                 dtype=b.dtype)])
                ln = np.concatenate(
                    [ln, np.full((Bp - ln.shape[0],), -1,
                                 dtype=ln.dtype)])
            fcols.append((b, ln))

        def _wait(x):
            return getattr(x, "block_until_ready", lambda: x)()

        def launch():
            if _fp.ACTIVE:
                _fp.fire("flux.device_update")
            m = lane.current_mesh(axis="flux") if mesh_on else None
            with span("flux.dispatch"):
                if m is not None:
                    got = kernels.sharded_fused_absorb(
                        m, seg32, valid, fcols, regs0, comp, comp_len,
                        table0, hll_p=spec.hll_p, cms=self.cms,
                        n_seg=n_seg)
                else:  # mesh shrunk below 2 devices (or none): plain jit
                    got = kernels.fused_absorb(
                        seg32, valid, fcols, regs0, comp, comp_len,
                        table0, hll_p=spec.hll_p, cms=self.cms,
                        n_seg=n_seg)
            counts, regs_out, table_out = got
            with span("flux.force"):
                return (_wait(counts),
                        tuple(_wait(r) for r in regs_out),
                        _wait(table_out) if table_out is not None
                        else None)

        def fallback():
            # device path failed: re-materialize EVERY sketch from its
            # pre-launch snapshot, host-pinned (numpy), and absorb
            # there — bit-identical math (the old-or-new contract of
            # _hll_absorb/_cms_absorb, for the whole fused region)
            ones = np.ones((seg.shape[0],), dtype=np.int32)
            counts = kernels.host_segment_counts(seg, ones, n_groups)
            for fi, f in enumerate(fields):
                b, ln = strcols[f]
                for gid, g in enumerate(groups):
                    hll = g.hlls[f]
                    hll.registers = np.asarray(regs0[fi][gid])
                    gb, gl = gslice(gid, b, ln)
                    hll.host_update(gb, gl)
            if comp is not None:
                self.cms.table = np.asarray(table0)
                self.cms.host_update(comp, comp_len)
            return counts, None, None

        t0 = time.perf_counter()
        counts, regs_out, table_out = lane.run(launch, fallback)
        self._launch_s += time.perf_counter() - t0
        # the fallback resolves to (counts, None, None): the host twin
        self._note_counts(fused=regs_out is not None)
        if regs_out is not None:
            self.counts_platform = _platform_of(counts)
            for fi, f in enumerate(fields):
                for gid, g in enumerate(groups):
                    g.hlls[f].registers = regs_out[fi][gid]
        if table_out is not None:
            self.cms.table = table_out
        return np.asarray(counts)

    def _note_counts(self, fused: bool) -> None:
        if self.timings is not None:
            self.timings.add("fused_absorbs" if fused
                             else "host_absorbs", 1)

    @staticmethod
    def _update_col(st: _ColStat, vals: np.ndarray,
                    kinds: np.ndarray) -> None:
        valid = kinds > 0
        if not valid.any():
            return
        vv = vals[valid]
        kk = kinds[valid]
        if np.isnan(vv).any():
            # NaN ordering is path-dependent under vectorized min/max;
            # run the exact per-value semantics instead (rare)
            for v, k in zip(vv.tolist(), kk.tolist()):
                is_int = k == 1
                if not st.has:
                    st.has = True
                    st.sum = 0.0 + v
                    st.min, st.min_int = v, is_int
                    st.max, st.max_int = v, is_int
                    continue
                st.sum = st.sum + v
                if v < st.min:
                    st.min, st.min_int = v, is_int
                if v > st.max:
                    st.max, st.max_int = v, is_int
            return
        start = st.sum if st.has else 0.0
        new_sum = _seq_sum(start, vv)
        gmin = float(vv.min())
        gmax = float(vv.max())
        min_int = bool(kk[int(np.argmax(vv == gmin))] == 1)
        max_int = bool(kk[int(np.argmax(vv == gmax))] == 1)
        if not st.has:
            st.has = True
            st.min, st.min_int = gmin, min_int
            st.max, st.max_int = gmax, max_int
        else:
            if gmin < st.min:
                st.min, st.min_int = gmin, min_int
            if gmax > st.max:
                st.max, st.max_int = gmax, max_int
        st.sum = new_sum

    def _use_device(self) -> bool:
        from ..ops import device

        return device.ready() and device.platform() not in (None, "cpu")

    def _flux_lane(self):
        """The flux plane's device fault domain (fbtpu-armor): sketch
        and count launches run on its watched worker with a deadline
        and breaker; failures resolve to the bit-identical host twins,
        and device sketch state re-materializes host-side (FAULTS.md
        "fbtpu-armor")."""
        lane = self._lane
        if lane is None:
            from ..ops import fault

            lane = self._lane = fault.lane("flux")
        return lane

    def _topk_composites(self, key: tuple, batch: np.ndarray,
                         lengths: np.ndarray):
        """Build one group's top-k composite rows (``prefix + value``)
        host-side — ``(comp, comp_len, plen)`` over the group's VALID
        rows, or None when the group contributes nothing.  The sketch
        update itself happens once for the whole batch (fused launch or
        host twin) on the concatenation of every group's rows."""
        prefix = self._group_prefix(key)
        W = self.spec.max_len
        valid = np.nonzero(lengths >= 0)[0]
        if valid.size == 0:
            return None
        plen = len(prefix)
        if plen > W:
            # the group prefix alone exceeds the composite width: no
            # value can fit, and the broadcast below would raise AFTER
            # earlier groups committed (a partial absorb = the
            # batch-exactness violation). Skip identically on both
            # paths — this group simply has no top-k.
            return None
        comp = np.zeros((valid.size, W), dtype=np.uint8)
        comp_len = np.full((valid.size,), -1, dtype=np.int32)
        if plen:
            comp[:, :plen] = np.frombuffer(prefix, dtype=np.uint8)
        vl = lengths[valid]
        fits = plen + vl <= W
        span = min(W - plen, batch.shape[1])
        comp[:, plen:plen + span] = batch[valid, :span]
        # oversize composites are excluded on BOTH paths (comp_len -1)
        comp_len[fits] = (plen + vl[fits]).astype(np.int32)
        # zero pad bytes past each composite's length (the batch slice
        # above copied arena garbage); candidate extraction below walks
        # by length so only the staged device batch needs the zeroing
        pad = np.arange(W)[None, :] >= np.clip(comp_len, 0, None)[:, None]
        comp[pad] = 0
        return comp, comp_len, plen

    def _topk_nominate(self, key: tuple, comp: np.ndarray,
                       comp_len: np.ndarray, plen: int) -> None:
        """Candidate set: a BOUNDED sample of this chunk's values (the
        CMS holds the counts; candidates only nominate keys for the
        top-k read). Stride-sampling rows instead of uniquing the
        whole chunk caps per-chunk work at O(limit) — hot keys appear
        in most chunks, so they enter the set with high probability,
        and the estimates themselves always come from the sketch."""
        cand = self._candidates.pop(key, None)
        if cand is None:
            cand = {}
        # re-insert at the END: the candidate-group map is bounded
        # LRU-ish (hot groups stay, historical group keys age out) —
        # per-group panes clear on window rollover but top-k is
        # state-lifetime, so without this a high-cardinality GROUP BY
        # grows candidate memory and exporter-refresh cost forever
        self._candidates[key] = cand
        if len(self._candidates) > _MAX_CANDIDATE_GROUPS:
            for stale in list(self._candidates)[
                    : len(self._candidates) - _MAX_CANDIDATE_GROUPS]:
                del self._candidates[stale]
        ok = np.nonzero(comp_len >= 0)[0]
        limit = max(64, 8 * self.spec.topk)
        if ok.size > limit:
            ok = ok[:: max(1, int(ok.size) // limit)][:limit]
        lens = comp_len[ok].tolist()
        for i, clen in zip(ok.tolist(), lens):
            vb = comp[i, plen:clen].tobytes()
            cand.pop(vb, None)
            cand[vb] = None
        if len(cand) > limit:
            for k in list(cand)[: len(cand) - limit]:
                del cand[k]

    def _group_prefix(self, key: tuple) -> bytes:
        if not key:
            return b""
        return _FIELD_SEP.join(
            b"\x00" if part is None
            else part if isinstance(part, bytes)
            # an integer is not its decimal string: \x01 marks it (and
            # \x02 any other value), where no label's bytes begin
            else b"\x01%d" % part if type(part) is int
            else b"\x02" + repr(part).encode("utf-8", "replace")
            for part in key
        ) + _VALUE_SEP

    # -- event-time (per-record path only) ----------------------------

    def _absorb_event_time(self, ts: np.ndarray, strcols,
                           numcols, keycols) -> int:
        size = self.spec.window.size
        wid = np.floor(ts / size).astype(np.int64)
        wm = self._watermark
        absorbed = 0
        min_open = None
        if wm is not None:
            min_open = int(math.floor(wm / size))
        uniq, first_idx = np.unique(wid, return_index=True)
        for j in np.argsort(first_idx, kind="stable"):
            w = int(uniq[j])
            rows = np.nonzero(wid == w)[0]
            if min_open is not None and w < min_open:
                self.late_records_total += int(rows.size)
                continue
            pane = self._event_windows.get(w)
            if pane is None:
                pane = self._event_windows[w] = {}
            sc = {f: (b[rows], ln[rows]) for f, (b, ln) in strcols.items()}
            nc = {f: (v[rows], k[rows]) for f, (v, k) in numcols.items()}
            kc = {f: c.take(rows) for f, c in keycols.items()}
            self._absorb_rows(pane, int(rows.size), sc, nc, kc)
            absorbed += int(rows.size)
        new_wm = float(ts.max())
        if wm is None or new_wm > wm:
            self._watermark = new_wm
        self._close_event_windows()
        return absorbed

    def _close_event_windows(self) -> None:
        if self._watermark is None:
            return
        size = self.spec.window.size
        done = int(math.floor(self._watermark / size))
        for w in sorted(k for k in self._event_windows if k < done):
            pane = self._event_windows.pop(w)
            if pane:
                self._pending_closed.append(
                    ((w + 1) * size, list(pane.items())))
                self.window_emits_total += 1
                self.window_closes_total += 1

    # ------------------------------------------------------------ window

    def due(self, now: Optional[float] = None) -> bool:
        """Whether :meth:`tick` would close a window now."""
        w = self.spec.window
        if self.spec.event_time:
            return bool(self._pending_closed)
        if w.kind is None:
            return False
        now = self._now() if now is None else now
        return now - self._window_start >= w.advance

    def close_window(self, now: Optional[float], hand_on) -> bool:
        """One tick, and where it closes a window the whole of the close
        under the span ``flux.close`` and on the timings: the pane roll
        and merge (:meth:`tick`), then ``hand_on(closed) -> rows``, the
        caller's rendering and emit of the closed groups (not called
        for a window that holds none). → whether a window closed."""
        if not self.due(now):
            return False
        t0 = time.perf_counter()
        with span("flux.close", window=self.spec.window.kind) as sp:
            closed = self.tick(now)
            rows = hand_on(closed) if closed else 0
            sp.set_metadata(groups=len(closed), rows=rows,
                            panes=len(self._panes))
        tm = self.timings
        if tm is not None:
            tm.add("close_s", time.perf_counter() - t0)
            tm.add("closes", 1)
            tm.add("closed_groups", len(closed))
            tm.add("emitted_rows", rows)
        return True

    def tick(self, now: Optional[float] = None
             ) -> List[Tuple[tuple, _FluxGroup]]:
        """Close expired windows; returns the closed window's groups in
        first-seen order (empty list = nothing to emit).  Mirrors
        ``SPTask.tick`` arithmetic exactly in processing-time mode."""
        w = self.spec.window
        if self.spec.event_time:
            out: List[Tuple[tuple, _FluxGroup]] = []
            for _, items in self._pending_closed:
                out.extend(items)
            self._pending_closed = []
            return out
        if w.kind is None:
            return []
        now = self._now() if now is None else now
        if w.kind == "tumbling":
            if now - self._window_start < w.size:
                return []
            self._window_start += w.size * (
                (now - self._window_start) // w.size)
            self.window_closes_total += 1
            closed = list(self._groups.items())
            self._groups = {}
            if closed:
                self.window_emits_total += 1
            return closed
        # hopping
        if now - self._window_start < w.advance:
            return []
        self._window_start += w.advance * (
            (now - self._window_start) // w.advance)
        self.window_closes_total += 1
        self._panes.append(self._groups)
        self._groups = {}
        self._panes = self._panes[-w.n_panes:]
        merged: Dict[tuple, _FluxGroup] = {}
        for pane in self._panes:
            for key, g in pane.items():
                m = merged.get(key)
                if m is None:
                    m = merged[key] = _FluxGroup(self.spec)
                m.merge(g)
        out = list(merged.items())
        if out:
            self.window_emits_total += 1
        return out

    def drain(self) -> List[Tuple[tuple, _FluxGroup]]:
        """Shutdown: whatever the open window(s) accumulated (SPTask
        drain semantics for processing-time; all open event windows)."""
        if self.spec.event_time:
            for w in sorted(self._event_windows):
                pane = self._event_windows.pop(w)
                if pane:
                    self._pending_closed.append(
                        ((w + 1) * self.spec.window.size,
                         list(pane.items())))
            return self.tick()
        if self.spec.window.kind is None:
            return list(self._groups.items())
        for pane in self._panes:
            for key, g in pane.items():
                cur = self._groups.get(key)
                if cur is None:
                    self._groups[key] = g
                else:
                    cur.merge(g)
        self._panes = []
        closed = list(self._groups.items())
        self._groups = {}
        return closed

    def live_groups(self) -> List[Tuple[tuple, _FluxGroup]]:
        """The OPEN pane's groups (metrics exporter reads; does not
        disturb window accounting)."""
        if self.spec.event_time:
            merged: Dict[tuple, _FluxGroup] = {}
            for w in sorted(self._event_windows):
                for key, g in self._event_windows[w].items():
                    m = merged.get(key)
                    if m is None:
                        m = merged[key] = _FluxGroup(self.spec)
                    m.merge(g)
            return list(merged.items())
        return list(self._groups.items())

    # ------------------------------------------------------------- top-k

    def topk(self, key: tuple) -> List[Tuple[int, bytes]]:
        """Current hottest values for one group: (estimate, value),
        highest first — CMS point queries over the candidate set, one
        device→host table copy for the whole set."""
        if self.cms is None:
            return []
        cand = list(self._candidates.get(key, ()))
        if not cand:
            return []
        prefix = self._group_prefix(key)
        ests = self.cms.query_many([prefix + v for v in cand])
        top = sorted(zip(ests, cand),
                     key=lambda t: (-t[0], t[1]))[: self.spec.topk]
        return [(int(e), v) for e, v in top]

    # ------------------------------------------------------ snapshot/restore

    def snapshot(self) -> dict:
        """Read-only structural snapshot (window accounting untouched —
        rollover under a concurrent snapshot stays correct)."""

        def enc_pane(pane):
            out = []
            for key, g in pane.items():
                out.append({
                    "key": key,
                    "count": g.count,
                    "cols": {
                        f: (st.has, st.sum, st.min, st.max,
                            st.min_int, st.max_int)
                        for f, st in g.cols.items()
                    },
                    "hlls": {
                        f: np.asarray(h.registers).copy()
                        for f, h in g.hlls.items()
                    },
                })
            return out

        snap = {
            "version": SNAPSHOT_VERSION,
            "name": self.spec.name,
            "shape": self.spec.shape(),
            "window_start": self._window_start,
            "groups": enc_pane(self._groups),
            "panes": [enc_pane(p) for p in self._panes],
            "event_windows": {
                w: enc_pane(p) for w, p in self._event_windows.items()
            },
            "watermark": self._watermark,
            "cms": (np.asarray(self.cms.table).copy()
                    if self.cms is not None else None),
            "candidates": {k: list(v) for k, v in
                           self._candidates.items()},
            "counters": (self.records_total, self.late_records_total,
                         self.window_emits_total, self.batches_total),
            "window_closes": self.window_closes_total,
        }
        return snap

    def restore(self, snap: dict) -> None:
        if snap.get("version") != SNAPSHOT_VERSION:
            raise ValueError(
                f"flux snapshot version {snap.get('version')!r} "
                f"unsupported (want {SNAPSHOT_VERSION})")
        # a snapshot persisted under a different config must not
        # silently restore: group keys would have the wrong arity and
        # columns/sketches would misalign (window rows with missing or
        # shifted labels) — reject and let the caller start fresh
        if snap.get("name") != self.spec.name:
            raise ValueError(
                f"flux snapshot belongs to state "
                f"{snap.get('name')!r}, not {self.spec.name!r}")
        if snap.get("shape") != self.spec.shape():
            raise ValueError(
                f"flux snapshot shape {snap.get('shape')!r} does not "
                f"match this state's spec {self.spec.shape()!r}")

        def dec_pane(items):
            pane: Dict[tuple, _FluxGroup] = {}
            for it in items:
                g = _FluxGroup(self.spec)
                g.count = it["count"]
                for f, (has, s, mn, mx, mni, mxi) in it["cols"].items():
                    if f in g.cols:
                        st = g.cols[f]
                        st.has, st.sum = has, s
                        st.min, st.max = mn, mx
                        st.min_int, st.max_int = mni, mxi
                for f, regs in it["hlls"].items():
                    if f in g.hlls:
                        arr = np.asarray(regs).astype(np.int32).copy()
                        # belt-and-braces behind the shape() check: a
                        # wrong-sized register array would be an
                        # out-of-bounds write in the C kernel
                        if arr.shape != (g.hlls[f].m,):
                            raise ValueError(
                                f"flux snapshot HLL register shape "
                                f"{arr.shape} != ({g.hlls[f].m},)")
                        g.hlls[f].registers = arr
                pane[it["key"]] = g
            return pane

        # decode EVERYTHING into locals before touching self: a decode
        # failure mid-way must leave the state exactly as it was (the
        # old-or-new recovery contract; load() falls back to fresh)
        groups = dec_pane(snap["groups"])
        panes = [dec_pane(p) for p in snap["panes"]]
        event_windows = {
            w: dec_pane(p) for w, p in snap["event_windows"].items()
        }
        cms_table = None
        if self.cms is not None and snap.get("cms") is not None:
            cms_table = np.asarray(snap["cms"]).astype(
                np.asarray(self.cms.table).dtype).copy()
            want = (self.cms.depth, self.cms.width)
            if cms_table.shape != want:
                raise ValueError(
                    f"flux snapshot CMS table shape {cms_table.shape} "
                    f"!= {want}")
        candidates = {
            k: {v: None for v in vs}
            for k, vs in snap.get("candidates", {}).items()
        }
        (records, late, emits, batches) = snap["counters"]
        self._groups = groups
        self._panes = panes
        self._event_windows = event_windows
        self._watermark = snap["watermark"]
        self._window_start = snap["window_start"]
        if cms_table is not None:
            self.cms.table = cms_table
        self._candidates = candidates
        self.records_total = records
        self.late_records_total = late
        self.window_emits_total = emits
        self.batches_total = batches
        self.window_closes_total = snap.get("window_closes", emits)

    def persist(self, path: str) -> None:
        """Atomic snapshot write: tmp + fsync + rename — a crash at the
        armed ``flux.snapshot`` failpoint leaves the previous file
        intact (old-or-new, never torn)."""
        self.write_snapshot(self.snapshot(), path)

    @staticmethod
    def write_snapshot(snap: dict, path: str) -> None:
        """Write an already-built snapshot dict (see :meth:`persist`).
        Split out so callers holding the engine ingest lock can build
        the (read-only, in-memory) snapshot under the lock and do the
        pickle/write/fsync OUTSIDE it — a slow disk must not stall
        every input's append."""
        payload = pickle.dumps(snap, protocol=4)
        d = os.path.dirname(os.path.abspath(path)) or "."
        fd, tmp = tempfile.mkstemp(prefix=".flux-snap-", dir=d)
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(payload)
                f.flush()
                os.fsync(f.fileno())
            if _fp.ACTIVE:
                _fp.fire("flux.snapshot")
            os.replace(tmp, path)
        finally:
            try:
                if os.path.exists(tmp):
                    os.unlink(tmp)
            except OSError:
                pass  # best-effort tmp cleanup; the snapshot landed

    def load(self, path: str) -> bool:
        """Restore from a persisted snapshot; False = no/corrupt file
        (fresh state — the recovery contract is old-or-new)."""
        try:
            with open(path, "rb") as f:
                payload = f.read()
        except OSError:
            return False
        try:
            snap = pickle.loads(payload)
        except Exception:
            # numpy/format upgrades surface as AttributeError /
            # ImportError / TypeError from array reconstruction — the
            # recovery contract is "unusable snapshot → fresh state",
            # never "pipeline fails to start"
            import logging

            logging.getLogger("flb.flux").warning(
                "flux snapshot %s undecodable; starting fresh", path,
                exc_info=True)
            return False
        try:
            self.restore(snap)
        except (KeyError, ValueError, TypeError):
            import logging

            logging.getLogger("flb.flux").warning(
                "flux snapshot %s unusable; starting fresh", path)
            return False
        return True
