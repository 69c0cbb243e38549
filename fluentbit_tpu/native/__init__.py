"""Native data-plane shim — ctypes bindings for fbtpu_native.

Builds native/fbtpu_native.cpp with g++ on first use (cached as
``native/build/fbtpu_native.so``; pybind11 is not available in this
image so the ABI is plain C via ctypes). Every entry point degrades
gracefully: if the toolchain or the .so is unavailable, callers fall
back to the pure-Python codec (``available()`` reports which path is
active).

API:
  count_records(buf)                       → int | None
  scan_offsets(buf)                        → numpy int64 offsets | None
  stage_field(buf, key, max_len, pad_to)   → (batch, lengths, offsets,
                                              n) | None
"""

from __future__ import annotations

import ctypes
import logging
import os
import threading
from typing import Optional, Tuple

import numpy as np

log = logging.getLogger("flb.native")

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(os.path.dirname(os.path.dirname(_HERE)),
                    "native", "fbtpu_native.cpp")
_BUILD_DIR = os.path.join(os.path.dirname(os.path.dirname(_HERE)),
                          "native", "build")
_SO = os.path.join(_BUILD_DIR, "fbtpu_native.so")

_lock = threading.Lock()
_lib = None
_tried = False


def _load():
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("FBTPU_NO_NATIVE"):
            return None
        # hash-cached build with prebuilt trust paths (buildlib: a
        # KNOWN-stale .so never loads — its ABI may not match the
        # Python callers, and a silent mismatch corrupts memory)
        from .buildlib import ensure_built

        cmd = ["g++", "-O3", "-fPIC", "-shared", "-std=c++17",
               "-pthread", _SRC, "-o", _SO]
        if not ensure_built(_SRC, _SO, cmd):
            return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError as e:
            log.warning("native load failed: %s", e)
            return None
        lib.fbtpu_count_records.restype = ctypes.c_longlong
        lib.fbtpu_count_records.argtypes = [ctypes.c_char_p,
                                            ctypes.c_longlong]
        lib.fbtpu_scan_offsets.restype = ctypes.c_longlong
        lib.fbtpu_scan_offsets.argtypes = [
            ctypes.c_char_p, ctypes.c_longlong,
            ctypes.POINTER(ctypes.c_longlong), ctypes.c_longlong,
        ]
        lib.fbtpu_stage_field.restype = ctypes.c_longlong
        lib.fbtpu_stage_field.argtypes = [
            ctypes.c_char_p, ctypes.c_longlong,
            ctypes.c_char_p, ctypes.c_longlong,
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_int32),
            ctypes.c_longlong, ctypes.c_longlong,
            ctypes.POINTER(ctypes.c_longlong),
        ]
        mt_fn = getattr(lib, "fbtpu_stage_field_mt", None)
        if mt_fn is not None:
            mt_fn.restype = ctypes.c_longlong
            mt_fn.argtypes = [
                ctypes.c_char_p, ctypes.c_longlong,
                ctypes.c_char_p, ctypes.c_longlong,
                ctypes.POINTER(ctypes.c_uint8),
                ctypes.POINTER(ctypes.c_int32),
                ctypes.c_longlong, ctypes.c_longlong,
                ctypes.POINTER(ctypes.c_longlong), ctypes.c_int32,
            ]
        eff_fn = getattr(lib, "fbtpu_stage_effective_threads", None)
        if eff_fn is not None:
            eff_fn.restype = ctypes.c_int32
            eff_fn.argtypes = [ctypes.c_int32]
        # fbtpu-flux entry points (absent in a stale prebuilt .so:
        # callers then stay on their Python/device paths)
        f64_fn = getattr(lib, "fbtpu_stage_field_f64", None)
        if f64_fn is not None:
            f64_fn.restype = ctypes.c_longlong
            f64_fn.argtypes = [
                ctypes.c_char_p, ctypes.c_longlong,
                ctypes.c_char_p, ctypes.c_longlong,
                ctypes.POINTER(ctypes.c_double),
                ctypes.POINTER(ctypes.c_uint8),
                ctypes.c_longlong, ctypes.POINTER(ctypes.c_longlong),
            ]
        i64_fn = getattr(lib, "fbtpu_stage_field_i64", None)
        if i64_fn is not None:
            i64_fn.restype = ctypes.c_longlong
            i64_fn.argtypes = [
                ctypes.c_char_p, ctypes.c_longlong,
                ctypes.c_char_p, ctypes.c_longlong,
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_uint8),
                ctypes.c_longlong,
            ]
        hll_fn = getattr(lib, "fbtpu_hll_update", None)
        if hll_fn is not None:
            hll_fn.restype = None
            hll_fn.argtypes = [
                ctypes.POINTER(ctypes.c_uint8),
                ctypes.POINTER(ctypes.c_int32),
                ctypes.c_longlong, ctypes.c_longlong, ctypes.c_int32,
                ctypes.POINTER(ctypes.c_int32),
            ]
        cms_fn = getattr(lib, "fbtpu_cms_update", None)
        if cms_fn is not None:
            cms_fn.restype = ctypes.c_longlong
            cms_fn.argtypes = [
                ctypes.POINTER(ctypes.c_uint8),
                ctypes.POINTER(ctypes.c_int32),
                ctypes.c_longlong, ctypes.c_longlong,
                ctypes.c_int32, ctypes.c_int32,
                ctypes.c_void_p, ctypes.c_int32,
            ]
        lib.fbtpu_compact.restype = ctypes.c_longlong
        lib.fbtpu_compact.argtypes = [
            ctypes.c_char_p, ctypes.c_longlong,
            ctypes.POINTER(ctypes.c_longlong),
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_longlong, ctypes.POINTER(ctypes.c_uint8),
        ]
        try:
            grep_fn = lib.fbtpu_grep_match_v2
        except AttributeError:
            # prebuilt .so from an older source (hash-less trust path):
            # the scanner entry points still work; grep_match() reports
            # unavailable and callers use their staged/Python paths
            grep_fn = None
            log.warning("fbtpu_grep_match_v2 absent in %s (stale prebuilt?)",
                        _SO)
        if grep_fn is not None:
            grep_fn.restype = ctypes.c_longlong
            grep_fn.argtypes = _grep_match_argtypes()
        filter_fn = getattr(lib, "fbtpu_grep_filter", None)
        if filter_fn is not None:
            i32p = ctypes.POINTER(ctypes.c_int32)
            i64p = ctypes.POINTER(ctypes.c_longlong)
            filter_fn.restype = ctypes.c_longlong
            filter_fn.argtypes = [
                ctypes.c_char_p, ctypes.c_longlong,       # buf
                ctypes.c_char_p,                          # keys_cat
                i64p, ctypes.c_longlong,                  # key_offs
                i32p, ctypes.c_longlong,                  # key_of_rule
                ctypes.POINTER(ctypes.c_int16),           # trans_cat
                i64p,                                     # troffs
                i32p, i32p, i32p,                         # cmaps/starts/ncls
                ctypes.POINTER(ctypes.c_uint16), i64p,    # cmap2/cm2offs
                ctypes.POINTER(ctypes.c_int16), i64p,     # btrans/btroffs
                ctypes.POINTER(ctypes.c_uint32), i64p,    # accel/aoffs
                ctypes.POINTER(ctypes.c_uint8),           # rule_exclude
                ctypes.c_int32,                           # op_mode
                ctypes.c_longlong,                        # max_records
                ctypes.POINTER(ctypes.c_uint8),           # out
                i64p,                                     # out_info
            ]
        _lib = lib
        return _lib


def _grep_match_argtypes():
    return [
            ctypes.c_char_p, ctypes.c_longlong,          # buf
            ctypes.c_char_p,                             # keys_cat
            ctypes.POINTER(ctypes.c_longlong), ctypes.c_longlong,
            ctypes.POINTER(ctypes.c_int32), ctypes.c_longlong,
            ctypes.POINTER(ctypes.c_int16),              # trans_cat (i16)
            ctypes.POINTER(ctypes.c_longlong),           # troffs
            ctypes.POINTER(ctypes.c_int32),              # cmaps
            ctypes.POINTER(ctypes.c_int32),              # starts
            ctypes.POINTER(ctypes.c_int32),              # ncls
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_longlong,
            ctypes.POINTER(ctypes.c_longlong),           # offsets
        ]


def available() -> bool:
    return _load() is not None


def _buf_arg(buf):
    """(arg, length, keepalive) presenting any C-contiguous bytes-like
    object to a ``c_char_p`` parameter WITHOUT copying. ``bytes`` goes
    straight through ctypes; memoryview / mmap / bytearray / uint8
    ndarray views travel as a raw pointer into the existing buffer
    (``c_char_p`` rejects non-bytes and ``from_buffer`` fails on
    read-only mmaps, so the pointer is taken through a zero-copy
    ``np.frombuffer`` view). The keepalive object must stay referenced
    for the duration of the native call — callers hold it in a local.

    This is what lets the mmap replay path (core/storage.py) hand
    chunk-file pages straight to the C walker: the bytes are untrusted
    and possibly crash-torn, which is exactly the load the
    untrusted-bytes bounds gate on the native side proves safe
    (analysis/native_gate.py, rule untrusted-bytes-bounds)."""
    if isinstance(buf, bytes):
        return buf, len(buf), None
    arr = np.frombuffer(buf, dtype=np.uint8)
    return arr.ctypes.data_as(ctypes.c_char_p), arr.size, arr


def count_records(buf) -> Optional[int]:
    lib = _load()
    if lib is None:
        return None
    p, blen, _keep = _buf_arg(buf)
    n = lib.fbtpu_count_records(p, blen)
    return None if n < 0 else int(n)


def scan_offsets(buf) -> Optional[np.ndarray]:
    lib = _load()
    if lib is None:
        return None
    p, blen, _keep = _buf_arg(buf)
    # worst case: 1-byte records
    cap = blen + 1
    offsets = np.empty(cap + 1, dtype=np.int64)
    n = lib.fbtpu_scan_offsets(
        p, blen,
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)), cap,
    )
    if n < 0:
        return None
    return offsets[: n + 1]


def compact(buf, offsets: np.ndarray,
            keep: np.ndarray) -> Optional[bytes]:
    """Order-preserving copy of the records with keep[i] True straight
    from the source buffer (the raw grep path's survivor re-emit)."""
    lib = _load()
    if lib is None:
        return None
    p, blen, _keep_ref = _buf_arg(buf)
    n = len(keep)
    out = np.empty(blen, dtype=np.uint8)
    keep_u8 = np.ascontiguousarray(keep, dtype=np.uint8)
    offs = np.ascontiguousarray(offsets, dtype=np.int64)
    w = lib.fbtpu_compact(
        p, blen,
        offs.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
        keep_u8.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        n,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
    )
    if w < 0:
        return None
    return out[:w].tobytes()


def _build_accel(trans: np.ndarray, class_map: np.ndarray):
    """Per-state escape-byte acceleration (the self-loop-skipping
    design documented at native/fbtpu_native.cpp: states that leave
    only on <=2 bytes get a memchr/SIMD skip instead of a table walk).

    accel[s] u32: bits 0-1 = 0 none / 1 one escape byte / 2 two /
    3 no escape bytes at all (state is fixed until EOL);
    bits 8-15 byte1; 16-23 byte2. Returns (accel u32[S], usable bool).

    Opt-in (FBTPU_ACCEL=1): on the bench corpus (short ~10-30 byte
    fields between delimiters) the scalar skip chain MEASURES SLOWER
    than the 16-lane interleaved k-composed walk — the skips save few
    table loads while forfeiting cross-record load-latency hiding
    (4.4M vs 8.1M lines/s). It wins on long self-loop runs (multi-KB
    lines, .*-tail patterns), so the engine stays available and
    differentially tested rather than default."""
    S = trans.shape[0]
    if not os.environ.get("FBTPU_ACCEL"):
        return np.zeros(1, dtype=np.uint32), False  # analysis skipped
    cm = class_map[:256].astype(np.int64)
    tb = trans[:, cm]  # [S, 256] next state per BYTE
    esc = tb != np.arange(S, dtype=tb.dtype)[:, None]
    n_esc = esc.sum(axis=1)
    accel = np.zeros(S, dtype=np.uint32)
    accel[n_esc == 0] = 3
    for s in np.nonzero(n_esc == 1)[0]:
        b = int(np.nonzero(esc[s])[0][0])
        accel[s] = 1 | (b << 8)
    for s in np.nonzero(n_esc == 2)[0]:
        b1, b2 = (int(x) for x in np.nonzero(esc[s])[0][:2])
        accel[s] = 2 | (b1 << 8) | (b2 << 16)
    skippy = int((accel != 0).sum())
    return accel, skippy * 20 >= S and skippy >= 2


class GrepTables:
    """Packed DFA tables for the one-pass native grep matcher — the
    host-side twin of ops.grep.GrepProgram (same tables, k=1). Verdicts
    are bit-exact with the device kernel and the Python regex engine."""

    __slots__ = ("n_rules", "keys_cat", "key_offs", "key_of_rule",
                 "trans_cat", "troffs", "cmaps", "starts", "ncls",
                 "cmap2_cat", "cm2offs", "btrans_cat", "btroffs",
                 "accel_cat", "aoffs", "decisions")

    def __init__(self, rules):
        """rules: iterable of (field_key: bytes, dfa) pairs."""
        keys: list = []
        key_idx = {}
        key_of_rule = []
        trans_parts = []
        troffs = [0]
        cmaps = []
        cmap2_parts = []
        cm2offs = []
        cm2_len = 0
        starts = []
        ncls = []
        btrans_parts = []
        btroffs = []
        btrans_len = 0
        accel_parts = []
        aoffs = []
        accel_len = 0
        # fbtpu-shrink audit: per-rule (S, C, chosen native k) plus the
        # compile pass's before-shapes — the native twin of
        # ops.grep.GrepProgram.decision(), recorded so bench/debug can
        # see that the reduced tables actually reached the C walker
        decisions: list = []
        for key, dfa in rules:
            if key not in key_idx:
                key_idx[key] = len(keys)
                keys.append(key)
            key_of_rule.append(key_idx[key])
            from ..regex.dfa import compose_supersteps

            t = np.ascontiguousarray(dfa.trans, dtype=np.int32)
            S, C = t.shape
            # pre-compose to k-byte super-steps (cuts the dependent-load
            # chain k-fold) while [S, C^k] stays cache-friendly; the
            # packed class count encodes C + 1000*(k-1) for the C side
            if S >= 32768:  # int16 table states (never in practice)
                raise ValueError(f"DFA too large for native tables ({S})")
            budget = int(os.environ.get("FBTPU_KTABLE_BUDGET",
                                        str(2 * 1024 * 1024)))
            # EVEN k preferred: the prepass then classifies via the
            # byte-PAIR table (one load per two bytes). k=4 may exceed
            # the plain budget — the walk only touches the visited
            # states' rows, so a larger-but-cold table still wins.
            k4_budget = int(os.environ.get("FBTPU_K4_BUDGET",
                                           str(12 * 1024 * 1024)))
            if C ** 4 <= 65535 and S * (C ** 4) * 2 <= k4_budget:
                k = 4
            else:
                k = 1
                # C^k <= 65535: super-symbols travel as uint16 through
                # the prepass scratch (dfa_prepass_block)
                while (k < 4 and S * (C ** (k + 1)) * 2 <= budget
                       and C ** (k + 1) <= 65535):
                    k += 1
                if k >= 2 and k % 2 == 1:
                    k -= 1  # even k unlocks the pair-table prepass
            st = getattr(dfa, "shrink", None)
            decisions.append({
                "s": S, "c": C, "k": k,
                "s_raw": st.s_raw if st is not None else None,
                "c_raw": st.c_raw if st is not None else None,
                "minimized": bool(st.minimized) if st is not None
                else False,
                "table_bytes": int(S * (C ** k) * 2),
            })
            tk = compose_supersteps(t, k)
            trans_parts.append(np.ascontiguousarray(
                tk, dtype=np.int16).reshape(-1))
            troffs.append(troffs[-1] + tk.size)
            ncls.append(C + 1000 * (k - 1))
            cmaps.append(np.ascontiguousarray(
                dfa.class_map, dtype=np.int32))
            if k % 2 == 0:
                # cmap2[b0 + (b1<<8)] = class(b0)*C + class(b1)
                cm = dfa.class_map[:256].astype(np.uint32)
                w = np.arange(65536, dtype=np.uint32)
                pair = cm[w & 255] * C + cm[w >> 8]
                cmap2_parts.append(pair.astype(np.uint16))
                cm2offs.append(cm2_len)
                cm2_len += 65536
            else:
                cm2offs.append(-1)
            starts.append(dfa.start)
            # escape-byte accel: byte-level table + skip words for
            # DFAs whose states mostly self-loop (log-matching shapes)
            accel, usable = _build_accel(t, dfa.class_map)
            if usable:
                aoffs.append(accel_len)
                accel_parts.append(accel)
                accel_len += accel.size
                btrans_parts.append(np.ascontiguousarray(
                    t, dtype=np.int16).reshape(-1))
                btroffs.append(btrans_len)
                btrans_len += t.size
            else:
                aoffs.append(-1)
                btroffs.append(0)
        self.n_rules = len(key_of_rule)
        self.decisions = decisions
        self.keys_cat = b"".join(keys)
        offs = [0]
        for k in keys:
            offs.append(offs[-1] + len(k))
        self.key_offs = np.asarray(offs, dtype=np.int64)
        self.key_of_rule = np.asarray(key_of_rule, dtype=np.int32)
        self.trans_cat = np.concatenate(trans_parts)
        self.troffs = np.asarray(troffs[:-1], dtype=np.int64)
        self.cmaps = np.concatenate(cmaps)
        self.cmap2_cat = (np.concatenate(cmap2_parts) if cmap2_parts
                          else np.zeros(1, dtype=np.uint16))
        self.cm2offs = np.asarray(cm2offs, dtype=np.int64)
        # DFA start-STATE ids (bounded by the state count, < 2^15), not
        # byte offsets; the C ABI takes int32 here
        # fbtpu-lint: allow(dtype-narrowing)
        self.starts = np.asarray(starts, dtype=np.int32)
        self.ncls = np.asarray(ncls, dtype=np.int32)
        self.btrans_cat = (np.concatenate(btrans_parts) if btrans_parts
                           else np.zeros(1, dtype=np.int16))
        self.btroffs = np.asarray(btroffs, dtype=np.int64)
        self.accel_cat = (np.concatenate(accel_parts) if accel_parts
                          else np.zeros(1, dtype=np.uint32))
        self.aoffs = np.asarray(aoffs, dtype=np.int64)

    def thread_copy(self) -> "GrepTables":
        """A private copy of the packed arrays for one worker thread.

        The tables are read-only so sharing is CORRECT — but with
        several inputs ingesting concurrently every walker hammers the
        same physical arrays, and on small hosts the shared hot lines
        serialize in the cache hierarchy (an earlier CPU-host run:
        inputs4 at 0.92× of inputs1). Each ingest thread matching through its own copy
        keeps the walk NUMA/cache-local; the copy is a few hundred KB,
        made once per (thread, filter)."""
        new = self.__class__.__new__(self.__class__)
        slots = []
        for klass in type(self).__mro__:
            slots.extend(getattr(klass, "__slots__", ()))
        for slot in slots:
            v = getattr(self, slot)
            setattr(new, slot,
                    v.copy() if isinstance(v, np.ndarray) else v)
        return new


def grep_match(buf, tables: GrepTables, n_hint: Optional[int] = None
               ) -> Optional[Tuple[np.ndarray, np.ndarray, int]]:
    """One-pass field-extract + DFA match over chunk bytes. Returns
    (mask[R, n] bool, offsets[n+1] i64, n) or None (native unavailable /
    malformed buffer)."""
    lib = _load()
    if lib is None or getattr(lib, "fbtpu_grep_match_v2", None) is None:
        return None
    est = n_hint if n_hint is not None else count_records(buf)
    if est is None:
        return None
    p, blen, _keep = _buf_arg(buf)
    R = tables.n_rules
    cap = max(est, 1)  # match/offsets sized to the capacity granted to C
    match = np.empty((R, cap), dtype=np.uint8)
    offsets = np.empty(cap + 1, dtype=np.int64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_longlong)
    n = getattr(lib, "fbtpu_grep_match_v2")(
        p, blen,
        tables.keys_cat,
        tables.key_offs.ctypes.data_as(i64p),
        len(tables.key_offs) - 1,
        tables.key_of_rule.ctypes.data_as(i32p), R,
        tables.trans_cat.ctypes.data_as(ctypes.POINTER(ctypes.c_int16)),
        tables.troffs.ctypes.data_as(i64p),
        tables.cmaps.ctypes.data_as(i32p),
        tables.starts.ctypes.data_as(i32p),
        tables.ncls.ctypes.data_as(i32p),
        match.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        cap,
        offsets.ctypes.data_as(i64p),
    )
    if n < 0:
        return None
    # u8 0/1 → bool is a reinterpret, not a copy (match is freshly
    # allocated per call, so the view escapes safely)
    return match[:, :n].view(bool), offsets[: n + 1], int(n)


class GrepFilterTables(GrepTables):
    """GrepTables (k-super-stepped int16 transition tables) plus the
    verdict inputs for the fused one-pass filter (fbtpu_grep_filter):
    per-rule exclude flags and the logical_op mode. The matcher splits
    each record into a branchless super-symbol prepass and a
    two-loads-per-step lockstep walk (dfa_prepass_block)."""

    __slots__ = ("excl", "op_mode")

    def __init__(self, rules, op: str = "legacy"):
        """rules: iterable of (field_key: bytes, dfa, is_exclude) trios."""
        rules = list(rules)
        super().__init__([(key, dfa) for key, dfa, _ in rules])
        self.excl = np.asarray(
            [1 if is_exclude else 0 for _, _, is_exclude in rules],
            dtype=np.uint8)
        self.op_mode = {"LEGACY": 0, "AND": 1, "OR": 2}.get(op.upper(), 0)


_tls = threading.local()


def _arena(size: int) -> np.ndarray:
    """Reusable per-thread output buffer (the fused filter writes the
    compacted chunk here; the engine copies it into the chunk store
    before the next call on this thread can overwrite it)."""
    buf = getattr(_tls, "out", None)
    if buf is None or buf.size < size:
        buf = np.empty(max(size, 1 << 20), dtype=np.uint8)
        _tls.out = buf
    return buf


def grep_filter(buf, tables: "GrepFilterTables",
                n_hint: Optional[int] = None):
    """One-pass extract + accel-DFA + verdict + compaction.

    Returns (n_records, n_kept, out) where out is the original ``buf``
    when nothing was dropped, b"" when everything was, else a memoryview
    of this thread's arena holding the surviving records byte-identically
    (caller must consume it before its next grep_filter call on this
    thread). None = native unavailable / malformed buffer."""
    lib = _load()
    if lib is None or getattr(lib, "fbtpu_grep_filter", None) is None:
        return None
    # non-bytes buffers (bytearray / memoryview / mmap view) travel as
    # a raw pointer — the walker reads them in place (the memscope
    # host-redundant-copy fix: this path used to materialize a bytes()
    # copy of every bytearray chunk before the call)
    p, blen, _keep = _buf_arg(buf)
    # no counting pre-pass: the walk discovers the record count, so an
    # unknown count just means sizing scratch to the 3-bytes-per-record
    # floor (array [ts, body] is at least 3 bytes)
    cap = max(n_hint if n_hint is not None else blen // 3 + 1, 1)
    out = _arena(blen)
    if _keep is not None:
        # a chained filter may hand back THIS thread's arena view from
        # a previous call: the walker writes survivors into the arena
        # while reading, so an aliased input must be materialized (the
        # one case the zero-copy pointer path cannot serve)
        p_addr = ctypes.cast(p, ctypes.c_void_p).value or 0
        o_addr = out.ctypes.data
        if o_addr <= p_addr < o_addr + out.size:
            buf = bytes(buf)
            p, blen, _keep = _buf_arg(buf)
    info = np.zeros(3, dtype=np.int64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_longlong)
    w = lib.fbtpu_grep_filter(
        p, blen,
        tables.keys_cat,
        tables.key_offs.ctypes.data_as(i64p),
        len(tables.key_offs) - 1,
        tables.key_of_rule.ctypes.data_as(i32p), tables.n_rules,
        tables.trans_cat.ctypes.data_as(ctypes.POINTER(ctypes.c_int16)),
        tables.troffs.ctypes.data_as(i64p),
        tables.cmaps.ctypes.data_as(i32p),
        tables.starts.ctypes.data_as(i32p),
        tables.ncls.ctypes.data_as(i32p),
        tables.cmap2_cat.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
        tables.cm2offs.ctypes.data_as(i64p),
        tables.btrans_cat.ctypes.data_as(ctypes.POINTER(ctypes.c_int16)),
        tables.btroffs.ctypes.data_as(i64p),
        tables.accel_cat.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        tables.aoffs.ctypes.data_as(i64p),
        tables.excl.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        tables.op_mode,
        cap,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        info.ctypes.data_as(i64p),
    )
    if w < 0:
        return None
    n, n_keep, wrote = int(info[0]), int(info[1]), int(info[2])
    if not wrote:
        return n, n_keep, buf
    if n_keep == 0:
        return n, 0, b""
    # the arena view IS the documented contract (docstring: consume
    # before this thread's next grep_filter call); the engine copies it
    # into the chunk store
    # fbtpu-lint: allow(host-mutable-view-escape)
    return n, n_keep, memoryview(out)[:w]


_stage_threads_cached: Optional[int] = None


def _stage_threads() -> int:
    global _stage_threads_cached
    if _stage_threads_cached is None:
        try:
            _stage_threads_cached = int(
                os.environ.get("FBTPU_STAGE_THREADS", "0")
            ) or (os.cpu_count() or 1)
        except ValueError:
            _stage_threads_cached = os.cpu_count() or 1
    return _stage_threads_cached


def stage_threads() -> int:
    """Requested stager fan-out (``FBTPU_STAGE_THREADS``, default = all
    cores). The native pool may clamp this to the hardware — see
    :func:`stage_threads_effective`."""
    return _stage_threads()


def stage_threads_effective(requested: Optional[int] = None) -> Optional[int]:
    """What the native pool will ACTUALLY fan a stage call out to after
    its hardware/16-way caps (``fbtpu_stage_effective_threads``) — the
    truth the bench RESULT records so a multi-core lane's scaling
    number can be read against the real slice count. None = native
    unavailable or an older .so without the probe."""
    lib = _load()
    fn = getattr(lib, "fbtpu_stage_effective_threads", None) \
        if lib is not None else None
    if fn is None:
        return None
    return int(fn(requested if requested is not None else _stage_threads()))


def stage_field_into(
    buf, key: bytes, out_batch: np.ndarray,
    out_lengths: np.ndarray, n_hint: Optional[int] = None,
    threads: Optional[int] = None,
    offsets_out: Optional[np.ndarray] = None,
) -> Optional[int]:
    """Stage one top-level string field DIRECTLY into caller-provided
    arrays — the per-device staging path of the mesh plane: the caller
    hands one rule-row slice of its ``[R, Bp, L]`` segment matrix
    (``out_batch`` u8 ``[B, L]`` C-contiguous, ``out_lengths`` i32
    ``[B]``) and the extraction fans out across the native worker pool
    (``FBTPU_STAGE_THREADS`` / ``threads``), each slice of records
    walking lock-free into its own row range. No arena, no copy-out —
    the staged bytes land where the device transfer reads them.

    Writes rows ``[0, n)`` only (bytes past each row's length are NOT
    zeroed; both DFA kernels mask by length); rows past ``n`` are left
    untouched, so pre-fill ``out_lengths`` with -1 for pad rows.
    ``offsets_out`` (i64, ≥ est+1 entries, contiguous) receives the
    record boundary table the walk discovers anyway — callers that
    need it (compaction, overflow decode) must NOT re-scan the buffer.
    Returns the record count, or None (native unavailable / malformed
    buffer / capacity exceeded / non-contiguous or mistyped target)."""
    lib = _load()
    if lib is None:
        return None
    est = n_hint if n_hint is not None else count_records(buf)
    if est is None:
        return None
    B, L = out_batch.shape
    if est > B or out_batch.dtype != np.uint8 \
            or not out_batch.flags["C_CONTIGUOUS"] \
            or out_lengths.dtype != np.int32 or out_lengths.shape[0] < B \
            or not out_lengths.flags["C_CONTIGUOUS"]:
        return None
    if offsets_out is not None:
        if offsets_out.dtype != np.int64 \
                or offsets_out.shape[0] < est + 1 \
                or not offsets_out.flags["C_CONTIGUOUS"]:
            return None
        offsets = offsets_out
    else:
        offsets = np.empty(est + 1, dtype=np.int64)
    p_b = out_batch.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
    p_l = out_lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
    p_o = offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong))
    # mmap replay staging: buf may be a read-only view of chunk-file
    # pages — the extraction walks them in place, no host copy between
    # the page cache and the caller's transfer matrix
    p, blen, _keep = _buf_arg(buf)
    mt_fn = getattr(lib, "fbtpu_stage_field_mt", None)
    if mt_fn is not None:
        n = mt_fn(p, blen, key, len(key), p_b, p_l, est, L, p_o,
                  threads if threads is not None else _stage_threads())
    else:
        n = lib.fbtpu_stage_field(p, blen, key, len(key), p_b, p_l,
                                  est, L, p_o)
    return None if n < 0 else int(n)


def stage_field(
    buf, key: bytes, max_len: int, pad_to: Optional[int] = None,
    n_hint: Optional[int] = None, threads: Optional[int] = None,
) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray, int]]:
    """Fill the staging matrix for one top-level string field straight
    from chunk bytes: (batch[B, L] u8, lengths[B] i32, offsets[n+1] i64,
    n_records). ``pad_to`` rounds B for jit shape stability; ``n_hint``
    (a caller-known record count) skips the counting pre-pass.

    The returned arrays are views of a per-thread arena reused across
    calls (the staging-ceiling fix of an earlier round: a fresh zeroed
    [B, L] matrix per chunk was pure memset bandwidth) — consume or copy them
    before this thread's next stage_field call. Bytes past lengths[i]
    in a row are NOT zeroed; consumers mask by length (both DFA kernels
    do). Extraction fans out across the native worker pool
    (fbtpu_stage_field_mt) when the chunk is large enough."""
    lib = _load()
    if lib is None:
        return None
    est = n_hint if n_hint is not None else count_records(buf)
    if est is None:
        return None
    B = pad_to if pad_to and pad_to >= est else est
    arena = getattr(_tls, "stage", None)
    if (arena is None or arena[0].shape[0] < B
            or arena[0].shape[1] != max_len):
        batch = np.zeros((max(B, 1024), max_len), dtype=np.uint8)
        lengths = np.empty((batch.shape[0],), dtype=np.int32)
        offsets = np.empty(batch.shape[0] + 1, dtype=np.int64)
        # ctypes pointers cached alongside: data_as() builds fresh
        # pointer objects (~µs each), pure overhead at bench chunk rates
        _tls.stage = arena = (
            batch, lengths, offsets,
            batch.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
        )
    batch, lengths, offsets, p_b, p_l, p_o = arena
    p, blen, _keep = _buf_arg(buf)
    mt_fn = getattr(lib, "fbtpu_stage_field_mt", None)
    if mt_fn is not None:
        n = mt_fn(p, blen, key, len(key), p_b, p_l, est, max_len,
                  p_o, threads if threads is not None else _stage_threads())
    else:
        n = lib.fbtpu_stage_field(p, blen, key, len(key), p_b, p_l,
                                  est, max_len, p_o)
    if n < 0:
        return None
    n = int(n)
    if n < B:
        lengths[n:B] = -1  # pad rows (jit shape stability) stay "missing"
    return batch[:B], lengths[:B], offsets[: n + 1], n


def stage_field_f64(
    buf: bytes, key: bytes, n_hint: Optional[int] = None,
) -> Optional[Tuple[np.ndarray, np.ndarray, int]]:
    """Stage one top-level NUMERIC field straight from chunk bytes:
    (values[B] f64, kinds[B] u8, n_records). kinds: 0 = missing/
    non-numeric (strings are non-numeric — the exact aggregate rule),
    1 = msgpack integer, 2 = msgpack float. Freshly allocated arrays
    (no arena: flux window state holds onto per-chunk columns)."""
    lib = _load()
    if lib is None or getattr(lib, "fbtpu_stage_field_f64", None) is None:
        return None
    est = n_hint if n_hint is not None else count_records(buf)
    if est is None:
        return None
    values = np.zeros((max(est, 1),), dtype=np.float64)
    kinds = np.zeros((max(est, 1),), dtype=np.uint8)
    n = lib.fbtpu_stage_field_f64(
        buf, len(buf), key, len(key),
        values.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        kinds.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        est, None,
    )
    if n < 0:
        return None
    n = int(n)
    return values[:n], kinds[:n], n


def stage_field_i64(
    buf, key: bytes, n_hint: Optional[int] = None,
) -> Optional[Tuple[np.ndarray, np.ndarray, int]]:
    """Stage one top-level field as a typed GROUP BY key straight from
    chunk bytes: (values[B] i64, kinds[B] u8, n_records). kinds, the
    ``KEY_*`` codes of ``flux/state.py``: 0 = missing or nil, 1 = a
    string (``stage_field_into`` carries the bytes), 2 = an integer
    int64 holds exactly (its value in ``values``), 3 = anything else
    (float, bool, uint64 past 2^63-1, bin, nested): what the batched
    flux path declines on. Freshly allocated arrays."""
    lib = _load()
    if lib is None or getattr(lib, "fbtpu_stage_field_i64", None) is None:
        return None
    est = n_hint if n_hint is not None else count_records(buf)
    if est is None:
        return None
    values = np.zeros((max(est, 1),), dtype=np.int64)
    kinds = np.zeros((max(est, 1),), dtype=np.uint8)
    p, blen, _keep = _buf_arg(buf)
    n = lib.fbtpu_stage_field_i64(
        p, blen, key, len(key),
        values.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        kinds.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        est,
    )
    if n < 0:
        return None
    n = int(n)
    return values[:n], kinds[:n], n


def has_flux_stagers() -> bool:
    """True when the loaded .so exports the flux entry points (a stale
    prebuilt library may predate them — callers should then skip the
    batched flux path once instead of probing per chunk)."""
    lib = _load()
    return lib is not None and \
        getattr(lib, "fbtpu_stage_field_f64", None) is not None and \
        getattr(lib, "fbtpu_stage_field_i64", None) is not None


def hll_update(registers: np.ndarray, batch: np.ndarray,
               lengths: np.ndarray, p: int) -> bool:
    """C twin of the device HLL register update over a staged [B, L]
    batch — bit-identical to HyperLogLog.add_cpu row by row. Mutates
    ``registers`` (int32 [2^p]) in place; False = native unavailable."""
    lib = _load()
    if lib is None or getattr(lib, "fbtpu_hll_update", None) is None:
        return False
    batch = np.ascontiguousarray(batch, dtype=np.uint8)
    lengths = np.ascontiguousarray(lengths, dtype=np.int32)
    B, L = batch.shape
    lib.fbtpu_hll_update(
        batch.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        B, L, int(p),
        registers.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    return True


def cms_update(table: np.ndarray, batch: np.ndarray,
               lengths: np.ndarray) -> bool:
    """C twin of the device count-min scatter-add (weight 1 per valid
    row). Mutates ``table`` ([d, w] int32/int64) in place."""
    lib = _load()
    if lib is None or getattr(lib, "fbtpu_cms_update", None) is None:
        return False
    if table.dtype == np.int32:
        elem = 4
    elif table.dtype == np.int64:
        elem = 8
    else:
        return False
    batch = np.ascontiguousarray(batch, dtype=np.uint8)
    lengths = np.ascontiguousarray(lengths, dtype=np.int32)
    B, L = batch.shape
    depth, width = table.shape
    rc = lib.fbtpu_cms_update(
        batch.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        B, L, int(depth), int(width),
        table.ctypes.data_as(ctypes.c_void_p), elem,
    )
    return rc == 0
