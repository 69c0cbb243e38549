"""Dynamic (.so) plugin loading — the flb_plugin.c role.

Reference: src/flb_plugin.c:200-326 — ``flb_plugin_load`` dlopens a
shared object, derives the registration symbol from the file name, and
links the plugin struct into the registry; exposed via the CLI ``-e``
flag and ``[PLUGINS]``/plugins-file config. The same contract here:
``load_dso_plugin(path)`` loads a C ABI object (``native/
fbtpu_plugin.h``), wraps its vtable in an InputPlugin/OutputPlugin
subclass, and registers it under the struct's name. The reference
proves native-language plugins with its Zig demo (lib/zig_fluent_bit);
this build's proof is ``native/demo_plugins/`` built with g++ in the
runtime tests.
"""

from __future__ import annotations

import ctypes
import json
import logging
import os
from typing import Optional

log = logging.getLogger("flb.dso")

FBTPU_PLUGIN_ABI_VERSION = 1

_EMIT_FN = ctypes.CFUNCTYPE(None, ctypes.c_void_p, ctypes.c_char_p,
                            ctypes.c_char_p, ctypes.c_longlong)


class _OutputVtable(ctypes.Structure):
    _fields_ = [
        ("abi_version", ctypes.c_int),
        ("name", ctypes.c_char_p),
        ("description", ctypes.c_char_p),
        ("init", ctypes.CFUNCTYPE(ctypes.c_void_p, ctypes.c_char_p)),
        ("flush", ctypes.CFUNCTYPE(
            ctypes.c_int, ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_ubyte), ctypes.c_longlong,
            ctypes.c_char_p)),
        ("destroy", ctypes.CFUNCTYPE(None, ctypes.c_void_p)),
    ]


class _InputVtable(ctypes.Structure):
    _fields_ = [
        ("abi_version", ctypes.c_int),
        ("name", ctypes.c_char_p),
        ("description", ctypes.c_char_p),
        ("collect_interval", ctypes.c_double),
        ("init", ctypes.CFUNCTYPE(ctypes.c_void_p, ctypes.c_char_p)),
        ("collect", ctypes.CFUNCTYPE(
            ctypes.c_int, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_char_p, _EMIT_FN)),
        ("destroy", ctypes.CFUNCTYPE(None, ctypes.c_void_p)),
    ]


def plugin_stem(path: str) -> str:
    """File name → registration stem (path_to_plugin_name role): strip
    directory, extension, and an optional flb- prefix."""
    base = os.path.basename(path)
    stem = base.split(".", 1)[0]
    if stem.startswith("flb-"):
        stem = stem[len("flb-"):]
    return stem


def elf_has_export(path: str, names) -> Optional[bool]:
    """Probe the ELF dynamic symbol table for any of ``names`` WITHOUT
    loading the object — dlopen runs static initializers/constructors,
    and the 'rejected objects must never be mapped' invariant says a
    malformed plugin's code must never execute. Returns True/False, or
    None when the file is not parseable as ELF (non-ELF platforms fall
    back to dlopen-and-check)."""
    import struct as _s

    want = {n.encode() if isinstance(n, str) else n for n in names}
    try:
        with open(path, "rb") as f:
            ident = f.read(16)
            if len(ident) < 16 or ident[:4] != b"\x7fELF":
                return None
            is64 = ident[4] == 2
            end = "<" if ident[5] == 1 else ">"
            if is64:
                f.seek(40)
                (shoff,) = _s.unpack(end + "Q", f.read(8))
                f.seek(58)
                shentsize, shnum = _s.unpack(end + "HH", f.read(4))
            else:
                f.seek(32)
                (shoff,) = _s.unpack(end + "I", f.read(4))
                f.seek(46)
                shentsize, shnum = _s.unpack(end + "HH", f.read(4))
            if not shoff or not shnum or shnum > 65535:
                return None
            sections = []
            for i in range(shnum):
                f.seek(shoff + i * shentsize)
                hdr = f.read(shentsize)
                if is64:
                    typ, = _s.unpack_from(end + "I", hdr, 4)
                    link, = _s.unpack_from(end + "I", hdr, 40)
                    off, size = _s.unpack_from(end + "QQ", hdr, 24)
                    entsize, = _s.unpack_from(end + "Q", hdr, 56)
                else:
                    typ, = _s.unpack_from(end + "I", hdr, 4)
                    off, size = _s.unpack_from(end + "II", hdr, 16)
                    link, = _s.unpack_from(end + "I", hdr, 24)
                    entsize, = _s.unpack_from(end + "I", hdr, 36)
                sections.append((typ, off, size, link, entsize))
            for typ, off, size, link, entsize in sections:
                if typ != 11:  # SHT_DYNSYM
                    continue
                if link >= len(sections) or not entsize:
                    return None
                _t, stroff, strsize, _l, _e = sections[link]
                f.seek(stroff)
                strtab = f.read(strsize)
                f.seek(off)
                syms = f.read(size)
                shndx_off = 6 if is64 else 14
                for so in range(0, len(syms) - entsize + 1, entsize):
                    (name_off,) = _s.unpack_from(end + "I", syms, so)
                    if not name_off or name_off >= len(strtab):
                        continue
                    # an UNDEFINED entry (st_shndx == SHN_UNDEF) is an
                    # import, not an export: an object that merely
                    # REFERENCES FLBPluginRegister must not pass
                    (shndx,) = _s.unpack_from(end + "H", syms,
                                              so + shndx_off)
                    if shndx == 0:
                        continue
                    nul = strtab.find(b"\x00", name_off)
                    if strtab[name_off:nul] in want:
                        return True
                return False
            return None  # stripped of dynsym: undecidable
    except (OSError, _s.error):
        return None


def _probe_exports(path: str, names, kind: str) -> None:
    """Reject (pre-dlopen) an object that exports none of ``names``."""
    if elf_has_export(path, names) is False:
        raise ValueError(
            f"cannot load {kind} {path!r}: registration structure is "
            f"missing ({' / '.join(sorted(str(n) for n in names))}) — "
            f"rejected before mapping; constructors never ran")


def _props_json(instance) -> bytes:
    props = {}
    for _lk, key, value in instance.properties._items:
        props[key] = value if isinstance(value, (str, int, float, bool)) \
            else str(value)
    return json.dumps(props).encode()


def load_dso_plugin(path: str, registry=None):
    """dlopen + register; returns the new plugin class. Raises
    ValueError on a malformed object (missing/unsupported symbol)."""
    from .plugin import InputPlugin, OutputPlugin
    from .plugin import registry as default_registry

    reg = registry if registry is not None else default_registry
    stem = plugin_stem(path)
    symbol = f"{stem}_plugin"
    if not stem.startswith(("in_", "out_")):
        # not the in-house vtable naming convention: it may still be a
        # Go-proxy-contract object, whose name comes from the plugin
        # itself (FLBPluginRegister), not the file
        return load_proxy_plugin(path, registry)
    # probe the export table BEFORE dlopen: a rejected object's static
    # initializers must never run (round-5 advisor: the invariant regressed
    # when the proxy fallback made every stem loadable)
    _probe_exports(path, {symbol, "FLBPluginRegister"}, "plugin")
    try:
        dso = ctypes.CDLL(os.path.abspath(path))
    except OSError as e:
        raise ValueError(f"cannot load plugin {path!r}: {e}") from e
    vt_cls = _OutputVtable if stem.startswith("out_") else _InputVtable
    try:
        vt = vt_cls.in_dll(dso, symbol)
    except ValueError as e:
        # in_/out_-named object without the vtable struct: fall back to
        # the proxy contract before rejecting (fluent-bit-go objects
        # are conventionally named out_*.so too)
        if hasattr(dso, "FLBPluginRegister"):
            return load_proxy_plugin(path, registry)
        raise ValueError(
            f"cannot load plugin {path!r}: registration structure "
            f"is missing {symbol!r}") from e
    if stem.startswith("out_"):
        return _register_output(reg, OutputPlugin, dso, vt, path)
    return _register_input(reg, InputPlugin, dso, vt, path)


def _check_abi(vt, path: str) -> str:
    if vt.abi_version != FBTPU_PLUGIN_ABI_VERSION:
        raise ValueError(
            f"plugin {path!r}: ABI version {vt.abi_version} "
            f"(host speaks {FBTPU_PLUGIN_ABI_VERSION})")
    name = (vt.name or b"").decode("utf-8", "replace")
    if not name:
        raise ValueError(f"plugin {path!r}: empty plugin name")
    return name


def _register_output(reg, OutputPlugin, dso, vt, path):
    from .plugin import FlushResult

    name = _check_abi(vt, path)

    class DsoOutput(OutputPlugin):
        description = (vt.description or b"").decode("utf-8", "replace")
        allow_unknown_properties = True  # props pass through as JSON
        _dso = dso  # keep the handle alive with the class
        _vt = vt

        def init(self, instance, engine) -> None:
            ctx = self._vt.init(_props_json(instance))
            if not ctx:
                raise RuntimeError(f"{self.name}: native init failed")
            self._ctx = ctypes.c_void_p(ctx)

        async def flush(self, data: bytes, tag: str, engine):
            buf = (ctypes.c_ubyte * len(data)).from_buffer_copy(data)
            rc = self._vt.flush(self._ctx, buf, len(data),
                                tag.encode("utf-8", "replace"))
            return {0: FlushResult.OK, 1: FlushResult.RETRY}.get(
                rc, FlushResult.ERROR)

        def exit(self) -> None:
            ctx = getattr(self, "_ctx", None)
            if ctx:
                self._vt.destroy(ctx)
                self._ctx = None

    DsoOutput.name = name
    DsoOutput.__name__ = f"Dso_{name}"
    reg.register(DsoOutput)
    log.info("dso: registered output plugin %r from %s", name, path)
    return DsoOutput


def _register_input(reg, InputPlugin, dso, vt, path):
    name = _check_abi(vt, path)
    interval = vt.collect_interval if vt.collect_interval > 0 else 1.0

    class DsoInput(InputPlugin):
        description = (vt.description or b"").decode("utf-8", "replace")
        allow_unknown_properties = True  # props pass through as JSON
        collect_interval = interval
        _dso = dso
        _vt = vt

        def init(self, instance, engine) -> None:
            ctx = self._vt.init(_props_json(instance))
            if not ctx:
                raise RuntimeError(f"{self.name}: native init failed")
            self._ctx = ctypes.c_void_p(ctx)

        def collect(self, engine) -> None:
            from ..codec.events import encode_event, now_event_time

            records = []

            def emit(_host, tag, json_text, length):
                # c_char_p already arrived as a NUL-bounded bytes
                # object; slicing by the advertised length stays
                # inside it even when the plugin lies about length
                try:
                    body = json.loads((json_text or b"")[:length])
                except (ValueError, TypeError):
                    return
                records.append((
                    (tag or b"").decode("utf-8", "replace"), body))

            cb = _EMIT_FN(emit)
            rc = self._vt.collect(
                self._ctx, None,
                (self.instance.tag or "").encode("utf-8", "replace"),
                cb)
            if rc < 0:
                log.warning("%s: native collect failed", self.name)
                return
            groups = {}
            for tag, body in records:
                tag = tag or self.instance.tag
                groups.setdefault(tag, []).append(
                    encode_event(body, now_event_time()))
            for tag, bufs in groups.items():
                engine.input_log_append(self.instance, tag,
                                        b"".join(bufs), len(bufs))

        def exit(self) -> None:
            ctx = getattr(self, "_ctx", None)
            if ctx:
                self._vt.destroy(ctx)
                self._ctx = None

    DsoInput.name = name
    DsoInput.__name__ = f"Dso_{name}"
    reg.register(DsoInput)
    log.info("dso: registered input plugin %r from %s", name, path)
    return DsoInput


# ---------------------------------------------------------------------
# Go-proxy-style foreign-runtime ABI (flb_plugin_proxy.c:347-433 +
# src/proxy/go/go.{c,h}): the HOST calls the object's exported
# ``FLBPluginRegister(def)``; the plugin fills the definition struct
# (type/name/description), then the host resolves the per-type callback
# set (FLBPluginInit / FLBPluginFlush[Ctx] / FLBPluginInputCallback /
# FLBPluginExit) and hands the plugin a callback TABLE (struct flb_api)
# through which it reads instance properties — the exact contract
# cgo-built fluent-bit-go plugins compile against.
# ---------------------------------------------------------------------

FLB_PROXY_INPUT_PLUGIN = 1
FLB_PROXY_OUTPUT_PLUGIN = 2

# fluent-bit-go return codes (output package)
_PROXY_FLB_ERROR = 0
_PROXY_FLB_OK = 1
_PROXY_FLB_RETRY = 2


class _ProxyDef(ctypes.Structure):
    """struct flb_plugin_proxy_def (flb_plugin_proxy.h:36-44)."""

    _fields_ = [
        ("type", ctypes.c_int),
        ("proxy", ctypes.c_int),
        ("flags", ctypes.c_int),
        ("name", ctypes.c_char_p),
        ("description", ctypes.c_char_p),
        ("event_type", ctypes.c_int),
    ]


# returns char* as c_void_p: a c_char_p restype would make ctypes
# convert a Python bytes temporarily (dangling pointer + the
# "memory leak in callback" warning); the address of a host-pinned
# buffer is stable until the next lookup for the same key
_GET_PROP_FN = ctypes.CFUNCTYPE(ctypes.c_void_p, ctypes.c_char_p,
                                ctypes.c_void_p)
_LOG_CHECK_FN = ctypes.CFUNCTYPE(ctypes.c_int, ctypes.c_void_p,
                                 ctypes.c_int)


class _FlbApi(ctypes.Structure):
    """struct flb_api — field ORDER is the ABI. The layout follows
    include/fluent-bit/flb_api.h (NOT flb_api.c's assignment order):
    the header appends custom_get_property/custom_log_check at the END
    'to preserve ABI', so a cgo-built fluent-bit-go plugin compiled
    against the header indexes slots 2-6 as the cmt/log entries."""

    _fields_ = [
        ("output_get_property", _GET_PROP_FN),
        ("input_get_property", _GET_PROP_FN),
        ("output_get_cmt_instance",
         ctypes.CFUNCTYPE(ctypes.c_void_p, ctypes.c_void_p)),
        ("input_get_cmt_instance",
         ctypes.CFUNCTYPE(ctypes.c_void_p, ctypes.c_void_p)),
        ("log_print", ctypes.c_void_p),  # variadic: not bridged
        ("input_log_check", _LOG_CHECK_FN),
        ("output_log_check", _LOG_CHECK_FN),
        ("custom_get_property", _GET_PROP_FN),
        ("custom_log_check", _LOG_CHECK_FN),
    ]


class _GoOutputPlugin(ctypes.Structure):
    """struct flbgo_output_plugin (src/proxy/go/go.h:26-37)."""

    _fields_ = [
        ("name", ctypes.c_char_p),
        ("api", ctypes.POINTER(_FlbApi)),
        ("o_ins", ctypes.c_void_p),
        ("context", ctypes.c_void_p),
        ("cb_init", ctypes.CFUNCTYPE(ctypes.c_int, ctypes.c_void_p)),
        ("cb_flush", ctypes.CFUNCTYPE(
            ctypes.c_int, ctypes.c_void_p, ctypes.c_size_t,
            ctypes.c_char_p)),
        ("cb_flush_ctx", ctypes.CFUNCTYPE(
            ctypes.c_int, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_size_t, ctypes.c_char_p)),
        ("cb_exit", ctypes.CFUNCTYPE(ctypes.c_int)),
        ("cb_exit_ctx", ctypes.CFUNCTYPE(ctypes.c_int, ctypes.c_void_p)),
    ]


class _GoInputPlugin(ctypes.Structure):
    """struct flbgo_input_plugin (src/proxy/go/go.h:39-51)."""

    _fields_ = [
        ("name", ctypes.c_char_p),
        ("api", ctypes.POINTER(_FlbApi)),
        ("i_ins", ctypes.c_void_p),
        ("context", ctypes.c_void_p),
        ("cb_init", ctypes.CFUNCTYPE(ctypes.c_int, ctypes.c_void_p)),
        ("cb_collect", ctypes.CFUNCTYPE(
            ctypes.c_int, ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_size_t))),
        ("cb_collect_ctx", ctypes.CFUNCTYPE(
            ctypes.c_int, ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_size_t))),
        ("cb_cleanup", ctypes.CFUNCTYPE(ctypes.c_int, ctypes.c_void_p)),
        ("cb_cleanup_ctx", ctypes.CFUNCTYPE(
            ctypes.c_int, ctypes.c_void_p, ctypes.c_void_p)),
        ("cb_exit", ctypes.CFUNCTYPE(ctypes.c_int)),
    ]


# instance handles passed through the void* o_ins/i_ins slots: the
# callback resolves them back to Instance objects. Keyed by a token,
# never a raw Python pointer.
_proxy_instances: dict = {}
_proxy_prop_cache: dict = {}  # returned c_char_p buffers stay alive


def _proxy_drop_handle(handle) -> None:
    """Release an instance handle AND its pinned property buffers
    (they would otherwise accumulate across plugin create/exit
    cycles for the process lifetime)."""
    if handle is None:
        return
    _proxy_instances.pop(handle, None)
    for k in [k for k in _proxy_prop_cache if k[0] == handle]:
        del _proxy_prop_cache[k]


def _proxy_get_property(key, handle):
    ins = _proxy_instances.get(int(handle or 0))
    if ins is None or not key:
        return None
    val = ins.properties.get(key.decode("utf-8", "replace"))
    if val is None:
        return None
    buf = ctypes.create_string_buffer(str(val).encode("utf-8"))
    _proxy_prop_cache[(int(handle), key)] = buf  # pin until next call
    return ctypes.addressof(buf)


def _make_api() -> _FlbApi:
    api = _FlbApi()
    get_prop = _GET_PROP_FN(_proxy_get_property)
    api.output_get_property = get_prop
    api.input_get_property = get_prop
    api.custom_get_property = get_prop
    api.log_print = None
    # FBTPU_DSO_API_PROBE=1 makes the three log_check slots return
    # distinct per-kind values (1/2/3) so the ABI tests can PROVE a
    # call reached its exact slot — an order regression hands back a
    # neighbouring entry. Production keeps the quiet 0 for all kinds
    # (log_check is a boolean gate; a nonzero stub would flood plugins
    # that log whenever their level "passes").
    probe = os.environ.get("FBTPU_DSO_API_PROBE") == "1"
    api.input_log_check = _LOG_CHECK_FN(
        lambda _i, _l: 1 if probe else 0)
    api.output_log_check = _LOG_CHECK_FN(
        lambda _i, _l: 2 if probe else 0)
    api.custom_log_check = _LOG_CHECK_FN(
        lambda _i, _l: 3 if probe else 0)
    # pin the closures with the struct
    api._refs = (get_prop, api.input_log_check, api.output_log_check,
                 api.custom_log_check)
    return api


def _proxy_symbol(dso, name, proto):
    try:
        fn = getattr(dso, name)
    except AttributeError:
        return None
    return ctypes.cast(fn, proto)


def load_proxy_plugin(path: str, registry=None):
    """Load a Go-proxy-contract shared object: call its
    FLBPluginRegister with a definition struct, then register the
    resulting plugin under the name the PLUGIN chose (not the file
    name). Returns the new plugin class."""
    from .plugin import registry as default_registry

    reg = registry if registry is not None else default_registry
    # pre-dlopen probe: an object without the registration export is
    # rejected before any of its code can run
    _probe_exports(path, {"FLBPluginRegister"}, "proxy plugin")
    try:
        dso = ctypes.CDLL(os.path.abspath(path))
    except OSError as e:
        raise ValueError(f"cannot load proxy plugin {path!r}: {e}") from e
    try:
        register = dso.FLBPluginRegister
    except AttributeError as e:
        raise ValueError(
            f"cannot load proxy plugin {path!r}: no FLBPluginRegister "
            f"export") from e
    register.restype = ctypes.c_int
    register.argtypes = [ctypes.POINTER(_ProxyDef)]
    pdef = _ProxyDef()
    if register(ctypes.byref(pdef)) < 0:
        raise ValueError(f"proxy plugin {path!r}: FLBPluginRegister "
                         f"failed")
    name = (pdef.name or b"").decode("utf-8", "replace")
    if not name:
        raise ValueError(f"proxy plugin {path!r}: empty plugin name")
    if pdef.type == FLB_PROXY_OUTPUT_PLUGIN:
        return _register_proxy_output(reg, dso, pdef, name, path)
    if pdef.type == FLB_PROXY_INPUT_PLUGIN:
        return _register_proxy_input(reg, dso, pdef, name, path)
    raise ValueError(
        f"proxy plugin {path!r}: unsupported type {pdef.type}")


def _register_proxy_output(reg, dso, pdef, name, path):
    from .plugin import FlushResult, OutputPlugin

    cb_init = _proxy_symbol(dso, "FLBPluginInit",
                            _GoOutputPlugin._fields_[4][1])
    if cb_init is None:
        raise ValueError(f"proxy plugin {path!r}: no FLBPluginInit")
    cb_flush = _proxy_symbol(dso, "FLBPluginFlush",
                             _GoOutputPlugin._fields_[5][1])
    cb_flush_ctx = _proxy_symbol(dso, "FLBPluginFlushCtx",
                                 _GoOutputPlugin._fields_[6][1])
    if cb_flush is None and cb_flush_ctx is None:
        raise ValueError(f"proxy plugin {path!r}: no FLBPluginFlush or "
                         f"FLBPluginFlushCtx")
    cb_exit = _proxy_symbol(dso, "FLBPluginExit",
                            _GoOutputPlugin._fields_[7][1])
    cb_exit_ctx = _proxy_symbol(dso, "FLBPluginExitCtx",
                                _GoOutputPlugin._fields_[8][1])
    desc = (pdef.description or b"").decode("utf-8", "replace")

    class ProxyOutput(OutputPlugin):
        description = desc
        allow_unknown_properties = True
        _dso = dso  # keep mapped

        def init(self, instance, engine) -> None:
            self._handle = id(instance)
            _proxy_instances[self._handle] = instance
            self._api = _make_api()
            self._plug = _GoOutputPlugin()
            self._plug.name = name.encode()
            self._plug.api = ctypes.pointer(self._api)
            self._plug.o_ins = self._handle
            if cb_flush:
                self._plug.cb_flush = cb_flush
            if cb_flush_ctx:
                self._plug.cb_flush_ctx = cb_flush_ctx
            rc = cb_init(ctypes.byref(self._plug))
            if rc <= 0:
                raise RuntimeError(
                    f"{name}: FLBPluginInit returned {rc}")

        async def flush(self, data: bytes, tag: str, engine):
            buf = ctypes.create_string_buffer(data, len(data))
            t = tag.encode("utf-8", "replace")
            # ctx-variant only when the plugin SET a context
            # (go.c proxy_go_output_flush dispatches the same way);
            # FLBPluginFlushCtx(NULL, ...) would crash ctx-assuming
            # plugins that export both symbols
            if cb_flush_ctx is not None and self._plug.context:
                rc = cb_flush_ctx(self._plug.context, buf, len(data), t)
            elif cb_flush is not None:
                rc = cb_flush(buf, len(data), t)
            else:
                rc = cb_flush_ctx(self._plug.context, buf, len(data), t)
            return {_PROXY_FLB_OK: FlushResult.OK,
                    _PROXY_FLB_RETRY: FlushResult.RETRY}.get(
                        rc, FlushResult.ERROR)

        def exit(self) -> None:
            if cb_exit_ctx is not None and self._plug.context:
                cb_exit_ctx(self._plug.context)
            elif cb_exit is not None:
                cb_exit()
            _proxy_drop_handle(getattr(self, "_handle", None))

    ProxyOutput.name = name
    ProxyOutput.__name__ = f"Proxy_{name}"
    reg.register(ProxyOutput)
    log.info("dso: registered proxy output %r from %s", name, path)
    return ProxyOutput


def _register_proxy_input(reg, dso, pdef, name, path):
    from .plugin import InputPlugin

    cb_init = _proxy_symbol(dso, "FLBPluginInit",
                            _GoInputPlugin._fields_[4][1])
    if cb_init is None:
        raise ValueError(f"proxy plugin {path!r}: no FLBPluginInit")
    cb_collect = _proxy_symbol(dso, "FLBPluginInputCallback",
                               _GoInputPlugin._fields_[5][1])
    if cb_collect is None:
        raise ValueError(
            f"proxy plugin {path!r}: no FLBPluginInputCallback")
    cb_cleanup = _proxy_symbol(dso, "FLBPluginInputCleanupCallback",
                               _GoInputPlugin._fields_[7][1])
    cb_exit = _proxy_symbol(dso, "FLBPluginExit",
                            _GoInputPlugin._fields_[9][1])
    desc = (pdef.description or b"").decode("utf-8", "replace")

    class ProxyInput(InputPlugin):
        description = desc
        allow_unknown_properties = True
        collect_interval = 1.0
        _dso = dso

        def init(self, instance, engine) -> None:
            self._handle = id(instance)
            _proxy_instances[self._handle] = instance
            self._api = _make_api()
            self._plug = _GoInputPlugin()
            self._plug.name = name.encode()
            self._plug.api = ctypes.pointer(self._api)
            self._plug.i_ins = self._handle
            rc = cb_init(ctypes.byref(self._plug))
            if rc <= 0:
                raise RuntimeError(
                    f"{name}: FLBPluginInit returned {rc}")

        def collect(self, engine) -> None:
            from ..codec.events import fast_count_records

            data = ctypes.c_void_p()
            size = ctypes.c_size_t(0)
            rc = cb_collect(ctypes.byref(data), ctypes.byref(size))
            if rc < 0 or not data or not size.value:
                return
            try:
                raw = ctypes.string_at(data, size.value)
            finally:
                # the plugin malloc'd the buffer; its cleanup callback
                # (or libc free) releases it — the reference proxy does
                # exactly this after enqueueing (flb_plugin_proxy.c)
                if cb_cleanup is not None:
                    cb_cleanup(data)
                else:
                    ctypes.CDLL(None).free(data)
            n = fast_count_records(raw)
            if not n:
                return
            engine.input_log_append(self.instance, self.instance.tag,
                                    raw, n)

        def exit(self) -> None:
            if cb_exit is not None:
                cb_exit()
            _proxy_drop_handle(getattr(self, "_handle", None))

    ProxyInput.name = name
    ProxyInput.__name__ = f"Proxy_{name}"
    reg.register(ProxyInput)
    log.info("dso: registered proxy input %r from %s", name, path)
    return ProxyInput
