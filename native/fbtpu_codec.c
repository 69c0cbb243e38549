/* fbtpu_codec — CPython C-API msgpack event decoder.
 *
 * The decode path (codec/events.decode_events → pure-Python Unpacker)
 * costs ~30µs/record and caps every non-raw filter stage near 50k
 * lines/s; this extension decodes the same log-event subset straight
 * into Python objects (~10x). Byte-for-byte semantic twin of
 * codec/msgpack.Unpacker + codec/events._to_event:
 *   - strings decode UTF-8 with errors="replace"
 *   - unhashable map keys degrade to repr()
 *   - ext type 0 (len 8) → EventTime(sec, nsec)
 *   - any OTHER ext type raises FallbackError: the caller reruns the
 *     pure-Python decoder (ExtType construction is not worth porting)
 *   - V2 [[ts, meta], body] and legacy [ts, body] records both map to
 *     LogEvent(timestamp, body, metadata, raw-span)
 *
 * Two entry points build no object per record at all, and run with the
 * GIL released: in_forward's loop takes a Forward / PackedForward chunk
 * off the wire with forward_cut (the message's end, a proof that every
 * entry's bytes are canonical, and the V2 events copied from them;
 * forward_cut_entries is the same walk over an inflated blob), and
 * unpack_from's decode stays the path of every message that proof does
 * not cover. parser_json_batch transcodes a whole chunk the same way,
 * and parser_spans_build builds filter_parser's records from the spans
 * the device found.
 *
 * Reference precedent: the hot decode loop is C in fluent-bit too
 * (lib/msgpack-c via flb_log_event_decoder, src/flb_log_event_decoder.c).
 */

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <stdint.h>
#include <stdlib.h>
#include <string.h>

static PyObject *g_logevent = NULL;   /* codec.events.LogEvent */
static PyObject *g_eventtime = NULL;  /* codec.msgpack.EventTime */
static PyObject *g_fallback = NULL;   /* fbtpu_codec.FallbackError */
static PyObject *g_truncated = NULL;  /* internal: torn trailing record */

/* nesting bound: the pure-Python decoder dies with a recoverable
 * RecursionError around CPython's ~1000-frame limit; unbounded C
 * recursion would overflow the REAL stack and segfault the process on
 * a hostile buffer (b"\x91" * N). 512 covers any sane log event. */
#define MAX_DEPTH 512

typedef struct {
    const uint8_t *p;
    const uint8_t *end;
    int depth;
} rd;

static int need(rd *r, Py_ssize_t n) {
    if (r->end - r->p < n) {
        /* the Python Unpacker treats a torn tail as end-of-stream
         * (OutOfData stops iteration, the decoded prefix is returned);
         * decode_events must mirror that, so truncation gets its own
         * exception type the loop can swallow */
        PyErr_SetString(g_truncated, "truncated msgpack");
        return -1;
    }
    return 0;
}

/* every caller need()s before calling — hoisting the check here would
 * double it on the hottest decode path
 * fbtpu-lint: allow(codec-bounds) */
static uint64_t rd_be(rd *r, int n) {
    uint64_t v = 0;
    for (int i = 0; i < n; i++) v = (v << 8) | r->p[i];
    r->p += n;
    return v;
}

static PyObject *decode_obj(rd *r);

static PyObject *decode_str(rd *r, Py_ssize_t n) {
    if (need(r, n) < 0) return NULL;
    PyObject *s = PyUnicode_DecodeUTF8((const char *)r->p, n, "replace");
    r->p += n;
    return s;
}

static PyObject *decode_bin(rd *r, Py_ssize_t n) {
    if (need(r, n) < 0) return NULL;
    PyObject *b = PyBytes_FromStringAndSize((const char *)r->p, n);
    r->p += n;
    return b;
}

static PyObject *decode_ext(rd *r, int code, Py_ssize_t n) {
    if (need(r, n) < 0) return NULL;
    if (code == 0 && n == 8) {
        uint32_t sec = ((uint32_t)r->p[0] << 24) | ((uint32_t)r->p[1] << 16)
                     | ((uint32_t)r->p[2] << 8) | r->p[3];
        uint32_t nsec = ((uint32_t)r->p[4] << 24) | ((uint32_t)r->p[5] << 16)
                      | ((uint32_t)r->p[6] << 8) | r->p[7];
        r->p += 8;
        return PyObject_CallFunction(g_eventtime, "kk",
                                     (unsigned long)sec,
                                     (unsigned long)nsec);
    }
    /* non-EventTime ext: the Python decoder builds ExtType — punt */
    PyErr_SetString(g_fallback, "non-EventTime ext type");
    return NULL;
}

static PyObject *decode_array(rd *r, Py_ssize_t n) {
    PyObject *lst = PyList_New(n);
    if (!lst) return NULL;
    r->depth++;
    for (Py_ssize_t i = 0; i < n; i++) {
        PyObject *it = decode_obj(r);
        if (!it) { r->depth--; Py_DECREF(lst); return NULL; }
        PyList_SET_ITEM(lst, i, it);
    }
    r->depth--;
    return lst;
}

static PyObject *decode_map(rd *r, Py_ssize_t n) {
    PyObject *d = PyDict_New();
    if (!d) return NULL;
    r->depth++;
    for (Py_ssize_t i = 0; i < n; i++) {
        PyObject *k = decode_obj(r);
        if (!k) { r->depth--; Py_DECREF(d); return NULL; }
        if (PyDict_Check(k) || PyList_Check(k)) {
            /* unhashable keys degrade to repr() (msgpack.py parity) */
            PyObject *rep = PyObject_Repr(k);
            Py_DECREF(k);
            if (!rep) { Py_DECREF(d); return NULL; }
            k = rep;
        }
        PyObject *v = decode_obj(r);
        if (!v) { r->depth--; Py_DECREF(k); Py_DECREF(d); return NULL; }
        if (PyDict_SetItem(d, k, v) < 0) {
            r->depth--;
            Py_DECREF(k); Py_DECREF(v); Py_DECREF(d);
            return NULL;
        }
        Py_DECREF(k);
        Py_DECREF(v);
    }
    r->depth--;
    return d;
}

static PyObject *decode_obj(rd *r) {
    if (r->depth >= MAX_DEPTH) {
        PyErr_SetString(PyExc_ValueError, "msgpack nesting too deep");
        return NULL;
    }
    if (need(r, 1) < 0) return NULL;
    uint8_t b = *r->p++;
    if (b < 0x80) return PyLong_FromLong(b);
    if (b >= 0xE0) return PyLong_FromLong((long)b - 0x100);
    if (b <= 0x8F) return decode_map(r, b & 0x0F);
    if (b <= 0x9F) return decode_array(r, b & 0x0F);
    if (b <= 0xBF) return decode_str(r, b & 0x1F);
    switch (b) {
    case 0xC0: Py_RETURN_NONE;
    case 0xC2: Py_RETURN_FALSE;
    case 0xC3: Py_RETURN_TRUE;
    case 0xC4: if (need(r, 1) < 0) return NULL;
        return decode_bin(r, (Py_ssize_t)rd_be(r, 1));
    case 0xC5: if (need(r, 2) < 0) return NULL;
        return decode_bin(r, (Py_ssize_t)rd_be(r, 2));
    case 0xC6: if (need(r, 4) < 0) return NULL;
        return decode_bin(r, (Py_ssize_t)rd_be(r, 4));
    case 0xC7: {
        if (need(r, 2) < 0) return NULL;
        Py_ssize_t n = (Py_ssize_t)rd_be(r, 1);
        int code = (int8_t)rd_be(r, 1);
        return decode_ext(r, code, n);
    }
    case 0xC8: {
        if (need(r, 3) < 0) return NULL;
        Py_ssize_t n = (Py_ssize_t)rd_be(r, 2);
        int code = (int8_t)rd_be(r, 1);
        return decode_ext(r, code, n);
    }
    case 0xC9: {
        if (need(r, 5) < 0) return NULL;
        Py_ssize_t n = (Py_ssize_t)rd_be(r, 4);
        int code = (int8_t)rd_be(r, 1);
        return decode_ext(r, code, n);
    }
    case 0xCA: {
        if (need(r, 4) < 0) return NULL;
        union { uint32_t u; float f; } c;
        c.u = (uint32_t)rd_be(r, 4);
        return PyFloat_FromDouble((double)c.f);
    }
    case 0xCB: {
        if (need(r, 8) < 0) return NULL;
        union { uint64_t u; double d; } c;
        c.u = rd_be(r, 8);
        return PyFloat_FromDouble(c.d);
    }
    case 0xCC: if (need(r, 1) < 0) return NULL;
        return PyLong_FromUnsignedLong((unsigned long)rd_be(r, 1));
    case 0xCD: if (need(r, 2) < 0) return NULL;
        return PyLong_FromUnsignedLong((unsigned long)rd_be(r, 2));
    case 0xCE: if (need(r, 4) < 0) return NULL;
        return PyLong_FromUnsignedLong((unsigned long)rd_be(r, 4));
    case 0xCF: if (need(r, 8) < 0) return NULL;
        return PyLong_FromUnsignedLongLong(
            (unsigned long long)rd_be(r, 8));
    case 0xD0: if (need(r, 1) < 0) return NULL;
        return PyLong_FromLong((int8_t)rd_be(r, 1));
    case 0xD1: if (need(r, 2) < 0) return NULL;
        return PyLong_FromLong((int16_t)rd_be(r, 2));
    case 0xD2: if (need(r, 4) < 0) return NULL;
        return PyLong_FromLong((int32_t)rd_be(r, 4));
    case 0xD3: if (need(r, 8) < 0) return NULL;
        return PyLong_FromLongLong((int64_t)rd_be(r, 8));
    case 0xD4: case 0xD5: case 0xD6: case 0xD7: case 0xD8: {
        Py_ssize_t n = (Py_ssize_t)1 << (b - 0xD4);
        if (need(r, 1) < 0) return NULL;
        int code = (int8_t)rd_be(r, 1);
        return decode_ext(r, code, n);
    }
    case 0xD9: if (need(r, 1) < 0) return NULL;
        return decode_str(r, (Py_ssize_t)rd_be(r, 1));
    case 0xDA: if (need(r, 2) < 0) return NULL;
        return decode_str(r, (Py_ssize_t)rd_be(r, 2));
    case 0xDB: if (need(r, 4) < 0) return NULL;
        return decode_str(r, (Py_ssize_t)rd_be(r, 4));
    case 0xDC: if (need(r, 2) < 0) return NULL;
        return decode_array(r, (Py_ssize_t)rd_be(r, 2));
    case 0xDD: if (need(r, 4) < 0) return NULL;
        return decode_array(r, (Py_ssize_t)rd_be(r, 4));
    case 0xDE: if (need(r, 2) < 0) return NULL;
        return decode_map(r, (Py_ssize_t)rd_be(r, 2));
    case 0xDF: if (need(r, 4) < 0) return NULL;
        return decode_map(r, (Py_ssize_t)rd_be(r, 4));
    default:
        PyErr_Format(PyExc_ValueError, "invalid msgpack byte 0x%02x", b);
        return NULL;
    }
}

/* obj (the decoded outer list) + raw span → LogEvent
 * (codec/events._to_event parity) */
static PyObject *to_event(PyObject *obj, PyObject *raw) {
    if (!PyList_Check(obj) || PyList_GET_SIZE(obj) == 0) {
        PyObject *rep = PyObject_Repr(obj);
        PyErr_Format(PyExc_ValueError, "invalid log event: %U",
                     rep ? rep : PyUnicode_FromString("?"));
        Py_XDECREF(rep);
        return NULL;
    }
    PyObject *header = PyList_GET_ITEM(obj, 0);  /* borrowed */
    PyObject *ts, *meta, *body;
    if (PyList_Check(header)) {
        ts = PyList_GET_SIZE(header) > 0
            ? PyList_GET_ITEM(header, 0) : NULL;
        if (ts == NULL) {
            ts = PyLong_FromLong(0);
        } else {
            Py_INCREF(ts);
        }
        meta = PyList_GET_SIZE(header) > 1
            && PyDict_Check(PyList_GET_ITEM(header, 1))
            ? PyList_GET_ITEM(header, 1) : NULL;
        body = PyList_GET_SIZE(obj) > 1
            && PyDict_Check(PyList_GET_ITEM(obj, 1))
            ? PyList_GET_ITEM(obj, 1) : NULL;
    } else {
        ts = header;
        Py_INCREF(ts);
        meta = NULL;
        body = PyList_GET_SIZE(obj) > 1
            && PyDict_Check(PyList_GET_ITEM(obj, 1))
            ? PyList_GET_ITEM(obj, 1) : NULL;
    }
    if (body == NULL) {
        body = PyDict_New();
        if (!body) { Py_DECREF(ts); return NULL; }
    } else {
        Py_INCREF(body);
    }
    if (meta == NULL) {
        meta = PyDict_New();
        if (!meta) { Py_DECREF(ts); Py_DECREF(body); return NULL; }
    } else {
        Py_INCREF(meta);
    }
    PyObject *ev = PyObject_CallFunctionObjArgs(
        g_logevent, ts, body, meta, raw, NULL);
    Py_DECREF(ts);
    Py_DECREF(body);
    Py_DECREF(meta);
    return ev;
}

/* ------------------------------------------------------------------ */
/* Packing — byte-exact twin of codec/msgpack._pack (exact-type
 * dispatch; anything outside the known set raises FallbackError and
 * the caller reruns the Python packer). */

typedef struct {
    uint8_t *buf;
    Py_ssize_t len, cap;
    int depth;
} wr;

static int wr_reserve(wr *w, Py_ssize_t extra) {
    if (w->len + extra <= w->cap) return 0;
    Py_ssize_t ncap = w->cap ? w->cap : 256;
    while (ncap < w->len + extra) ncap *= 2;
    uint8_t *nb = PyMem_Realloc(w->buf, ncap);
    if (!nb) { PyErr_NoMemory(); return -1; }
    w->buf = nb;
    w->cap = ncap;
    return 0;
}

static int wr_bytes(wr *w, const void *p, Py_ssize_t n) {
    if (wr_reserve(w, n) < 0) return -1;
    memcpy(w->buf + w->len, p, n);
    w->len += n;
    return 0;
}

static int wr_u8(wr *w, uint8_t b) { return wr_bytes(w, &b, 1); }

static int wr_be(wr *w, uint64_t v, int n) {
    uint8_t tmp[8];
    for (int i = n - 1; i >= 0; i--) { tmp[i] = v & 0xff; v >>= 8; }
    return wr_bytes(w, tmp, n);
}

static int pack_obj(wr *w, PyObject *obj);

static int pack_header(wr *w, Py_ssize_t n, uint8_t fixbase,
                       uint8_t b16, uint8_t b32, int fixmax) {
    if (n < fixmax) return wr_u8(w, (uint8_t)(fixbase | n));
    if (n <= 0xFFFF) {
        if (wr_u8(w, b16) < 0) return -1;
        return wr_be(w, (uint64_t)n, 2);
    }
    if (wr_u8(w, b32) < 0) return -1;
    return wr_be(w, (uint64_t)n, 4);
}

static int pack_obj(wr *w, PyObject *obj) {
    if (w->depth >= MAX_DEPTH) {
        PyErr_SetString(PyExc_ValueError, "msgpack nesting too deep");
        return -1;
    }
    if (obj == Py_None) return wr_u8(w, 0xC0);
    PyTypeObject *t = Py_TYPE(obj);
    if (obj == Py_True) return wr_u8(w, 0xC3);
    if (obj == Py_False) return wr_u8(w, 0xC2);
    if (t == &PyLong_Type) {
        int overflow = 0;
        long long v = PyLong_AsLongLongAndOverflow(obj, &overflow);
        if (overflow > 0) {  /* > i64 max: may still fit u64 */
            unsigned long long u = PyLong_AsUnsignedLongLong(obj);
            if (PyErr_Occurred()) {
                PyErr_Clear();
                PyErr_SetString(PyExc_OverflowError,
                                "int too large for msgpack");
                return -1;
            }
            if (wr_u8(w, 0xCF) < 0) return -1;
            return wr_be(w, (uint64_t)u, 8);
        }
        if (overflow < 0) {
            PyErr_SetString(PyExc_OverflowError,
                            "int too small for msgpack");
            return -1;
        }
        if (v >= 0) {
            if (v < 0x80) return wr_u8(w, (uint8_t)v);
            if (v <= 0xFF) {
                if (wr_u8(w, 0xCC) < 0) return -1;
                return wr_u8(w, (uint8_t)v);
            }
            if (v <= 0xFFFF) {
                if (wr_u8(w, 0xCD) < 0) return -1;
                return wr_be(w, (uint64_t)v, 2);
            }
            if (v <= 0xFFFFFFFFLL) {
                if (wr_u8(w, 0xCE) < 0) return -1;
                return wr_be(w, (uint64_t)v, 4);
            }
            if (wr_u8(w, 0xCF) < 0) return -1;
            return wr_be(w, (uint64_t)v, 8);
        }
        if (v >= -32) return wr_u8(w, (uint8_t)(int8_t)v);
        if (v >= -128) {
            if (wr_u8(w, 0xD0) < 0) return -1;
            return wr_u8(w, (uint8_t)(int8_t)v);
        }
        if (v >= -32768) {
            if (wr_u8(w, 0xD1) < 0) return -1;
            return wr_be(w, (uint64_t)(uint16_t)(int16_t)v, 2);
        }
        if (v >= -2147483648LL) {
            if (wr_u8(w, 0xD2) < 0) return -1;
            return wr_be(w, (uint64_t)(uint32_t)(int32_t)v, 4);
        }
        if (wr_u8(w, 0xD3) < 0) return -1;
        return wr_be(w, (uint64_t)v, 8);
    }
    if (t == &PyFloat_Type) {
        union { double d; uint64_t u; } c;
        c.d = PyFloat_AS_DOUBLE(obj);
        if (wr_u8(w, 0xCB) < 0) return -1;
        return wr_be(w, c.u, 8);
    }
    if (t == &PyUnicode_Type) {
        Py_ssize_t n;
        const char *s = PyUnicode_AsUTF8AndSize(obj, &n);
        if (!s) return -1;
        if (n < 32) {
            if (wr_u8(w, (uint8_t)(0xA0 | n)) < 0) return -1;
        } else if (n <= 0xFF) {
            if (wr_u8(w, 0xD9) < 0 || wr_u8(w, (uint8_t)n) < 0)
                return -1;
        } else if (n <= 0xFFFF) {
            if (wr_u8(w, 0xDA) < 0 || wr_be(w, (uint64_t)n, 2) < 0)
                return -1;
        } else {
            if (wr_u8(w, 0xDB) < 0 || wr_be(w, (uint64_t)n, 4) < 0)
                return -1;
        }
        return wr_bytes(w, s, n);
    }
    if (t == &PyBytes_Type || t == &PyByteArray_Type
            || t == &PyMemoryView_Type) {
        PyObject *b = PyBytes_FromObject(obj);
        if (!b) return -1;
        Py_ssize_t n = PyBytes_GET_SIZE(b);
        int rc;
        if (n <= 0xFF)
            rc = wr_u8(w, 0xC4) < 0 ? -1 : wr_u8(w, (uint8_t)n);
        else if (n <= 0xFFFF)
            rc = wr_u8(w, 0xC5) < 0 ? -1 : wr_be(w, (uint64_t)n, 2);
        else
            rc = wr_u8(w, 0xC6) < 0 ? -1 : wr_be(w, (uint64_t)n, 4);
        if (rc == 0) rc = wr_bytes(w, PyBytes_AS_STRING(b), n);
        Py_DECREF(b);
        return rc;
    }
    if (t == &PyList_Type || t == &PyTuple_Type) {
        Py_ssize_t n = PySequence_Fast_GET_SIZE(obj);
        if (pack_header(w, n, 0x90, 0xDC, 0xDD, 16) < 0) return -1;
        PyObject **items = PySequence_Fast_ITEMS(obj);
        w->depth++;
        for (Py_ssize_t i = 0; i < n; i++)
            if (pack_obj(w, items[i]) < 0) { w->depth--; return -1; }
        w->depth--;
        return 0;
    }
    if (t == &PyDict_Type) {
        Py_ssize_t n = PyDict_GET_SIZE(obj);
        if (pack_header(w, n, 0x80, 0xDE, 0xDF, 16) < 0) return -1;
        Py_ssize_t pos = 0;
        PyObject *k, *v;
        w->depth++;
        while (PyDict_Next(obj, &pos, &k, &v)) {
            if (pack_obj(w, k) < 0) { w->depth--; return -1; }
            if (pack_obj(w, v) < 0) { w->depth--; return -1; }
        }
        w->depth--;
        return 0;
    }
    if ((PyObject *)t == g_eventtime) {
        PyObject *sec = PyObject_GetAttrString(obj, "sec");
        PyObject *nsec = PyObject_GetAttrString(obj, "nsec");
        if (!sec || !nsec) { Py_XDECREF(sec); Py_XDECREF(nsec); return -1; }
        uint32_t s = (uint32_t)PyLong_AsUnsignedLongLongMask(sec);
        uint32_t ns = (uint32_t)PyLong_AsUnsignedLongLongMask(nsec);
        Py_DECREF(sec);
        Py_DECREF(nsec);
        if (wr_u8(w, 0xD7) < 0 || wr_u8(w, 0x00) < 0) return -1;
        if (wr_be(w, s, 4) < 0) return -1;
        return wr_be(w, ns, 4);
    }
    /* ExtType, subclasses, exotic types: let the Python packer decide */
    PyErr_SetString(g_fallback, "type outside the fast-pack set");
    return -1;
}

static PyObject *py_pack_event(PyObject *self, PyObject *args) {
    PyObject *ts, *meta, *body;
    if (!PyArg_ParseTuple(args, "OOO", &ts, &meta, &body)) return NULL;
    wr w = {NULL, 0, 0, 0};
    /* [[ts, meta], body] */
    if (wr_u8(&w, 0x92) < 0 || wr_u8(&w, 0x92) < 0
            || pack_obj(&w, ts) < 0 || pack_obj(&w, meta) < 0
            || pack_obj(&w, body) < 0) {
        PyMem_Free(w.buf);
        return NULL;
    }
    PyObject *out = PyBytes_FromStringAndSize((const char *)w.buf, w.len);
    PyMem_Free(w.buf);
    return out;
}

static PyObject *py_decode_events(PyObject *self, PyObject *arg) {
    Py_buffer view;
    if (PyObject_GetBuffer(arg, &view, PyBUF_SIMPLE) < 0) return NULL;
    rd r = {(const uint8_t *)view.buf,
            (const uint8_t *)view.buf + view.len, 0};
    PyObject *events = PyList_New(0);
    if (!events) { PyBuffer_Release(&view); return NULL; }
    while (r.p < r.end) {
        const uint8_t *start = r.p;
        PyObject *obj = decode_obj(&r);
        if (!obj) {
            if (PyErr_ExceptionMatches(g_truncated)) {
                /* torn trailing record: Python-parity — keep prefix */
                PyErr_Clear();
                break;
            }
            goto fail;
        }
        PyObject *raw = PyBytes_FromStringAndSize(
            (const char *)start, r.p - start);
        if (!raw) { Py_DECREF(obj); goto fail; }
        PyObject *ev = to_event(obj, raw);
        Py_DECREF(obj);
        Py_DECREF(raw);
        if (!ev) goto fail;
        int rc = PyList_Append(events, ev);
        Py_DECREF(ev);
        if (rc < 0) goto fail;
    }
    PyBuffer_Release(&view);
    return events;
fail:
    Py_DECREF(events);
    PyBuffer_Release(&view);
    return NULL;
}

/* ------------------------------------------------------------------ */
/* filter_parser JSON fast path — whole-chunk JSON→msgpack transcode.
 *
 * parser_json_batch(buf, key) walks the concatenated V2 log-event
 * buffer once and, for every record whose top-level string field `key`
 * holds a JSON object, rewrites the record as
 * ``[[ts, meta], <parsed object>]`` — byte-exactly what the per-record
 * path (json.loads → dict → pack_event) produces:
 *
 *   - JSON object keys keep first-position/last-value duplicate
 *     semantics (Python dict insertion behavior);
 *   - ints pack with pack_obj's minimal-width rules, floats as f64,
 *     NaN/Infinity with CPython's exact bit patterns;
 *   - strings unescape (incl. surrogate pairs) to UTF-8;
 *   - parse failures / non-object documents / missing or non-string
 *     field values leave the record verbatim (the per-record path
 *     re-emits ev.raw for those).
 *
 * Anything the C path cannot reproduce bit-exactly raises
 * FallbackError and the caller runs the per-record path for the whole
 * chunk: legacy (V1) records, non-canonical msgpack in a parsed
 * record's [ts, meta] header (re-encode would change bytes), bin-typed
 * field values (decoded with errors="replace" upstream), invalid UTF-8
 * in the JSON text, ints beyond u64/i64, lone surrogate escapes, torn
 * trailing records, pathological nesting. */

#define JT_SYNTAX   (-1)  /* json.loads would fail → record verbatim */
#define JT_FALLBACK (-2)  /* bit-exactness not guaranteed → chunk decline */
#define JT_NOMEM    (-3)
#define JT_MAX_DEPTH 64
#define JT_MAX_ENTRIES 128

/* ---- span-level msgpack walking (no PyObject) ---- */

/* mp_walk: end of the one object at p, or NULL. A NULL with *bad
 * left alone means the buffer ended inside the object (more bytes may
 * complete it); *bad = 1 means no bytes ever will: 0xC1, or nesting
 * past MAX_DEPTH. */
static const uint8_t *mp_walk(const uint8_t *p, const uint8_t *end,
                              int depth, int *bad);

static const uint8_t *mp_skip_n(const uint8_t *p, const uint8_t *end,
                                long long n, int depth, int *bad) {
    for (long long i = 0; i < n; i++) {
        p = mp_walk(p, end, depth, bad);
        if (!p) return NULL;
    }
    return p;
}

static const uint8_t *mp_walk(const uint8_t *p, const uint8_t *end,
                              int depth, int *bad) {
    if (depth > MAX_DEPTH) { *bad = 1; return NULL; }
    if (p >= end) return NULL;
    uint8_t b = *p++;
    long long n;
    if (b < 0x80 || b >= 0xE0) return p;              /* fixint */
    if (b <= 0x8F)
        return mp_skip_n(p, end, 2LL * (b & 0x0F), depth + 1, bad);
    if (b <= 0x9F)
        return mp_skip_n(p, end, b & 0x0F, depth + 1, bad);
    if (b <= 0xBF) { n = b & 0x1F; return (end - p >= n) ? p + n : NULL; }
    switch (b) {
    case 0xC0: case 0xC2: case 0xC3: return p;
    case 0xC4: case 0xD9:
        if (end - p < 1) return NULL;
        n = p[0]; p += 1; return (end - p >= n) ? p + n : NULL;
    case 0xC5: case 0xDA:
        if (end - p < 2) return NULL;
        n = ((long long)p[0] << 8) | p[1]; p += 2;
        return (end - p >= n) ? p + n : NULL;
    case 0xC6: case 0xDB:
        if (end - p < 4) return NULL;
        n = ((long long)p[0] << 24) | ((long long)p[1] << 16)
          | ((long long)p[2] << 8) | p[3];
        p += 4; return (end - p >= n) ? p + n : NULL;
    case 0xC7:
        if (end - p < 2) return NULL;
        n = p[0]; p += 2; return (end - p >= n) ? p + n : NULL;
    case 0xC8:
        if (end - p < 3) return NULL;
        n = ((long long)p[0] << 8) | p[1]; p += 3;
        return (end - p >= n) ? p + n : NULL;
    case 0xC9:
        if (end - p < 5) return NULL;
        n = ((long long)p[0] << 24) | ((long long)p[1] << 16)
          | ((long long)p[2] << 8) | p[3];
        p += 5; return (end - p >= n) ? p + n : NULL;
    case 0xCA: return (end - p >= 4) ? p + 4 : NULL;
    case 0xCB: return (end - p >= 8) ? p + 8 : NULL;
    case 0xCC: case 0xD0: return (end - p >= 1) ? p + 1 : NULL;
    case 0xCD: case 0xD1: return (end - p >= 2) ? p + 2 : NULL;
    case 0xCE: case 0xD2: return (end - p >= 4) ? p + 4 : NULL;
    case 0xCF: case 0xD3: return (end - p >= 8) ? p + 8 : NULL;
    case 0xD4: case 0xD5: case 0xD6: case 0xD7: case 0xD8:
        n = 1 + ((long long)1 << (b - 0xD4));
        return (end - p >= n) ? p + n : NULL;
    case 0xDC:
        if (end - p < 2) return NULL;
        n = ((long long)p[0] << 8) | p[1];
        return mp_skip_n(p + 2, end, n, depth + 1, bad);
    case 0xDD:
        if (end - p < 4) return NULL;
        n = ((long long)p[0] << 24) | ((long long)p[1] << 16)
          | ((long long)p[2] << 8) | p[3];
        return mp_skip_n(p + 4, end, n, depth + 1, bad);
    case 0xDE:
        if (end - p < 2) return NULL;
        n = ((long long)p[0] << 8) | p[1];
        return mp_skip_n(p + 2, end, 2 * n, depth + 1, bad);
    case 0xDF:
        if (end - p < 4) return NULL;
        n = ((long long)p[0] << 24) | ((long long)p[1] << 16)
          | ((long long)p[2] << 8) | p[3];
        return mp_skip_n(p + 4, end, 2 * n, depth + 1, bad);
    default: *bad = 1; return NULL;                    /* 0xC1 */
    }
}

/* the walk where torn and malformed are one answer */
static const uint8_t *mp_skip_span(const uint8_t *p, const uint8_t *end,
                                   int depth) {
    int bad = 0;
    return mp_walk(p, end, depth, &bad);
}

/* str header reader: NULL when the object at p is not a str */
static const uint8_t *mp_str_hdr(const uint8_t *p, const uint8_t *end,
                                 long long *len_out) {
    if (p >= end) return NULL;
    uint8_t b = *p;
    if (b >= 0xA0 && b <= 0xBF) { *len_out = b & 0x1F; return p + 1; }
    if (b == 0xD9 && end - p >= 2) { *len_out = p[1]; return p + 2; }
    if (b == 0xDA && end - p >= 3) {
        *len_out = ((long long)p[1] << 8) | p[2]; return p + 3;
    }
    if (b == 0xDB && end - p >= 5) {
        *len_out = ((long long)p[1] << 24) | ((long long)p[2] << 16)
                 | ((long long)p[3] << 8) | p[4];
        return p + 5;
    }
    return NULL;
}

/* strict RFC 3629 validator — mirrors CPython's UTF-8 decoder, which
 * replaces exactly the sequences this rejects (so a fully valid span
 * means errors="replace" upstream was an identity). */
static int utf8_valid(const uint8_t *p, long long n) {
    const uint8_t *end = p + n;
    while (p < end) {
        uint8_t c = *p;
        if (c < 0x80) {
            /* log lines are mostly ASCII: eight bytes a test */
            uint64_t w;
            while (end - p >= 8 && (memcpy(&w, p, 8),
                                    !(w & 0x8080808080808080ULL)))
                p += 8;
            if (p < end && *p < 0x80) p++;
            continue;
        }
        if (c < 0xC2) return 0;
        if (c < 0xE0) {
            if (end - p < 2 || (p[1] & 0xC0) != 0x80) return 0;
            p += 2; continue;
        }
        if (c < 0xF0) {
            uint8_t lo = 0x80, hi = 0xBF;
            if (c == 0xE0) lo = 0xA0;
            else if (c == 0xED) hi = 0x9F;      /* no surrogates */
            if (end - p < 3 || p[1] < lo || p[1] > hi
                    || (p[2] & 0xC0) != 0x80) return 0;
            p += 3; continue;
        }
        if (c < 0xF5) {
            uint8_t lo = 0x80, hi = 0xBF;
            if (c == 0xF0) lo = 0x90;
            else if (c == 0xF4) hi = 0x8F;      /* <= U+10FFFF */
            if (end - p < 4 || p[1] < lo || p[1] > hi
                    || (p[2] & 0xC0) != 0x80
                    || (p[3] & 0xC0) != 0x80) return 0;
            p += 4; continue;
        }
        return 0;
    }
    return 1;
}

/* canonicality walk: 0 = decode→pack_obj round-trips to the same
 * bytes, JT_FALLBACK = it would not (or we cannot prove it), sets
 * *nx to the element end. Applied to the [ts, meta] header of parsed
 * records, whose bytes the transcoder copies verbatim in place of the
 * per-record path's re-encode. */
static int mp_canonical(const uint8_t *p, const uint8_t *end, int depth,
                        const uint8_t **nx) {
    if (depth > JT_MAX_DEPTH || p >= end) return JT_FALLBACK;
    uint8_t b = *p;
    long long n, i;
    const uint8_t *q;
    if (b < 0x80 || b >= 0xE0) { *nx = p + 1; return 0; }  /* fixint */
    if (b <= 0x8F || b == 0xDE || b == 0xDF) {             /* map */
        if (b <= 0x8F) { n = b & 0x0F; q = p + 1; }
        else if (b == 0xDE) {
            if (end - p < 3) return JT_FALLBACK;
            n = ((long long)p[1] << 8) | p[2]; q = p + 3;
            if (n < 16) return JT_FALLBACK;
        } else {
            if (end - p < 5) return JT_FALLBACK;
            n = ((long long)p[1] << 24) | ((long long)p[2] << 16)
              | ((long long)p[3] << 8) | p[4];
            q = p + 5;
            if (n <= 0xFFFF) return JT_FALLBACK;
        }
        /* map keys: require str keys and no duplicates — anything else
         * (int/float key collisions, dup dedup) can re-pack differently */
        const uint8_t *keys[16];
        long long klens[16];
        for (i = 0; i < n; i++) {
            long long klen;
            const uint8_t *kstr = mp_str_hdr(q, end, &klen);
            if (!kstr || klen > end - kstr) return JT_FALLBACK;
            int rc = mp_canonical(q, end, depth + 1, &q);
            if (rc) return rc;
            if (i < 16) {
                for (long long j = 0; j < i; j++)
                    if (klens[j] == klen
                            && memcmp(keys[j], kstr, klen) == 0)
                        return JT_FALLBACK;
                keys[i] = kstr; klens[i] = klen;
            } else {
                return JT_FALLBACK;  /* >16 keys: skip the dup proof */
            }
            rc = mp_canonical(q, end, depth + 1, &q);
            if (rc) return rc;
        }
        *nx = q;
        return 0;
    }
    if (b <= 0x9F || b == 0xDC || b == 0xDD) {             /* array */
        if (b <= 0x9F) { n = b & 0x0F; q = p + 1; }
        else if (b == 0xDC) {
            if (end - p < 3) return JT_FALLBACK;
            n = ((long long)p[1] << 8) | p[2]; q = p + 3;
            if (n < 16) return JT_FALLBACK;
        } else {
            if (end - p < 5) return JT_FALLBACK;
            n = ((long long)p[1] << 24) | ((long long)p[2] << 16)
              | ((long long)p[3] << 8) | p[4];
            q = p + 5;
            if (n <= 0xFFFF) return JT_FALLBACK;
        }
        for (i = 0; i < n; i++) {
            int rc = mp_canonical(q, end, depth + 1, &q);
            if (rc) return rc;
        }
        *nx = q;
        return 0;
    }
    if ((b >= 0xA0 && b <= 0xBF) || b == 0xD9 || b == 0xDA
            || b == 0xDB) {                                /* str */
        long long slen;
        const uint8_t *s = mp_str_hdr(p, end, &slen);
        if (!s || slen > end - s) return JT_FALLBACK;
        if (b == 0xD9 && slen < 32) return JT_FALLBACK;
        if (b == 0xDA && slen <= 0xFF) return JT_FALLBACK;
        if (b == 0xDB && slen <= 0xFFFF) return JT_FALLBACK;
        if (!utf8_valid(s, slen)) return JT_FALLBACK;  /* replace ≠ id */
        *nx = s + slen;
        return 0;
    }
    switch (b) {
    case 0xC0: case 0xC2: case 0xC3: *nx = p + 1; return 0;
    case 0xC4:                                             /* bin8 */
        if (end - p < 2) return JT_FALLBACK;
        n = p[1];
        if (end - (p + 2) < n) return JT_FALLBACK;
        *nx = p + 2 + n;
        return 0;
    case 0xC5:
        if (end - p < 3) return JT_FALLBACK;
        n = ((long long)p[1] << 8) | p[2];
        if (n <= 0xFF || end - (p + 3) < n) return JT_FALLBACK;
        *nx = p + 3 + n;
        return 0;
    case 0xC6:
        if (end - p < 5) return JT_FALLBACK;
        n = ((long long)p[1] << 24) | ((long long)p[2] << 16)
          | ((long long)p[3] << 8) | p[4];
        if (n <= 0xFFFF || end - (p + 5) < n) return JT_FALLBACK;
        *nx = p + 5 + n;
        return 0;
    case 0xCB: return (end - p >= 9) ? (*nx = p + 9, 0) : JT_FALLBACK;
    case 0xCC:
        if (end - p < 2 || p[1] < 0x80) return JT_FALLBACK;
        *nx = p + 2; return 0;
    case 0xCD: {
        if (end - p < 3) return JT_FALLBACK;
        uint64_t v = ((uint64_t)p[1] << 8) | p[2];
        if (v <= 0xFF) return JT_FALLBACK;
        *nx = p + 3; return 0;
    }
    case 0xCE: {
        if (end - p < 5) return JT_FALLBACK;
        uint64_t v = ((uint64_t)p[1] << 24) | ((uint64_t)p[2] << 16)
                   | ((uint64_t)p[3] << 8) | p[4];
        if (v <= 0xFFFF) return JT_FALLBACK;
        *nx = p + 5; return 0;
    }
    case 0xCF: {
        if (end - p < 9) return JT_FALLBACK;
        uint64_t v = 0;
        for (i = 1; i <= 8; i++) v = (v << 8) | p[i];
        if (v <= 0xFFFFFFFFULL) return JT_FALLBACK;
        *nx = p + 9; return 0;
    }
    case 0xD0: {
        if (end - p < 2) return JT_FALLBACK;
        int8_t v = (int8_t)p[1];
        if (v >= -32) return JT_FALLBACK;
        *nx = p + 2; return 0;
    }
    case 0xD1: {
        if (end - p < 3) return JT_FALLBACK;
        int16_t v = (int16_t)(((uint16_t)p[1] << 8) | p[2]);
        if (v >= -128) return JT_FALLBACK;
        *nx = p + 3; return 0;
    }
    case 0xD2: {
        if (end - p < 5) return JT_FALLBACK;
        int32_t v = (int32_t)(((uint32_t)p[1] << 24)
                              | ((uint32_t)p[2] << 16)
                              | ((uint32_t)p[3] << 8) | p[4]);
        if (v >= -32768) return JT_FALLBACK;
        *nx = p + 5; return 0;
    }
    case 0xD3: {
        if (end - p < 9) return JT_FALLBACK;
        uint64_t u = 0;
        for (i = 1; i <= 8; i++) u = (u << 8) | p[i];
        if ((int64_t)u >= -2147483648LL) return JT_FALLBACK;
        *nx = p + 9; return 0;
    }
    case 0xD7:                    /* fixext8: EventTime round-trips */
        if (end - p < 10 || p[1] != 0x00) return JT_FALLBACK;
        *nx = p + 10;
        return 0;
    /* float32 re-packs as float64; other ext types build ExtType —
     * both change bytes on re-encode */
    default: return JT_FALLBACK;
    }
}

/* ---- JSON scanner/emitter ---- */

typedef struct {
    const uint8_t *p, *end;
    wr *w;
    int depth;
} jt;

static int jt_value(jt *t);

static void jt_ws(jt *t) {
    while (t->p < t->end) {
        uint8_t c = *t->p;
        if (c == ' ' || c == '\t' || c == '\n' || c == '\r') t->p++;
        else break;
    }
}

static int wr_insert(wr *w, Py_ssize_t at, const uint8_t *hdr, int n) {
    if (wr_reserve(w, n) < 0) return JT_NOMEM;
    memmove(w->buf + at + n, w->buf + at, w->len - at);
    memcpy(w->buf + at, hdr, n);
    w->len += n;
    return 0;
}

static int jt_close_str(wr *w, Py_ssize_t start) {
    Py_ssize_t n = w->len - start;
    uint8_t hdr[5];
    int hl;
    if (n < 32) { hdr[0] = (uint8_t)(0xA0 | n); hl = 1; }
    else if (n <= 0xFF) { hdr[0] = 0xD9; hdr[1] = (uint8_t)n; hl = 2; }
    else if (n <= 0xFFFF) {
        hdr[0] = 0xDA; hdr[1] = (uint8_t)(n >> 8); hdr[2] = (uint8_t)n;
        hl = 3;
    } else {
        hdr[0] = 0xDB;
        hdr[1] = (uint8_t)(n >> 24); hdr[2] = (uint8_t)(n >> 16);
        hdr[3] = (uint8_t)(n >> 8); hdr[4] = (uint8_t)n;
        hl = 5;
    }
    return wr_insert(w, start, hdr, hl);
}

static int jt_close_seq(wr *w, Py_ssize_t start, long long n,
                        uint8_t fixbase, uint8_t b16, uint8_t b32) {
    uint8_t hdr[5];
    int hl;
    if (n < 16) { hdr[0] = (uint8_t)(fixbase | n); hl = 1; }
    else if (n <= 0xFFFF) {
        hdr[0] = b16; hdr[1] = (uint8_t)(n >> 8); hdr[2] = (uint8_t)n;
        hl = 3;
    } else {
        hdr[0] = b32;
        hdr[1] = (uint8_t)(n >> 24); hdr[2] = (uint8_t)(n >> 16);
        hdr[3] = (uint8_t)(n >> 8); hdr[4] = (uint8_t)n;
        hl = 5;
    }
    return wr_insert(w, start, hdr, hl);
}

static int wr_utf8cp(wr *w, uint32_t cp) {
    uint8_t b[4];
    int n;
    if (cp < 0x80) { b[0] = (uint8_t)cp; n = 1; }
    else if (cp < 0x800) {
        b[0] = 0xC0 | (cp >> 6); b[1] = 0x80 | (cp & 0x3F); n = 2;
    } else if (cp < 0x10000) {
        b[0] = 0xE0 | (cp >> 12); b[1] = 0x80 | ((cp >> 6) & 0x3F);
        b[2] = 0x80 | (cp & 0x3F); n = 3;
    } else {
        b[0] = 0xF0 | (cp >> 18); b[1] = 0x80 | ((cp >> 12) & 0x3F);
        b[2] = 0x80 | ((cp >> 6) & 0x3F); b[3] = 0x80 | (cp & 0x3F);
        n = 4;
    }
    return wr_bytes(w, b, n) < 0 ? JT_NOMEM : 0;
}

static int jt_hex4(jt *t, uint32_t *out) {
    if (t->end - t->p < 4) return JT_SYNTAX;
    uint32_t v = 0;
    for (int i = 0; i < 4; i++) {
        uint8_t c = t->p[i];
        uint32_t d;
        if (c >= '0' && c <= '9') d = c - '0';
        else if (c >= 'a' && c <= 'f') d = c - 'a' + 10;
        else if (c >= 'A' && c <= 'F') d = c - 'A' + 10;
        else return JT_SYNTAX;
        v = (v << 4) | d;
    }
    t->p += 4;
    *out = v;
    return 0;
}

static int jt_string(jt *t) {
    t->p++;  /* opening quote */
    Py_ssize_t start = t->w->len;
    for (;;) {
        /* bulk-copy the plain run */
        const uint8_t *run = t->p;
        while (t->p < t->end && *t->p != '"' && *t->p != '\\'
               && *t->p >= 0x20)
            t->p++;
        if (t->p > run && wr_bytes(t->w, run, t->p - run) < 0)
            return JT_NOMEM;
        if (t->p >= t->end) return JT_SYNTAX;
        uint8_t c = *t->p;
        if (c == '"') { t->p++; break; }
        if (c < 0x20) return JT_SYNTAX;  /* strict: raw control char */
        t->p++;  /* backslash */
        if (t->p >= t->end) return JT_SYNTAX;
        uint8_t e = *t->p++;
        int rc = 0;
        switch (e) {
        case '"': rc = wr_u8(t->w, '"'); break;
        case '\\': rc = wr_u8(t->w, '\\'); break;
        case '/': rc = wr_u8(t->w, '/'); break;
        case 'b': rc = wr_u8(t->w, '\b'); break;
        case 'f': rc = wr_u8(t->w, '\f'); break;
        case 'n': rc = wr_u8(t->w, '\n'); break;
        case 'r': rc = wr_u8(t->w, '\r'); break;
        case 't': rc = wr_u8(t->w, '\t'); break;
        case 'u': {
            uint32_t cp;
            int hrc = jt_hex4(t, &cp);
            if (hrc) return hrc;
            if (cp >= 0xDC00 && cp <= 0xDFFF)
                return JT_FALLBACK;  /* lone low surrogate */
            if (cp >= 0xD800 && cp <= 0xDBFF) {
                if (t->end - t->p < 6 || t->p[0] != '\\'
                        || t->p[1] != 'u')
                    return JT_FALLBACK;  /* lone high surrogate */
                t->p += 2;
                uint32_t lo;
                hrc = jt_hex4(t, &lo);
                if (hrc) return hrc;
                if (lo < 0xDC00 || lo > 0xDFFF) return JT_FALLBACK;
                cp = 0x10000 + ((cp - 0xD800) << 10) + (lo - 0xDC00);
            }
            hrc = wr_utf8cp(t->w, cp);
            if (hrc) return hrc;
            rc = 0;
            break;
        }
        default: return JT_SYNTAX;
        }
        if (rc < 0) return JT_NOMEM;
    }
    return jt_close_str(t->w, start);
}

static int wr_pack_int(wr *w, int neg, unsigned long long mag) {
    int rc;
    if (!neg) {
        if (mag < 0x80) return wr_u8(w, (uint8_t)mag) < 0 ? JT_NOMEM : 0;
        if (mag <= 0xFF) {
            rc = wr_u8(w, 0xCC) < 0 || wr_u8(w, (uint8_t)mag) < 0;
        } else if (mag <= 0xFFFF) {
            rc = wr_u8(w, 0xCD) < 0 || wr_be(w, mag, 2) < 0;
        } else if (mag <= 0xFFFFFFFFULL) {
            rc = wr_u8(w, 0xCE) < 0 || wr_be(w, mag, 4) < 0;
        } else {
            rc = wr_u8(w, 0xCF) < 0 || wr_be(w, mag, 8) < 0;
        }
        return rc ? JT_NOMEM : 0;
    }
    if (mag > 0x8000000000000000ULL) return JT_FALLBACK;  /* < i64 min */
    long long v = (long long)(0 - mag);
    if (v >= -32)
        return wr_u8(w, (uint8_t)(int8_t)v) < 0 ? JT_NOMEM : 0;
    if (v >= -128)
        rc = wr_u8(w, 0xD0) < 0 || wr_u8(w, (uint8_t)(int8_t)v) < 0;
    else if (v >= -32768)
        rc = wr_u8(w, 0xD1) < 0
            || wr_be(w, (uint64_t)(uint16_t)(int16_t)v, 2) < 0;
    else if (v >= -2147483648LL)
        rc = wr_u8(w, 0xD2) < 0
            || wr_be(w, (uint64_t)(uint32_t)(int32_t)v, 4) < 0;
    else
        rc = wr_u8(w, 0xD3) < 0 || wr_be(w, (uint64_t)v, 8) < 0;
    return rc ? JT_NOMEM : 0;
}

static int wr_pack_f64(wr *w, double d) {
    union { double d; uint64_t u; } c;
    c.d = d;
    if (wr_u8(w, 0xCB) < 0 || wr_be(w, c.u, 8) < 0) return JT_NOMEM;
    return 0;
}

static int wr_pack_f64_bits(wr *w, uint64_t bits) {
    if (wr_u8(w, 0xCB) < 0 || wr_be(w, bits, 8) < 0) return JT_NOMEM;
    return 0;
}

static int jt_number(jt *t) {
    const uint8_t *tok = t->p;
    int neg = 0, is_float = 0;
    if (t->p < t->end && *t->p == '-') { neg = 1; t->p++; }
    if (t->p >= t->end) return JT_SYNTAX;
    if (*t->p == '0') {
        t->p++;
        if (t->p < t->end && *t->p >= '0' && *t->p <= '9')
            return JT_SYNTAX;  /* leading zero */
    } else if (*t->p >= '1' && *t->p <= '9') {
        while (t->p < t->end && *t->p >= '0' && *t->p <= '9') t->p++;
    } else {
        return JT_SYNTAX;
    }
    if (t->p < t->end && *t->p == '.') {
        is_float = 1;
        t->p++;
        if (t->p >= t->end || *t->p < '0' || *t->p > '9')
            return JT_SYNTAX;
        while (t->p < t->end && *t->p >= '0' && *t->p <= '9') t->p++;
    }
    if (t->p < t->end && (*t->p == 'e' || *t->p == 'E')) {
        is_float = 1;
        t->p++;
        if (t->p < t->end && (*t->p == '+' || *t->p == '-')) t->p++;
        if (t->p >= t->end || *t->p < '0' || *t->p > '9')
            return JT_SYNTAX;
        while (t->p < t->end && *t->p >= '0' && *t->p <= '9') t->p++;
    }
    Py_ssize_t toklen = t->p - tok;
    if (is_float) {
        char buf[384];
        if (toklen >= (Py_ssize_t)sizeof(buf)) return JT_FALLBACK;
        memcpy(buf, tok, toklen);
        buf[toklen] = '\0';
        char *endp = NULL;
        double d = strtod(buf, &endp);
        if (endp != buf + toklen) return JT_FALLBACK;
        return wr_pack_f64(t->w, d);
    }
    /* integer: accumulate magnitude with overflow detection */
    const uint8_t *q = tok + neg;
    unsigned long long mag = 0;
    for (; q < t->p; q++) {
        unsigned long long d = (unsigned long long)(*q - '0');
        if (mag > (0xFFFFFFFFFFFFFFFFULL - d) / 10)
            return JT_FALLBACK;  /* Python bigint territory */
        mag = mag * 10 + d;
    }
    return wr_pack_int(t->w, neg, mag);
}

static int jt_object(jt *t) {
    if (++t->depth > JT_MAX_DEPTH) { t->depth--; return JT_FALLBACK; }
    t->p++;  /* '{' */
    wr *w = t->w;
    Py_ssize_t start = w->len;
    struct { Py_ssize_t koff, kend, vend; } ents[JT_MAX_ENTRIES];
    long long n = 0;
    jt_ws(t);
    if (t->p < t->end && *t->p == '}') {
        t->p++;
    } else {
        for (;;) {
            jt_ws(t);
            if (t->p >= t->end || *t->p != '"') { t->depth--; return JT_SYNTAX; }
            Py_ssize_t koff = w->len;
            int rc = jt_string(t);
            if (rc) { t->depth--; return rc; }
            Py_ssize_t kend = w->len;
            jt_ws(t);
            if (t->p >= t->end || *t->p != ':') { t->depth--; return JT_SYNTAX; }
            t->p++;
            jt_ws(t);
            rc = jt_value(t);
            if (rc) { t->depth--; return rc; }
            Py_ssize_t vend = w->len;
            /* duplicate key → Python dict semantics: keep the FIRST
             * position, take the LAST value */
            long long dup = -1;
            for (long long i = 0; i < n; i++) {
                if (ents[i].kend - ents[i].koff == kend - koff
                        && memcmp(w->buf + ents[i].koff, w->buf + koff,
                                  kend - koff) == 0) {
                    dup = i;
                    break;
                }
            }
            if (dup >= 0) {
                Py_ssize_t nvlen = vend - kend;
                Py_ssize_t ovoff = ents[dup].kend;
                Py_ssize_t ovend = ents[dup].vend;
                Py_ssize_t ovlen = ovend - ovoff;
                uint8_t *tmp = (uint8_t *)PyMem_Malloc(nvlen ? nvlen : 1);
                if (!tmp) { t->depth--; return JT_NOMEM; }
                memcpy(tmp, w->buf + kend, nvlen);
                w->len = koff;  /* drop the new entry from the tail */
                Py_ssize_t delta = nvlen - ovlen;
                if (delta > 0 && wr_reserve(w, delta) < 0) {
                    PyMem_Free(tmp);
                    t->depth--;
                    return JT_NOMEM;
                }
                memmove(w->buf + ovoff + nvlen, w->buf + ovend,
                        w->len - ovend);
                memcpy(w->buf + ovoff, tmp, nvlen);
                PyMem_Free(tmp);
                w->len += delta;
                for (long long i = 0; i < n; i++) {
                    if (ents[i].koff > ovoff) {
                        ents[i].koff += delta;
                        ents[i].kend += delta;
                    }
                    if (ents[i].vend >= ovend) ents[i].vend += delta;
                }
            } else {
                if (n >= JT_MAX_ENTRIES) { t->depth--; return JT_FALLBACK; }
                ents[n].koff = koff;
                ents[n].kend = kend;
                ents[n].vend = vend;
                n++;
            }
            jt_ws(t);
            if (t->p >= t->end) { t->depth--; return JT_SYNTAX; }
            if (*t->p == ',') { t->p++; continue; }
            if (*t->p == '}') { t->p++; break; }
            t->depth--;
            return JT_SYNTAX;
        }
    }
    t->depth--;
    return jt_close_seq(w, start, n, 0x80, 0xDE, 0xDF);
}

static int jt_array(jt *t) {
    if (++t->depth > JT_MAX_DEPTH) { t->depth--; return JT_FALLBACK; }
    t->p++;  /* '[' */
    Py_ssize_t start = t->w->len;
    long long n = 0;
    jt_ws(t);
    if (t->p < t->end && *t->p == ']') {
        t->p++;
    } else {
        for (;;) {
            jt_ws(t);
            int rc = jt_value(t);
            if (rc) { t->depth--; return rc; }
            n++;
            jt_ws(t);
            if (t->p >= t->end) { t->depth--; return JT_SYNTAX; }
            if (*t->p == ',') { t->p++; continue; }
            if (*t->p == ']') { t->p++; break; }
            t->depth--;
            return JT_SYNTAX;
        }
    }
    t->depth--;
    return jt_close_seq(t->w, start, n, 0x90, 0xDC, 0xDD);
}

static int jt_lit(jt *t, const char *word, Py_ssize_t wl) {
    if (t->end - t->p < wl || memcmp(t->p, word, wl) != 0)
        return JT_SYNTAX;
    t->p += wl;
    return 0;
}

static int jt_value(jt *t) {
    if (t->p >= t->end) return JT_SYNTAX;
    uint8_t c = *t->p;
    int rc;
    switch (c) {
    case '{': return jt_object(t);
    case '[': return jt_array(t);
    case '"': return jt_string(t);
    case 't':
        rc = jt_lit(t, "true", 4);
        if (rc) return rc;
        return wr_u8(t->w, 0xC3) < 0 ? JT_NOMEM : 0;
    case 'f':
        rc = jt_lit(t, "false", 5);
        if (rc) return rc;
        return wr_u8(t->w, 0xC2) < 0 ? JT_NOMEM : 0;
    case 'n':
        rc = jt_lit(t, "null", 4);
        if (rc) return rc;
        return wr_u8(t->w, 0xC0) < 0 ? JT_NOMEM : 0;
    /* CPython's json accepts these constants by default and maps them
     * to float('nan')/float('inf') — match the exact bit patterns */
    case 'N':
        rc = jt_lit(t, "NaN", 3);
        if (rc) return rc;
        return wr_pack_f64_bits(t->w, 0x7FF8000000000000ULL);
    case 'I':
        rc = jt_lit(t, "Infinity", 8);
        if (rc) return rc;
        return wr_pack_f64_bits(t->w, 0x7FF0000000000000ULL);
    case '-':
        if (t->end - t->p >= 2 && t->p[1] == 'I') {
            rc = jt_lit(t, "-Infinity", 9);
            if (rc) return rc;
            return wr_pack_f64_bits(t->w, 0xFFF0000000000000ULL);
        }
        return jt_number(t);
    default:
        if (c >= '0' && c <= '9') return jt_number(t);
        return JT_SYNTAX;
    }
}

/* one record: 1 = parsed + re-emitted, 0 = copied verbatim,
 * JT_FALLBACK / JT_NOMEM on the chunk-decline paths */
static int transcode_record(const uint8_t *rec, const uint8_t *end,
                            const uint8_t *key, Py_ssize_t keylen,
                            wr *w, const uint8_t **rec_end_out) {
    const uint8_t *rend = mp_skip_span(rec, end, 0);
    if (!rend) return JT_FALLBACK;  /* malformed or torn tail */
    *rec_end_out = rend;
    /* the per-record path re-encodes legacy / odd-arity records as V2;
     * only the exact [[ts, meta], body] shape copies through */
    if (*rec != 0x92) return JT_FALLBACK;
    const uint8_t *hdr = rec + 1;
    if (hdr >= end || *hdr != 0x92) return JT_FALLBACK;
    const uint8_t *ts = hdr + 1;
    const uint8_t *meta = mp_skip_span(ts, end, 0);
    if (!meta) return JT_FALLBACK;
    const uint8_t *body = mp_skip_span(meta, end, 0);
    if (!body || body >= rend) return JT_FALLBACK;
    /* body must be a map; otherwise the record passes through */
    uint8_t b = *body;
    long long pairs;
    const uint8_t *kv;
    if (b >= 0x80 && b <= 0x8F) { pairs = b & 0x0F; kv = body + 1; }
    else if (b == 0xDE && end - body >= 3) {
        pairs = ((long long)body[1] << 8) | body[2];
        kv = body + 3;
    } else if (b == 0xDF && end - body >= 5) {
        pairs = ((long long)body[1] << 24) | ((long long)body[2] << 16)
              | ((long long)body[3] << 8) | body[4];
        kv = body + 5;
    } else {
        goto verbatim;
    }
    {
        /* find the LAST occurrence of the key (dict decode keeps it) */
        const uint8_t *vstr = NULL;
        long long vlen = 0;
        int hit_kind = 0;  /* 0 none, 1 str, 2 other, 3 bin */
        for (long long i = 0; i < pairs; i++) {
            long long klen;
            const uint8_t *kstr = mp_str_hdr(kv, end, &klen);
            const uint8_t *val;
            int match = 0;
            if (kstr && klen <= end - kstr) {
                val = kstr + klen;
                match = (klen == keylen && memcmp(kstr, key, klen) == 0);
            } else {
                val = mp_skip_span(kv, end, 0);  /* non-str key */
                if (!val) return JT_FALLBACK;
            }
            if (match) {
                if (val >= end) return JT_FALLBACK;
                long long sl;
                const uint8_t *s = mp_str_hdr(val, end, &sl);
                if (s && sl <= end - s) {
                    vstr = s;
                    vlen = sl;
                    hit_kind = 1;
                } else if (*val == 0xC4 || *val == 0xC5
                           || *val == 0xC6) {
                    /* bin value: _to_str decodes with errors="replace"
                     * and still parses — C can't reproduce that */
                    hit_kind = 3;
                } else {
                    hit_kind = 2;  /* non-string: _to_str → None */
                }
            }
            kv = mp_skip_span(val, end, 0);
            if (!kv) return JT_FALLBACK;
        }
        if (hit_kind == 3) return JT_FALLBACK;
        if (hit_kind != 1) goto verbatim;
        /* JSON must be an object for _do_json to replace the body */
        const uint8_t *jp = vstr, *jend = vstr + vlen;
        while (jp < jend && (*jp == ' ' || *jp == '\t' || *jp == '\n'
                             || *jp == '\r'))
            jp++;
        if (jp >= jend || *jp != '{') goto verbatim;
        if (!utf8_valid(vstr, vlen)) return JT_FALLBACK;
        /* the header bytes stand in for the per-record re-encode, so
         * they must be canonical (decode→pack round-trip identity) */
        const uint8_t *nx;
        if (mp_canonical(ts, meta, 0, &nx) || nx != meta)
            return JT_FALLBACK;
        if (mp_canonical(meta, body, 0, &nx) || nx != body)
            return JT_FALLBACK;
        Py_ssize_t ckpt = w->len;
        if (wr_u8(w, 0x92) < 0 || wr_u8(w, 0x92) < 0
                || wr_bytes(w, ts, body - ts) < 0)
            return JT_NOMEM;
        jt t = {jp, jend, w, 0};
        int rc = jt_object(&t);
        if (rc == 0) {
            jt_ws(&t);
            if (t.p != t.end) rc = JT_SYNTAX;  /* trailing garbage */
        }
        if (rc == JT_SYNTAX) {
            w->len = ckpt;  /* json.loads would fail → verbatim */
            goto verbatim;
        }
        if (rc) return rc;
        return 1;
    }
verbatim:
    if (wr_bytes(w, rec, rend - rec) < 0) return JT_NOMEM;
    return 0;
}

static PyObject *py_parser_json_batch(PyObject *self, PyObject *args) {
    Py_buffer view;
    const char *key;
    Py_ssize_t keylen;
    if (!PyArg_ParseTuple(args, "y*y#", &view, &key, &keylen))
        return NULL;
    const uint8_t *p = (const uint8_t *)view.buf;
    const uint8_t *end = p + view.len;
    wr w = {NULL, 0, 0, 0};
    long long n = 0, parsed = 0;
    int rc = 0;
    while (p < end) {
        const uint8_t *rec_end = NULL;
        rc = transcode_record(p, end, (const uint8_t *)key, keylen,
                              &w, &rec_end);
        if (rc < 0) break;
        parsed += rc;
        n++;
        p = rec_end;
    }
    if (rc < 0) {
        PyMem_Free(w.buf);
        PyBuffer_Release(&view);
        if (rc == JT_FALLBACK)
            PyErr_SetString(g_fallback,
                            "record outside the fast-transcode set");
        else if (!PyErr_Occurred())
            PyErr_NoMemory();
        return NULL;
    }
    PyObject *out = PyBytes_FromStringAndSize((const char *)w.buf, w.len);
    PyMem_Free(w.buf);
    PyBuffer_Release(&view);
    if (!out) return NULL;
    PyObject *res = Py_BuildValue("(NLL)", out, n, parsed);
    return res;
}

/* ------------------------------------------------------------------ */
/* filter_parser's record build from the device's spans.
 *
 * parser_spans_build(data, offsets, planes, lengths, ok, spans, names,
 *                    casts, time_group, time_keep, time_fmt,
 *                    time_offset, skip_empty, need_orig, preserve,
 *                    pair, key_group)
 *     → (out, leftovers, native_rows, host_rows)
 *
 * The chunk's new bytes from the span verdict of filter_grep.staged_match,
 * the twin of filter_parser._span_event for every row whose result it can
 * prove, with the GIL released from the first row to the last. `names` are
 * the groups' UTF-8 names in the spans' order, `casts` one byte a group (1:
 * Types integer), `time_fmt` the Time_Format compiled by filter_parser to
 * ops ('W' white space, 'L' + a literal byte, and the directives d m b Y
 * H M S z), `pair` the body {key: ...}'s head (0x81 + the packed key) and
 * `key_group` the group named as the key, or -1.
 *
 * A row passes through (its bytes copied in runs) where it does not match
 * or leaves no field; it is built here where it is a matched ASCII device
 * row, its integers strict ^[+-]?[0-9]+$ within msgpack's range (a capture
 * with no digit and no '.' stays a string: int() rejects it), its time
 * the format's shape within flb_strptime's ranges, its record
 * [[EventTime, {}], body] with the body {key: value} where the originals
 * count. Every other row is a leftover: an empty place in `out` at its
 * position, (row, pos, host) in `leftovers`, for the Python build to fill.
 * A host row is one with no staged value or a byte past ASCII. Offsets,
 * planes or spans that do not describe the chunk raise FallbackError. */

#define SB_PASS  0
#define SB_BUILT 1
#define SB_LEFT  2
#define SB_MAX_GROUPS 256

typedef struct {
    Py_ssize_t n_groups;
    const uint8_t *names[SB_MAX_GROUPS];
    Py_ssize_t name_len[SB_MAX_GROUPS];
    const uint8_t *casts, *fmt, *pair;
    Py_ssize_t fmt_len, pair_len;
    int time_group, time_keep, skip_empty, need_orig, preserve, key_group;
    long long time_offset;
} sb_desc;

typedef struct {
    long long year, mon, mday, hour, min, sec, off;
    int has_off;
} sb_tm;

typedef struct {
    Py_ssize_t row, pos;
    int host;
} sb_left;

/* str.isspace() over ASCII: \t-\r, the four separators \x1c-\x1f, ' ' */
static int sb_isspace(uint8_t c) {
    return (c >= 9 && c <= 13) || (c >= 28 && c <= 32);
}

/* strptime's _digits: 1 to `max` ASCII digits at *i → their value, or -1
 * with *i unmoved */
static long long sb_digits(const uint8_t *s, Py_ssize_t s_len,
                           Py_ssize_t *i, int max) {
    Py_ssize_t j = *i;
    long long v = 0;
    while (j < s_len && j - *i < max && s[j] >= '0' && s[j] <= '9')
        v = v * 10 + (s[j++] - '0');
    if (j == *i) return -1;
    *i = j;
    return v;
}

static const char *const sb_months[12] = {
    "january", "february", "march", "april", "may", "june", "july",
    "august", "september", "october", "november", "december"};

static int sb_ieq(const uint8_t *s, const char *name, Py_ssize_t n) {
    for (Py_ssize_t k = 0; k < n; k++) {
        uint8_t c = s[k];
        if (c >= 'A' && c <= 'Z') c = (uint8_t)(c + 32);
        if (c != (uint8_t)name[k]) return 0;
    }
    return 1;
}

/* strptime's _name over the months: the first whose three letters
 * match, its whole name where that follows → 1..12, or -1 */
static int sb_month(const uint8_t *s, Py_ssize_t s_len, Py_ssize_t *i) {
    Py_ssize_t left_len = s_len - *i;
    if (left_len < 3) return -1;
    for (int m = 0; m < 12; m++) {
        Py_ssize_t nl = (Py_ssize_t)strlen(sb_months[m]);
        if (!sb_ieq(s + *i, sb_months[m], 3)) continue;
        *i += nl <= left_len && sb_ieq(s + *i, sb_months[m], nl) ? nl : 3;
        return m + 1;
    }
    return -1;
}

/* flb_strptime over the compiled ops: 0, or -1 where it returns None;
 * trailing bytes are left, as there */
static int sb_strptime(const uint8_t *s, Py_ssize_t s_len,
                       const uint8_t *fmt, Py_ssize_t fmt_len, sb_tm *tm) {
    Py_ssize_t i = 0;
    for (Py_ssize_t f = 0; f < fmt_len; f++) {
        long long v, h, m;
        switch (fmt[f]) {
        case 'W':
            while (i < s_len && sb_isspace(s[i])) i++;
            break;
        case 'L':
            if (f + 1 >= fmt_len || i >= s_len || s[i] != fmt[f + 1])
                return -1;
            i++;
            f++;
            break;
        case 'd':
            v = sb_digits(s, s_len, &i, 2);
            if (v < 1 || v > 31) return -1;
            tm->mday = v;
            break;
        case 'm':
            v = sb_digits(s, s_len, &i, 2);
            if (v < 1 || v > 12) return -1;
            tm->mon = v;
            break;
        case 'b':
            v = sb_month(s, s_len, &i);
            if (v < 0) return -1;
            tm->mon = v;
            break;
        case 'Y':
            v = sb_digits(s, s_len, &i, 4);
            if (v < 0) return -1;
            tm->year = v;
            break;
        case 'H':
            v = sb_digits(s, s_len, &i, 2);
            if (v < 0 || v > 23) return -1;
            tm->hour = v;
            break;
        case 'M':
            v = sb_digits(s, s_len, &i, 2);
            if (v < 0 || v > 59) return -1;
            tm->min = v;
            break;
        case 'S':
            v = sb_digits(s, s_len, &i, 2);
            if (v < 0 || v > 61) return -1;
            tm->sec = v;
            break;
        case 'z':
            if (i < s_len && (s[i] == 'Z' || s[i] == 'z')) {
                tm->off = 0;
                tm->has_off = 1;
                i++;
                break;
            }
            if (i >= s_len || (s[i] != '+' && s[i] != '-')) return -1;
            v = s[i++] == '-' ? -1 : 1;
            h = sb_digits(s, s_len, &i, 2);
            if (h < 0) return -1;
            if (i < s_len && s[i] == ':') i++;
            m = sb_digits(s, s_len, &i, 2);
            tm->off = v * (h * 3600 + (m < 0 ? 0 : m) * 60);
            tm->has_off = 1;
            break;
        default:
            return -1;
        }
    }
    return 0;
}

/* Tm.to_epoch through calendar.timegm (days from the civil date, as
 * datetime.date.toordinal counts them); -1 where date() would raise */
static int sb_epoch(const sb_tm *tm, long long dflt_off, long long *out) {
    if (tm->year < 1 || tm->year > 9999) return -1;
    long long y = tm->year - (tm->mon <= 2);
    long long mp = tm->mon > 2 ? tm->mon - 3 : tm->mon + 9;
    long long era = y / 400;  /* y >= 0 */
    long long yoe = y - era * 400;
    long long doe = yoe * 365 + yoe / 4 - yoe / 100 + (153 * mp + 2) / 5;
    long long days = era * 146097 + doe - 719468 + tm->mday - 1;
    *out = ((days * 24 + tm->hour) * 60 + tm->min) * 60 + tm->sec
        - (tm->has_off ? tm->off : dflt_off);
    return 0;
}

/* _cast_int's outcome where it is certain: 1 an integer msgpack packs
 * (neg, mag), 0 the string itself, -1 the Python build decides */
static int sb_int(const uint8_t *v, Py_ssize_t v_len, int *neg,
                  unsigned long long *mag) {
    int digit = 0, dot = 0;
    for (Py_ssize_t k = 0; k < v_len; k++) {
        digit |= v[k] >= '0' && v[k] <= '9';
        dot |= v[k] == '.';
    }
    if (!digit && !dot) return 0;
    Py_ssize_t k = v_len > 0 && (v[0] == '+' || v[0] == '-');
    *neg = k && v[0] == '-';
    unsigned long long m = 0;
    if (k >= v_len) return -1;
    for (; k < v_len; k++) {
        if (v[k] < '0' || v[k] > '9') return -1;
        unsigned d = (unsigned)(v[k] - '0');
        if (m > (UINT64_MAX - d) / 10) return -1;
        m = m * 10 + d;
    }
    if (*neg && m > 0x8000000000000000ULL) return -1;
    *mag = m;
    return 1;
}

/* pack_obj's str branch over bytes */
static int wr_pack_str(wr *w, const uint8_t *s, Py_ssize_t n) {
    int rc;
    if (n < 32)
        rc = wr_u8(w, (uint8_t)(0xA0 | n));
    else if (n <= 0xFF)
        rc = wr_u8(w, 0xD9) < 0 ? -1 : wr_u8(w, (uint8_t)n);
    else if (n <= 0xFFFF)
        rc = wr_u8(w, 0xDA) < 0 ? -1 : wr_be(w, (uint64_t)n, 2);
    else
        rc = wr_u8(w, 0xDB) < 0 ? -1 : wr_be(w, (uint64_t)n, 4);
    return rc < 0 ? -1 : wr_bytes(w, s, n);
}

/* room for `extra` more bytes with plain realloc (no GIL): the writers
 * after it then never reach wr_reserve's PyMem_Realloc */
static int sb_room(wr *w, Py_ssize_t extra) {
    if (extra <= w->cap - w->len) return 0;
    Py_ssize_t ncap = w->cap ? w->cap : 4096;
    while (ncap - w->len < extra) ncap *= 2;
    uint8_t *nb = realloc(w->buf, (size_t)ncap);
    if (!nb) return -1;
    w->buf = nb;
    w->cap = ncap;
    return 0;
}

/* what sb_decide finds of a row and sb_write packs */
typedef struct {
    uint8_t present[SB_MAX_GROUPS], as_int[SB_MAX_GROUPS];
    int neg[SB_MAX_GROUPS];
    unsigned long long mag[SB_MAX_GROUPS];
    Py_ssize_t nf, bound;
    double ts;
    int add_key;
} sb_fields;

/* one matched device row → SB_PASS, SB_BUILT (f says what to pack) or
 * SB_LEFT, in do_fields' order: skip-empty, zero fields, Types, the
 * Time_Key lookup; then the record's shape */
static int sb_decide(const sb_desc *d, const uint8_t *row,
                     Py_ssize_t row_len, const int32_t *sp,
                     const uint8_t *rec, Py_ssize_t rec_len, sb_fields *f) {
    f->nf = 0;
    f->bound = 18 + d->pair_len + 5 + row_len;
    for (Py_ssize_t g = 0; g < d->n_groups; g++) {
        int32_t s = sp[2 * g], e = sp[2 * g + 1];
        f->present[g] = f->as_int[g] = 0;
        if (s < 0) continue;
        if (e < s || e > row_len) return SB_LEFT;
        if (e == s && d->skip_empty) continue;
        f->present[g] = 1;
        f->nf++;
        f->bound += 14 + d->name_len[g] + (e - s);
        if (d->casts[g]) {
            int k = sb_int(row + s, e - s, &f->neg[g], &f->mag[g]);
            if (k < 0) return SB_LEFT;
            f->as_int[g] = (uint8_t)k;
        }
    }
    if (f->nf == 0) return SB_PASS;  /* zero fields: the parse fails */
    int tg = d->time_group;
    f->ts = 0.0;
    if (tg >= 0 && f->present[tg]) {
        sb_tm tm = {1970, 1, 1, 0, 0, 0, 0, 0};
        long long epoch;
        int32_t s = sp[2 * tg], e = sp[2 * tg + 1];
        if (sb_strptime(row + s, e - s, d->fmt, d->fmt_len, &tm) < 0
                || sb_epoch(&tm, d->time_offset, &epoch) < 0)
            return SB_LEFT;  /* Python logs it and drops the field */
        if (!d->time_keep) {
            f->present[tg] = 0;
            f->nf--;
        }
        f->ts = (double)epoch;
    }
    if (rec_len < 13 || memcmp(rec, "\x92\x92\xd7\x00", 4) != 0
            || rec[12] != 0x80)
        return SB_LEFT;
    if (d->need_orig && (rec_len - 13 < d->pair_len
                         || memcmp(rec + 13, d->pair, d->pair_len) != 0))
        return SB_LEFT;
    f->add_key = d->preserve
        && !(d->key_group >= 0 && f->present[d->key_group]);
    return SB_BUILT;
}

/* [[time, {}], {the fields in group order, the kept key last}]; a time
 * of 0 keeps the record's EventTime */
static int sb_write(const sb_desc *d, const sb_fields *f,
                    const uint8_t *row, Py_ssize_t row_len,
                    const int32_t *sp, const uint8_t *rec, wr *w) {
    if (sb_room(w, f->bound) < 0) return JT_NOMEM;
    if (wr_u8(w, 0x92) < 0 || wr_u8(w, 0x92) < 0) return JT_NOMEM;
    if (f->ts != 0.0 ? wr_pack_f64(w, f->ts) != 0
            : wr_bytes(w, rec + 2, 10) < 0)
        return JT_NOMEM;
    if (wr_u8(w, 0x80) < 0
            || pack_header(w, f->nf + f->add_key, 0x80, 0xDE, 0xDF, 16) < 0)
        return JT_NOMEM;
    for (Py_ssize_t g = 0; g < d->n_groups; g++) {
        if (!f->present[g]) continue;
        int32_t s = sp[2 * g], e = sp[2 * g + 1];
        if (wr_pack_str(w, d->names[g], d->name_len[g]) < 0)
            return JT_NOMEM;
        if (f->as_int[g] ? wr_pack_int(w, f->neg[g], f->mag[g]) != 0
                : wr_pack_str(w, row + s, e - s) < 0)
            return JT_NOMEM;
    }
    if (f->add_key && (wr_bytes(w, d->pair + 1, d->pair_len - 1) < 0
                       || wr_pack_str(w, row, row_len) < 0))
        return JT_NOMEM;
    return 0;
}

/* the chunk: every record in order, under the GIL released */
static int sb_chunk(const sb_desc *d, const uint8_t *data,
                    Py_ssize_t data_len, const int64_t *offs, Py_ssize_t n,
                    const Py_buffer *planes, Py_ssize_t n_planes,
                    const int32_t *lens, const uint8_t *ok,
                    const int32_t *spans, wr *w, sb_left *left,
                    Py_ssize_t *n_left, Py_ssize_t *n_native,
                    Py_ssize_t *n_host) {
    sb_fields f;
    if (offs[0] < 0 || offs[n] > data_len) return JT_FALLBACK;
    for (Py_ssize_t i = 0; i < n; i++)
        if (offs[i] > offs[i + 1]) return JT_FALLBACK;
    if (sb_room(w, data_len + data_len / 4 + 4096) < 0) return JT_NOMEM;
    Py_ssize_t kept_from = 0, i = 0;
    for (Py_ssize_t p = 0; p < n_planes; p++) {
        const uint8_t *plane = planes[p].buf;
        Py_ssize_t cnt = planes[p].shape[0], width = planes[p].shape[1];
        for (Py_ssize_t r = 0; r < cnt; r++, i++) {
            const uint8_t *row = plane + r * width;
            const int32_t *sp = spans + i * 2 * d->n_groups;
            Py_ssize_t row_len = lens[i];
            int host = row_len < 0, rc = SB_LEFT;
            if (row_len > width) return JT_FALLBACK;
            for (Py_ssize_t k = 0; !host && k < row_len; k++)
                host = row[k] >= 0x80;
            if (host) {
                ++*n_host;
            } else if (!ok[i]) {
                continue;
            } else {
                rc = sb_decide(d, row, row_len, sp, data + offs[i],
                               offs[i + 1] - offs[i], &f);
                if (rc == SB_PASS) continue;
            }
            /* the records before this one pass through as one run */
            Py_ssize_t run = offs[i] - offs[kept_from];
            if (sb_room(w, run) < 0
                    || wr_bytes(w, data + offs[kept_from], run) < 0)
                return JT_NOMEM;
            kept_from = i + 1;
            if (rc == SB_LEFT) {
                left[*n_left].row = i;
                left[*n_left].pos = w->len;
                left[(*n_left)++].host = host;
                continue;
            }
            if (sb_write(d, &f, row, row_len, sp, data + offs[i], w) < 0)
                return JT_NOMEM;
            ++*n_native;
        }
    }
    Py_ssize_t tail = offs[n] - offs[kept_from];
    if (sb_room(w, tail) < 0
            || wr_bytes(w, data + offs[kept_from], tail) < 0)
        return JT_NOMEM;
    return 0;
}

static int sb_view(PyObject *o, Py_buffer *v, int ndim, Py_ssize_t item) {
    if (PyObject_GetBuffer(o, v, PyBUF_ND | PyBUF_FORMAT) < 0) return -1;
    if (v->ndim == ndim && v->itemsize == item) return 0;
    PyBuffer_Release(v);
    PyErr_SetString(g_fallback, "parser_spans_build: array of another shape");
    return -1;
}

static PyObject *py_parser_spans_build(PyObject *self, PyObject *args) {
    PyObject *data_o, *offs_o, *planes_o, *lens_o, *ok_o, *spans_o, *names;
    PyObject *res = NULL;
    Py_buffer data, offs, lens, ok, spans, *planes = NULL;
    Py_ssize_t casts_len, n_planes, got_planes = 0, n = 0, rows = 0;
    Py_ssize_t n_left = 0, n_native = 0, n_host = 0;
    int views = 0, rc = JT_NOMEM;  /* views: those taken, in order */
    wr w = {NULL, 0, 0, 0};
    sb_left *left = NULL;
    sb_desc d;
    if (!PyArg_ParseTuple(args, "OOO!OOOO!y#ipy#Lpppy#i", &data_o, &offs_o,
                          &PyList_Type, &planes_o, &lens_o, &ok_o,
                          &spans_o, &PyTuple_Type, &names, &d.casts,
                          &casts_len, &d.time_group, &d.time_keep, &d.fmt,
                          &d.fmt_len, &d.time_offset, &d.skip_empty,
                          &d.need_orig, &d.preserve, &d.pair, &d.pair_len,
                          &d.key_group))
        return NULL;
    d.n_groups = PyTuple_GET_SIZE(names);
    if (d.n_groups < 1 || d.n_groups > SB_MAX_GROUPS
            || casts_len != d.n_groups || d.pair_len < 1
            || d.time_group < -1 || d.time_group >= d.n_groups
            || d.key_group < -1 || d.key_group >= d.n_groups) {
        PyErr_SetString(g_fallback, "parser_spans_build: bad description");
        return NULL;
    }
    for (Py_ssize_t g = 0; g < d.n_groups; g++) {
        char *s;
        if (PyBytes_AsStringAndSize(PyTuple_GET_ITEM(names, g), &s,
                                    &d.name_len[g]) < 0)
            return NULL;
        d.names[g] = (const uint8_t *)s;
    }
    n_planes = PyList_GET_SIZE(planes_o);
    planes = PyMem_Calloc((size_t)n_planes + 1, sizeof *planes);
    if (!planes) return PyErr_NoMemory();
    if (PyObject_GetBuffer(data_o, &data, PyBUF_SIMPLE) < 0) goto done;
    views++;
    if (sb_view(offs_o, &offs, 1, 8) < 0) goto done;
    views++;
    if (sb_view(lens_o, &lens, 1, 4) < 0) goto done;
    views++;
    if (sb_view(ok_o, &ok, 1, 1) < 0) goto done;
    views++;
    if (sb_view(spans_o, &spans, 3, 4) < 0) goto done;
    views++;
    n = lens.shape[0];
    for (; got_planes < n_planes; got_planes++) {
        if (sb_view(PyList_GET_ITEM(planes_o, got_planes),
                    &planes[got_planes], 2, 1) < 0)
            goto done;
        rows += planes[got_planes].shape[0];
    }
    if (offs.shape[0] != n + 1 || ok.shape[0] != n || rows != n
            || spans.shape[0] != n || spans.shape[1] != d.n_groups
            || spans.shape[2] != 2) {
        PyErr_SetString(g_fallback, "parser_spans_build: the arrays "
                                    "do not describe one chunk");
        goto done;
    }
    /* from here to the result: the views and malloc'ed memory alone */
    Py_BEGIN_ALLOW_THREADS
    left = malloc(sizeof *left * (size_t)(n + 1));
    if (left)
        rc = sb_chunk(&d, data.buf, data.len, offs.buf, n, planes,
                      n_planes, lens.buf, ok.buf, spans.buf, &w, left,
                      &n_left, &n_native, &n_host);
    Py_END_ALLOW_THREADS
    if (rc == JT_FALLBACK) {
        PyErr_SetString(g_fallback, "parser_spans_build: offsets or "
                                    "lengths outside the chunk");
    } else if (rc < 0) {
        PyErr_NoMemory();
    } else {
        PyObject *lst = PyList_New(n_left);
        for (Py_ssize_t k = 0; lst && k < n_left; k++) {
            PyObject *t = Py_BuildValue("(nnO)", left[k].row, left[k].pos,
                                        left[k].host ? Py_True : Py_False);
            if (!t) Py_CLEAR(lst);
            else PyList_SET_ITEM(lst, k, t);
        }
        if (lst)
            res = Py_BuildValue("(y#Nnn)", w.buf ? (const char *)w.buf : "",
                                w.len, lst, n_native, n_host);
    }
done:
    free(left);
    free(w.buf);
    for (Py_ssize_t k = 0; k < got_planes; k++)
        PyBuffer_Release(&planes[k]);
    PyMem_Free(planes);
    if (views > 4) PyBuffer_Release(&spans);
    if (views > 3) PyBuffer_Release(&ok);
    if (views > 2) PyBuffer_Release(&lens);
    if (views > 1) PyBuffer_Release(&offs);
    if (views > 0) PyBuffer_Release(&data);
    return res;
}

/* ------------------------------------------------------------------ */
/* in_forward's chunk cut — a Forward or PackedForward message straight
 * to the V2 event buffer, with no Python object for any entry.
 *
 * The object path (unpack_from → net_forward._decode →
 * _entries_to_events → pack_event) decodes every [time, record] entry
 * and packs it again as [[time, {}], record]. Where the wire's bytes
 * are canonical — what decode → pack_obj would write back, which
 * mp_canonical proves — the event is the entry's own bytes with two
 * put in: 0x92 0x92 <time> 0x80 <record>. Everything up to the result
 * tuple touches the caller's buffer and one malloc'ed output alone, so
 * it runs with the GIL released: the device lane's thread dispatches
 * while the loop cuts. Whatever is not proven — another message shape,
 * an entry that is not a fixarray of [time, map], a time that is not an
 * int, a float64 or an EventTime (the object path puts the clock in
 * nil's place), non-canonical bytes, a torn blob — is FallbackError for
 * the whole message, and the object path, the reference this cut is
 * held to, runs as it always did. */

#define CUT_OK       0
#define CUT_MORE     1   /* the buffer does not hold the message whole */
#define CUT_FALLBACK 2   /* the Python walk decides */
#define CUT_NOMEM    3

/* below this many bytes a walk is shorter than a hand-over of the GIL */
#define CUT_NOGIL_MIN 4096

/* the most the events of `span` bytes of entries can take: an entry is
 * three bytes or more, its event two bytes longer */
static size_t cut_capacity(size_t span) { return span + 2 * (span / 3) + 2; }

static int is_map_hdr(uint8_t b) {
    return (b >= 0x80 && b <= 0x8F) || b == 0xDE || b == 0xDF;
}

/* entries [time, map] from p: `want` of them (an array's), or as many
 * as end at `end` exactly (a bin's stream: want < 0). The events go to
 * `out`, which holds cut_capacity(end - p); *nx is where the walk
 * stopped. mp_canonical bounds every read by `end`. */
static int cut_entries(const uint8_t *p, const uint8_t *end, long long want,
                       uint8_t *out, size_t *out_len, long long *n_out,
                       const uint8_t **nx) {
    uint8_t *o = out;
    long long n = 0;
    while (want < 0 ? p < end : n < want) {
        if (end - p < 3 || p[0] != 0x92) return CUT_FALLBACK;
        const uint8_t *t = p + 1, *rec, *next;
        uint8_t b = *t;
        if (!(b < 0x80 || b >= 0xE0 || b == 0xCB || b == 0xD7
              || (b >= 0xCC && b <= 0xD3)))
            return CUT_FALLBACK;
        if (mp_canonical(t, end, 0, &rec) || rec >= end
                || !is_map_hdr(*rec) || mp_canonical(rec, end, 0, &next))
            return CUT_FALLBACK;
        *o++ = 0x92;
        *o++ = 0x92;
        memcpy(o, t, (size_t)(rec - t));
        o += rec - t;
        *o++ = 0x80;
        memcpy(o, rec, (size_t)(next - rec));
        o += next - rec;
        p = next;
        n++;
    }
    if (want < 0 && p != end) return CUT_FALLBACK;
    *out_len = (size_t)(o - out);
    *n_out = n;
    *nx = p;
    return CUT_OK;
}

/* the big-endian length word of a header: n bytes at p, or -1 where
 * `end` comes first */
static long long mp_be(const uint8_t *p, const uint8_t *end, int n) {
    if (end - p < n) return -1;
    long long v = 0;
    while (n--) v = (v << 8) | *p++;
    return v;
}

/* does the map at p, which ends at `end`, hold the str key `key`? */
static int map_has_key(const uint8_t *p, const uint8_t *end,
                       const char *key, long long keylen) {
    int w = *p == 0xDE ? 2 : *p == 0xDF ? 4 : 0;
    long long n = w ? mp_be(p + 1, end, w) : (*p & 0x0F), klen;
    p += 1 + w;
    for (long long i = 0; i < n; i++) {
        const uint8_t *k = mp_str_hdr(p, end, &klen);
        if (k && klen == keylen && klen <= end - k
                && memcmp(k, key, (size_t)klen) == 0)
            return 1;
        if (!(p = mp_skip_span(p, end, 0))
                || !(p = mp_skip_span(p, end, 0)))
            return 0;
    }
    return 0;
}

typedef struct {
    uint8_t first;                 /* 0x92 or 0x93: the message's header */
    const uint8_t *tag, *body, *stop, *opt, *msg_end;
    long long tag_len, want, n;
} cut_msg;

/* what follows a chunk's body at `at`: in a message of three a map that
 * ends the message (→ m->opt), in a message of two nothing */
static int cut_option(cut_msg *m, const uint8_t *at) {
    const uint8_t *end = m->msg_end;
    if (m->first == 0x92) return at == end ? CUT_OK : CUT_FALLBACK;
    if (at >= end || !is_map_hdr(*at) || mp_skip_span(at, end, 0) != end)
        return CUT_FALLBACK;
    m->opt = at;
    return CUT_OK;
}

/* the one message [str tag, array | bin, map?] at p: where it ends and
 * where its entries lie — `want` of them from `body` (Forward mode), or
 * those of the bin [body, stop) with `opt` found already (want < 0),
 * which stays uncut (n = -1) where the option names a compression */
static int cut_shape(const uint8_t *p, const uint8_t *end, cut_msg *m) {
    if (p >= end) return CUT_MORE;
    m->first = *p;
    if (m->first != 0x92 && m->first != 0x93) return CUT_FALLBACK;
    /* told before the walk: HELO, PING, an ack, an entry of a stream */
    if (end - p >= 2 && !((p[1] >= 0xA0 && p[1] <= 0xBF)
                          || (p[1] >= 0xD9 && p[1] <= 0xDB)))
        return CUT_FALLBACK;
    int bad = 0;
    /* depth 1, as unpack_from walks: the same nesting is refused */
    m->msg_end = mp_walk(p, end, 1, &bad);
    if (!m->msg_end) return bad ? CUT_FALLBACK : CUT_MORE;
    end = m->msg_end;  /* nothing below reads past the walked span */
    m->tag = mp_str_hdr(p + 1, end, &m->tag_len);
    if (!m->tag || m->tag_len >= end - m->tag) return CUT_FALLBACK;
    /* the body: an array of entries (Forward mode) or a bin of them */
    const uint8_t *q = m->tag + m->tag_len;
    uint8_t b = *q;
    int w = b == 0xC4 ? 1 : (b == 0xC5 || b == 0xDC) ? 2
          : (b == 0xC6 || b == 0xDD) ? 4 : 0;
    if (!w && (b < 0x90 || b > 0x9F)) return CUT_FALLBACK;  /* Message mode */
    long long len = w ? mp_be(q + 1, end, w) : (b & 0x0F);
    if (len < 0) return CUT_FALLBACK;
    m->body = q + 1 + w;
    if (b < 0xC4 || b > 0xC6) {
        m->want = len;
        m->stop = end;  /* the array ends where its entries do */
        return CUT_OK;
    }
    m->want = -1;
    if (len > end - m->body) return CUT_FALLBACK;
    m->stop = m->body + len;
    if (cut_option(m, m->stop)) return CUT_FALLBACK;
    if (m->opt && map_has_key(m->opt, end, "compressed", 10)) m->n = -1;
    return CUT_OK;
}

/* forward_cut(buf, pos) — unpack_from's contract (None while the
 * message at pos is not whole, FallbackError where the Python walk must
 * decide) for a chunk-shaped Forward message, with the entries never
 * built: → (tag, events, n, option, end). `n` is counted, whatever a
 * `size` option says; `option` is the decoded map or None. A bin whose
 * option map has a `compressed` key comes back uncut, as `events` with
 * n = -1: the caller inflates it and calls forward_cut_entries. */
static PyObject *py_forward_cut(PyObject *self, PyObject *args) {
    Py_buffer view;
    Py_ssize_t pos;
    if (!PyArg_ParseTuple(args, "y*n", &view, &pos)) return NULL;
    if (pos < 0 || pos > view.len) {
        PyBuffer_Release(&view);
        PyErr_SetString(PyExc_ValueError, "forward_cut: pos out of range");
        return NULL;
    }
    const uint8_t *base = (const uint8_t *)view.buf, *after;
    cut_msg m;
    memset(&m, 0, sizeof m);
    uint8_t *out = NULL;
    size_t out_len = 0;
    /* from here to the result objects: the view and `out` alone */
    PyThreadState *unlocked = view.len - pos >= CUT_NOGIL_MIN
        ? PyEval_SaveThread() : NULL;
    int rc = cut_shape(base + pos, base + view.len, &m);
    if (rc == CUT_OK && m.n >= 0) {
        out = malloc(cut_capacity((size_t)(m.stop - m.body)));
        rc = out ? cut_entries(m.body, m.stop, m.want, out, &out_len, &m.n,
                               &after) : CUT_NOMEM;
        if (rc == CUT_OK && m.want >= 0) rc = cut_option(&m, after);
    }
    if (unlocked) PyEval_RestoreThread(unlocked);
    PyObject *res = NULL, *tag = NULL, *events = NULL, *option = NULL;
    if (rc == CUT_MORE) {
        res = Py_None;
        Py_INCREF(res);
    } else if (rc == CUT_FALLBACK) {
        PyErr_SetString(g_fallback, "not a chunk of canonical entries");
    } else if (rc == CUT_NOMEM) {
        PyErr_NoMemory();
    } else {
        tag = PyUnicode_DecodeUTF8((const char *)m.tag, m.tag_len, "replace");
        events = m.n < 0
            ? PyBytes_FromStringAndSize((const char *)m.body, m.stop - m.body)
            : PyBytes_FromStringAndSize((const char *)out, out_len);
        if (m.opt) {
            rd r = {m.opt, m.msg_end, 0};
            option = decode_obj(&r);  /* a handful of objects */
            if (!option && PyErr_ExceptionMatches(g_truncated)) {
                PyErr_Clear();  /* the walk and the decoder parted */
                PyErr_SetString(g_fallback, "span walk and decode disagree");
            }
        } else {
            option = Py_None;
            Py_INCREF(option);
        }
        if (tag && events && option)
            res = Py_BuildValue("(OOLOn)", tag, events, m.n, option,
                                (Py_ssize_t)(m.msg_end - base));
        Py_XDECREF(tag);
        Py_XDECREF(events);
        Py_XDECREF(option);
    }
    free(out);
    PyBuffer_Release(&view);
    return res;
}

/* forward_cut_entries(buf) → (events, n): the entry walk alone over a
 * concatenated stream of [time, record] entries — what a compressed
 * PackedForward blob inflates to. FallbackError as above. */
static PyObject *py_forward_cut_entries(PyObject *self, PyObject *arg) {
    Py_buffer view;
    if (PyObject_GetBuffer(arg, &view, PyBUF_SIMPLE) < 0) return NULL;
    const uint8_t *base = (const uint8_t *)view.buf, *nx;
    uint8_t *out;
    size_t out_len = 0;
    long long n = 0;
    int rc = CUT_NOMEM;
    Py_BEGIN_ALLOW_THREADS
    out = malloc(cut_capacity((size_t)view.len));
    if (out)
        rc = cut_entries(base, base + view.len, -1, out, &out_len, &n, &nx);
    Py_END_ALLOW_THREADS
    PyObject *res = NULL;
    if (rc == CUT_FALLBACK)
        PyErr_SetString(g_fallback, "not a stream of canonical entries");
    else if (rc == CUT_NOMEM)
        PyErr_NoMemory();
    else
        res = Py_BuildValue("(y#L)", (const char *)out, (Py_ssize_t)out_len,
                            n);
    free(out);
    PyBuffer_Release(&view);
    return res;
}

/* unpack_from(buf, pos) — the streaming Unpacker's fast path
 * (codec/msgpack.Unpacker.__next__). The span walk comes first and
 * builds nothing: a message the buffer does not hold whole yet costs
 * one pass over its bytes and returns None, where the Python walk
 * built and threw away every object up to the tear, once per read.
 * A whole message is decoded once, over exactly its span, so no
 * array32/map32 header can make PyList_New ask for more entries than
 * the message has bytes. What decode_obj cannot reproduce bit for bit
 * (non-EventTime ext), and what no further byte can complete (0xC1,
 * nesting at decode_obj's bound), raises FallbackError: the caller
 * runs the Python walk, which decodes or raises as it always did. */
static PyObject *py_unpack_from(PyObject *self, PyObject *args) {
    Py_buffer view;
    Py_ssize_t pos;
    if (!PyArg_ParseTuple(args, "y*n", &view, &pos)) return NULL;
    if (pos < 0 || pos > view.len) {
        PyBuffer_Release(&view);
        PyErr_SetString(PyExc_ValueError, "unpack_from: pos out of range");
        return NULL;
    }
    const uint8_t *base = (const uint8_t *)view.buf;
    const uint8_t *end = base + view.len;
    int bad = 0;
    /* depth 1: the walk then refuses exactly the nesting decode_obj
     * would (walk depth d + 1 > MAX_DEPTH <=> decode depth d >= MAX) */
    const uint8_t *msg_end = mp_walk(base + pos, end, 1, &bad);
    if (!msg_end) {
        PyBuffer_Release(&view);
        if (bad) {
            PyErr_SetString(g_fallback, "malformed or too deeply nested");
            return NULL;
        }
        Py_RETURN_NONE;
    }
    rd r = {base + pos, msg_end, 0};
    PyObject *obj = decode_obj(&r);
    if (obj ? r.p != msg_end : PyErr_ExceptionMatches(g_truncated)) {
        /* the walk and the decoder are twins; should they ever part,
         * the Python walk decides and nothing is mis-positioned */
        Py_CLEAR(obj);
        PyErr_Clear();
        PyErr_SetString(g_fallback, "span walk and decode disagree");
    }
    Py_ssize_t at = msg_end - base;
    PyBuffer_Release(&view);
    if (!obj) return NULL;
    return Py_BuildValue("(Nn)", obj, at);
}

static PyObject *py_init(PyObject *self, PyObject *args) {
    PyObject *logevent, *eventtime;
    if (!PyArg_ParseTuple(args, "OO", &logevent, &eventtime)) return NULL;
    Py_XINCREF(logevent);
    Py_XINCREF(eventtime);
    Py_XSETREF(g_logevent, logevent);
    Py_XSETREF(g_eventtime, eventtime);
    Py_RETURN_NONE;
}

static PyMethodDef methods[] = {
    {"decode_events", py_decode_events, METH_O,
     "decode a concatenated log-event msgpack buffer → list[LogEvent]"},
    {"pack_event", py_pack_event, METH_VARARGS,
     "pack_event(ts, meta, body) → V2 log-event msgpack bytes"},
    {"parser_json_batch", py_parser_json_batch, METH_VARARGS,
     "parser_json_batch(buf, key) → (out, n_records, n_parsed): "
     "whole-chunk JSON field transcode (filter_parser fast path); "
     "raises FallbackError when the per-record path must run"},
    {"parser_spans_build", py_parser_spans_build, METH_VARARGS,
     "parser_spans_build(data, offsets, planes, lengths, ok, spans, "
     "*description) → (out, leftovers, native_rows, host_rows): a "
     "chunk's records built from the device's spans with the GIL "
     "released (filter_parser); raises FallbackError"},
    {"unpack_from", py_unpack_from, METH_VARARGS,
     "unpack_from(buf, pos) → (obj, end) for the one msgpack object "
     "at pos, None while the buffer does not hold it whole; raises "
     "FallbackError when the Python walk must decide"},
    {"forward_cut", py_forward_cut, METH_VARARGS,
     "forward_cut(buf, pos) → (tag, events, n, option, end) for the "
     "Forward / PackedForward chunk at pos, its entries cut to V2 "
     "events with no object built; None and FallbackError as "
     "unpack_from"},
    {"forward_cut_entries", py_forward_cut_entries, METH_O,
     "forward_cut_entries(buf) → (events, n) for a concatenated "
     "stream of [time, record] entries; raises FallbackError"},
    {"_init", py_init, METH_VARARGS,
     "register the LogEvent and EventTime classes"},
    {NULL, NULL, 0, NULL},
};

static struct PyModuleDef moduledef = {
    PyModuleDef_HEAD_INIT, "fbtpu_codec",
    "C msgpack log-event decoder", -1, methods,
};

PyMODINIT_FUNC PyInit_fbtpu_codec(void) {
    PyObject *m = PyModule_Create(&moduledef);
    if (!m) return NULL;
    g_fallback = PyErr_NewException("fbtpu_codec.FallbackError",
                                    PyExc_ValueError, NULL);
    if (!g_fallback || PyModule_AddObject(m, "FallbackError",
                                          g_fallback) < 0) {
        Py_XDECREF(g_fallback);
        Py_DECREF(m);
        return NULL;
    }
    Py_INCREF(g_fallback);  /* module owns one, we keep one */
    g_truncated = PyErr_NewException("fbtpu_codec.TruncatedError",
                                     PyExc_ValueError, NULL);
    if (!g_truncated || PyModule_AddObject(m, "TruncatedError",
                                           g_truncated) < 0) {
        Py_XDECREF(g_truncated);
        Py_DECREF(m);
        return NULL;
    }
    Py_INCREF(g_truncated);
    return m;
}
