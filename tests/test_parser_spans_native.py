"""filter_parser's record build from spans in C (fbtpu_codec
``parser_spans_build``), held to the per-record host chain
(``tpu.enable off``) byte for byte, with the rows C built counted
(``native_rows``): what C proves it builds, what it leaves to the Python
build (``_span_event``) at its place, and the parsers whose description
keeps the Python build whole.

The platform gate is forced open as in ``tests/test_parser_spans.py``,
whose helpers these cases share. Records carry an EventTime and empty
metadata, as ``in_forward``'s cut writes them, unless a case is about
another framing.
"""

import numpy as np
import pytest

pytest.importorskip("jax")

from fluentbit_tpu.codec import _native_codec  # noqa: E402
from fluentbit_tpu.codec.events import decode_events, encode_event  # noqa: E402
from fluentbit_tpu.codec.msgpack import EventTime, packb  # noqa: E402
from fluentbit_tpu.core.chunk_batch import RawChunk  # noqa: E402
from fluentbit_tpu.plugins.filter_parser import _time_ops  # noqa: E402

from test_parser_spans import (ACCESS, access, gate_open,  # noqa: E402,F401
                               host_chain, parser_engine)

#: a parser whose integer and time captures can hold anything but "|"
PIPE = {"Regex": r"^(?<a>[^|]*)\|(?<n>[^|]*)\|(?<time>[^|]*)$",
        "Types": "n:integer"}
TIME = "10/Oct/2000:13:55:36 -0700"
N_PLAIN = 60  # plain rows around the case's rows; C builds every one


def event_chunk(records, meta=None) -> bytes:
    return b"".join(encode_event(r, EventTime(1700000000 + i, 5), meta)
                    for i, r in enumerate(records))


def pipe_line(i: int, n: str = "7", time: str = TIME) -> str:
    return f"row{i}|{n}|{time}"


def differential(data: bytes, n: int, props=None, parser_props=None):
    """process_batch on the device path == the host chain; → plugin."""
    want = host_chain(data, props, parser_props)
    _e, plugin = parser_engine(props, parser_props)
    got_n, out, n_in = plugin.process_batch(RawChunk(data, "t", n))
    assert (got_n, n_in) == (n, n)
    assert out == want
    return plugin


def with_case(case_lines):
    """N_PLAIN plain pipe rows with the case's rows at 3, 30, ..."""
    lines = [pipe_line(i) for i in range(N_PLAIN)]
    for k, line in enumerate(case_lines):
        lines.insert(3 + 27 * k, line)
    return [{"log": v} for v in lines]


#: (n capture, time capture, built in C?) through PIPE
CAPTURES = {
    "size_dash_stays_a_string": ("-", TIME, True),
    "plus_sign_is_an_integer": ("+12", TIME, True),
    "minus_zero": ("-0", TIME, True),
    "past_int64_packs_as_uint64": ("9223372036854775808", TIME, True),
    "uint64_max": ("18446744073709551615", TIME, True),
    "int64_min": ("-9223372036854775808", TIME, True),
    "leading_zeros": ("007", TIME, True),
    "no_digit_no_dot_stays_a_string": ("inf", TIME, True),
    "leading_space_left_over": (" 12", TIME, False),
    "underscore_left_over": ("1_000", TIME, False),
    "decimal_point_left_over": ("12.5", TIME, False),
    "digits_and_letters_left_over": ("12a", TIME, False),
    "empty_time_is_skipped": ("5", "", True),
    "epoch_keeps_the_event_time": ("5", "01/Jan/1970:00:00:00 +0000", True),
    "offset_plus_0530": ("5", "10/Oct/2000:13:55:36 +0530", True),
    "offset_minus_0000": ("5", "10/Oct/2000:13:55:36 -0000", True),
    "offset_with_colon": ("5", "10/Oct/2000:13:55:36 +05:30", True),
    "offset_one_digit_hour": ("5", "10/Oct/2000:13:55:36 +5", True),
    "zone_Z": ("5", "10/Oct/2000:13:55:36 Z", True),
    "month_lower_case": ("5", "10/oct/2000:13:55:36 -0700", True),
    "month_upper_case": ("5", "10/OCT/2000:13:55:36 -0700", True),
    "month_full_name": ("5", "10/October/2000:13:55:36 -0700", True),
    "one_digit_day": ("5", "1/Oct/2000:13:55:36 -0700", True),
    "leap_second": ("5", "31/Dec/2016:23:59:60 +0000", True),
    "february_29": ("5", "29/Feb/2024:12:00:00 +0000", True),
    "day_31_of_a_30_day_month": ("5", "31/Nov/2000:00:00:00 +0000", True),
    "trailing_bytes_after_the_zone": ("5", TIME + " junk", True),
    "day_32_left_over": ("5", "32/Oct/2000:13:55:36 -0700", False),
    "hour_25_left_over": ("5", "10/Oct/2000:25:55:36 -0700", False),
    "no_zone_left_over": ("5", "10/Oct/2000:13:55:36", False),
    "unknown_month_left_over": ("5", "10/Xyz/2000:13:55:36 -0700", False),
}


@pytest.mark.parametrize("name", sorted(CAPTURES))
def test_capture_shapes(gate_open, name):
    n, time, native = CAPTURES[name]
    records = with_case([pipe_line(900, n, time), pipe_line(901, n, time)])
    plugin = differential(event_chunk(records), len(records),
                          parser_props=PIPE)
    tm = plugin.raw_timings
    assert plugin.decision()["build"] == "native"
    assert tm["parsed"] == len(records)
    assert tm["native_rows"] == N_PLAIN + (2 if native else 0)


#: parser and filter options → the rows of ``with_case``'s that C leaves
#: over
OPTIONS = {
    # the empty time capture is kept and does not parse
    "keep_empty_values": ({}, {"Skip_Empty_Values": "off"}, 1),
    "time_keep": ({"reserve_data": "on"}, {"Time_Keep": "on"}, 0),
    "preserve_key": ({"preserve_key": "on"}, {}, 0),
    "reserve_and_preserve": ({"reserve_data": "on",
                              "preserve_key": "on"}, {}, 0),
    "time_offset_without_zone": ({}, {"Time_Format": "%d/%b/%Y:%H:%M:%S",
                                      "Time_Offset": "+0200"}, 0),
    "percent_T": ({}, {"Time_Format": "%d/%b/%Y:%T %z"}, 0),
    "white_space_run": ({}, {"Time_Format": "%d/%b/%Y:%H:%M:%S  %z"}, 0),
    "no_time_format": ({"reserve_data": "on"}, {"Time_Format": ""}, 0),
    # every time but the empty one (skipped) fails
    "numeric_month_fails_every_row": ({}, {"Time_Format": "%Y-%m-%d"},
                                      N_PLAIN + 1),
}


@pytest.mark.parametrize("name", sorted(OPTIONS))
def test_options(gate_open, name):
    props, parser_props, left = OPTIONS[name]
    records = with_case([pipe_line(900, "", TIME), pipe_line(901, "3", "")])
    plugin = differential(event_chunk(records), len(records), props,
                          {**PIPE, "Time_Key": "time",
                           "Time_Format": "%d/%b/%Y:%H:%M:%S %z",
                           **parser_props})
    tm = plugin.raw_timings
    assert plugin.decision()["build"] == "native"
    assert tm["native_rows"] == len(records) - left


def test_the_key_named_as_a_group_is_not_added_twice(gate_open):
    pp = {"Regex": r"^(?<log>[^|]*)\|(?<n>[^|]*)\|(?<time>[^|]*)$",
          "Types": "n:integer"}
    records = with_case([pipe_line(900, "", TIME)])
    plugin = differential(event_chunk(records), len(records),
                          {"preserve_key": "on"}, pp)
    assert plugin.raw_timings["native_rows"] == len(records)


#: records of another framing or body: every one a leftover
FRAMINGS = {
    "extra_keys_under_reserve_data": lambda i, v: encode_event(
        {"log": v, "other": i}, EventTime(1700000000 + i, 5)),
    "metadata_not_empty": lambda i, v: encode_event(
        {"log": v}, EventTime(1700000000 + i, 5), {"m": 1}),
    "legacy_ts_map": lambda i, v: packb([1700000000 + i, {"log": v}]),
    "float_time": lambda i, v: encode_event({"log": v}, 1700000000.5 + i),
}


@pytest.mark.parametrize("name", sorted(FRAMINGS))
def test_other_framings_are_left_to_python(gate_open, name):
    make = FRAMINGS[name]
    plain = [access(i) for i in range(40)]
    data = b"".join(
        make(i, v) if i % 10 == 3 else encode_event(
            {"log": v}, EventTime(1700000000 + i, 5))
        for i, v in enumerate(plain))
    plugin = differential(data, 40, {"reserve_data": "on"})
    tm = plugin.raw_timings
    assert tm["parsed"] == 40 and tm["native_rows"] == 36


def test_a_chunk_of_leftovers_only(gate_open):
    data = b"".join(encode_event({"log": access(i)}, 1700000000.0 + i)
                    for i in range(70))
    plugin = differential(data, 70, {"reserve_data": "on"})
    tm = plugin.raw_timings
    assert tm["native_rows"] == 0 and tm["parsed"] == 70


def test_host_rows_among_native_ones(gate_open):
    """Overflow rows, a bin value, a byte past ASCII and a missing key
    are leftovers (host rows) spliced between rows C built."""
    records = []
    for i in range(90):
        if i % 9 == 4:
            records.append({"log": access(i, "x" * 600)})
        elif i % 9 == 5:
            records.append({"log": access(i, " é")})
        elif i % 9 == 6:
            records.append({"log": access(i).encode()})
        elif i % 9 == 7:
            records.append({"nolog": i})
        elif i % 9 == 8:
            records.append({"log": f"kernel: oom {i}"})
        else:
            records.append({"log": access(i)})
    plugin = differential(event_chunk(records), 90, {"reserve_data": "on"})
    tm = plugin.raw_timings
    # the bin values parse; the overflow and é lines, whose tails follow
    # the agent's closing quote, do not
    assert tm["host_rows"] == 40 and tm["native_rows"] == 40
    assert tm["parsed"] == 50


#: parser descriptions C does not serve: the Python build whole
PYTHON_BUILD = {
    "float_type": ({**PIPE, "Types": "n:float"}, "Types n"),
    "fraction_directive": (
        {**PIPE, "Time_Format": "%Y-%m-%dT%H:%M:%S.%L"}, "%L"),
    "no_year": ({**PIPE, "Time_Format": "%b %d %H:%M:%S"}, "no year"),
    "type_on_the_time_key": ({**PIPE, "Types": "time:integer n:integer"},
                             "Time_Key"),
}


@pytest.mark.parametrize("name", sorted(PYTHON_BUILD))
def test_descriptions_outside_the_set_take_the_python_build(gate_open,
                                                            name):
    pp, reason = PYTHON_BUILD[name]
    records = with_case([pipe_line(900, "1.5", "2000-10-10T13:55:36.25"),
                         pipe_line(901, "12", "Oct 10 13:55:36")])
    plugin = differential(event_chunk(records), len(records),
                          parser_props={"Time_Key": "time", **pp})
    d = plugin.decision()
    assert d["build"] == "python" and reason in d["build_decline"]
    assert plugin.raw_timings["native_rows"] == 0
    assert plugin.raw_timings["parsed"] == len(records)


def test_an_extension_without_the_function_serves_as_before(gate_open,
                                                            monkeypatch):
    real = _native_codec.load()

    class Older:
        def __getattr__(self, name):
            if name == "parser_spans_build":
                raise AttributeError(name)
            return getattr(real, name)

    monkeypatch.setattr(_native_codec, "load", lambda: Older())
    records = [{"log": access(i)} for i in range(70)]
    plugin = differential(event_chunk(records), 70, {"reserve_data": "on"})
    d = plugin.decision()
    assert d["build"] == "python" and "parser_spans_build" in \
        d["build_decline"]
    assert plugin.raw_timings["native_rows"] == 0
    assert plugin.raw_timings["parsed"] == 70


@pytest.mark.parametrize("fmt,ops", [
    ("%d/%b/%Y:%H:%M:%S %z", b"dL/bL/YL:HL:ML:SWz"),
    ("%Y-%m-%dT%T", b"YL-mL-dLTHL:ML:S"),
    ("%Y %h %e", "Time_Format directive %e is outside the C build's set"),
    ("%H:%M", "Time_Format has no year (time_lookup prepends this one)"),
    ("%Y%", "Time_Format directive % is outside the C build's set"),
])
def test_time_format_compiles_to_ops_or_says_why(fmt, ops):
    assert _time_ops(fmt) == ops


# ----------------------------------------- the C function's own guards


def _spans_args(n_rows=3, width=16):
    """A chunk of matched rows and its verdict, built by hand."""
    values = [b"a|1|" + b"x" * (i % 5) for i in range(n_rows)]
    data = b"".join(encode_event({"log": v.decode()},
                                 EventTime(1, 0)) for v in values)
    offs = [0]
    for v in values:
        offs.append(offs[-1] + len(encode_event({"log": v.decode()},
                                                EventTime(1, 0))))
    plane = np.zeros((n_rows, width), dtype=np.uint8)
    lengths = np.zeros(n_rows, dtype=np.int32)
    spans = np.full((n_rows, 2, 2), -1, dtype=np.int32)
    for i, v in enumerate(values):
        plane[i, :len(v)] = np.frombuffer(v, dtype=np.uint8)
        lengths[i] = len(v)
        spans[i] = [(0, 1), (2, 3)]
    desc = ((b"a", b"n"), b"\x00\x01", -1, False, b"", 0, True, False,
            False, b"\x81\xa3log", -1)
    return [data, np.array(offs, dtype=np.int64), [plane], lengths,
            np.ones(n_rows, dtype=bool), spans, *desc]


@pytest.mark.parametrize("breakage", [
    "offset_past_the_buffer", "offsets_backwards", "length_past_the_row",
    "rows_short_of_the_offsets", "spans_of_another_width",
    "time_group_out_of_range"])
def test_parser_spans_build_refuses_what_does_not_describe_the_chunk(
        breakage):
    mod = _native_codec.load()
    if mod is None or not hasattr(mod, "parser_spans_build"):
        pytest.skip("codec extension unavailable")
    args = _spans_args()
    out, left, native_rows, host_rows = mod.parser_spans_build(*args)
    assert (native_rows, host_rows, left) == (3, 0, [])
    assert [ev.body for ev in decode_events(out)] == [{"a": "a", "n": 1}] * 3
    if breakage == "offset_past_the_buffer":
        args[1][-1] += 1
    elif breakage == "offsets_backwards":
        args[1][1], args[1][2] = args[1][2], args[1][1]
    elif breakage == "length_past_the_row":
        args[3][1] = 17
    elif breakage == "rows_short_of_the_offsets":
        args[2] = [args[2][0][:2]]
    elif breakage == "spans_of_another_width":
        args[5] = np.zeros((3, 3, 2), dtype=np.int32)
    else:
        args[8] = 2
    with pytest.raises(mod.FallbackError):
        mod.parser_spans_build(*args)


def test_parser_spans_build_over_an_empty_chunk():
    mod = _native_codec.load()
    if mod is None or not hasattr(mod, "parser_spans_build"):
        pytest.skip("codec extension unavailable")
    args = _spans_args(0)
    assert mod.parser_spans_build(*args) == (b"", [], 0, 0)
