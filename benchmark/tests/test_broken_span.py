"""A whole run of ``parser-apache2.catchup``, the look for a chip skipped
(``--rehearse``, CPU) and the platform gate forced open so that the
records are built from the span program's offsets (on the CPU backend,
through the lane, as tier-1 forces it): with one span of one record off
by one byte underneath, ``correct`` has to come out false — the
``exactness`` guarantee the configuration states — and the sound run
through the same code reads ``correct: true``. ``test_broken_path.py``'s
method; not part of tier-1:

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

#: run in a process of its own: ``run.py`` starts a generator, and its
#: entry leaves through ``os._exit``
DRIVER = """
import sys
sys.path[:0] = [{bench!r}, {root!r}]
import numpy as np
import run
from fluentbit_tpu.ops import device, grep

device.platform = lambda: "tpu"   # the selection points take the device path
fault, sound, calls = {fault!r}, grep.SpanProgram.dispatch, [0]

def broken(self, planes, lengths):
    ok, spans = (np.asarray(o) for o in sound(self, planes, lengths))
    calls[0] += 1
    if calls[0] == 7 and ok.any():
        spans = spans.copy()
        row = int(np.argmax(ok))
        spans[row, 0, 1] -= 1     # the first group ends a byte early
    return ok, spans

if fault:
    grep.SpanProgram.dispatch = broken
rc = run.main(["--workload", "parser-apache2.catchup", "--seed", "3200000077",
               "--seconds", "2", "--trace", "0", "--rehearse"])
sys.stdout.flush()
sys.stderr.flush()
import os
os._exit(rc)
"""


@pytest.mark.parametrize("fault", [None, "span_off_by_one"])
def test_a_span_off_by_one_byte_reads_not_correct(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "-c",
         DRIVER.format(bench=BENCH, root=ROOT, fault=fault)],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    lines = done.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    counters = next(json.loads(ln)["window_counters"] for ln in lines
                    if ln.startswith('{"frames"'))
    # the records came from spans, not from the host path
    assert counters["filter.parser.device_records"] > 0
    assert counters["filter.parser.parsed"] > 0
    # (a launch may be in flight when the window's counters are read)
    assert counters["lane.grep.fallback_segments"] == 0
    assert counters["lane.grep.ok"] >= counters["lane.grep.launches"] - 1 > 0
    compared = [ln for ln in done.stderr.splitlines()
                if ln.startswith("compared ")]
    if fault is None:
        assert result["correct"] is True and result["failed"] == 0
        assert done.returncode == 0
        assert all(c["value"] == 0 for c in result["compared"].values())
        return
    assert result["correct"] is False and result["failed"] >= 1
    assert done.returncode == 1
    assert "compared output_sha256_differs: 1 (limit 0)" in compared
    # one byte of one field: the stream is one byte short
    assert result["compared"]["output_bytes_less_expected"]["value"] == -1
    assert "failed check: output_equal_expected_survivors_in_order" \
        in done.stderr
