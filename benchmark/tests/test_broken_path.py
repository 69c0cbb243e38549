"""A whole run of a cell, the look for a chip skipped (``--rehearse``,
CPU), with the served path broken underneath: ``correct`` has to come
out false, and the numbers compared have to say which guarantee broke.
The faults are the ones a cell of this system can have — an answer
altered where it is produced (a byte of one surviving record, in the
filter's own output buffer) and an answer left out (one frame's
survivors dropped by the filter) — each breaking the ``exactness``
guarantee every configuration states; the sound run beside them reads
``correct: true`` through the same code. Not part of tier-1:

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
import run
from fluentbit_tpu.plugins import filter_grep

fault, sound, calls = {fault!r}, filter_grep.GrepFilter.process_batch, [0]

def broken(self, chunk):
    got = sound(self, chunk)
    calls[0] += 1
    if got is None or calls[0] != 7 or not got[0]:
        return got
    if fault == "altered":
        out = bytearray(got[1])
        out[-1] ^= 1
        return (got[0], bytes(out)) + tuple(got[2:])
    return (0, b"") + tuple(got[2:])

if fault:
    filter_grep.GrepFilter.process_batch = broken
rc = run.main(["--workload", "grep-apache2.catchup", "--seed", "2200000077",
               "--seconds", "2", "--trace", "0", "--rehearse"])
sys.stdout.flush()
sys.stderr.flush()
import os
os._exit(rc)
"""


@pytest.mark.parametrize("fault,failing", [
    (None, None),
    ("altered", "output_sha256_differs: 1 (limit 0)"),
    ("dropped", "output_sha256_differs: 1 (limit 0)"),
])
def test_a_broken_served_path_reads_not_correct(fault, failing):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "-c",
         DRIVER.format(bench=BENCH, root=ROOT, fault=fault)],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert list(result)[-1] == "compared"
    assert result["device"]["platform"] == "cpu"
    compared = [ln for ln in done.stderr.splitlines()
                if ln.startswith("compared ")]
    assert len(compared) == len(result["compared"])
    assert done.stderr.strip().splitlines()[-1] == compared[-1]
    if fault is None:
        assert result["correct"] is True and result["failed"] == 0
        assert done.returncode == 0
        assert all(c["value"] == 0 for c in result["compared"].values())
        return
    assert result["correct"] is False and result["failed"] >= 1
    assert done.returncode == 1
    assert "compared " + failing in compared
    assert "failed check: output_equal_expected_survivors_in_order" \
        in done.stderr
    assert result["compared"]["failed_checks"]["value"] >= 1
    if fault == "dropped":
        assert result["compared"]["output_bytes_less_expected"]["value"] < 0
