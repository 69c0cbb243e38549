"""A whole run of ``grep-tenants.rules50``, the look for a chip skipped
(``--rehearse``, CPU) and the platform gate forced open so that the 50
rules are matched by the device program (on the CPU backend, through the
lane, on one device, as tier-1 forces it): with one of the 50 rules
dropped from the program underneath — its row of the verdict never set,
so the lines that only tenant 1's ``level=(debug|trace)`` drops come
through — ``correct`` has to come out false, by the ``exactness``
guarantee the configuration states, and the sound run through the same
code reads ``correct: true`` with every compared number 0.
``test_broken_span.py``'s method; not part of tier-1 (a launch at
``[50, 4096, 512]`` takes the CPU backend the better part of a second):

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
from fluentbit_tpu.ops import device, grep

device.platform = lambda: "tpu"   # the selection points take the device path
fault, sound = {fault!r}, grep.GrepProgram.dispatch

def one_rule_short(self, planes, lengths, first_match=False):
    mask = sound(self, planes, lengths, first_match)
    if len(self.dfas) == 50:
        assert self.dfas[1].pattern == "level=(debug|trace)"
        mask = mask.at[1].set(False)    # rule 1 is not in the program
    return mask

if fault:
    grep.GrepProgram.dispatch = one_rule_short
rc = run.main(["--workload", "grep-tenants.rules50", "--seed", "3400000077",
               "--seconds", "4", "--trace", "0", "--rehearse"])
sys.stdout.flush()
sys.stderr.flush()
import os
os._exit(rc)
"""


@pytest.mark.parametrize("fault", [None, "rule_dropped"])
def test_a_rule_dropped_from_the_program_reads_not_correct(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu", FBTPU_MESH="off")
    done = subprocess.run(
        [sys.executable, "-c",
         DRIVER.format(bench=BENCH, root=ROOT, fault=fault)],
        capture_output=True, text=True, timeout=900, env=env, cwd=ROOT)
    lines = done.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    counters = next(json.loads(ln)["window_counters"] for ln in lines
                    if ln.startswith('{"frames"'))
    # the verdicts came from the device program, not from the host twin
    assert counters["filter.grep.device_records"] > 0
    assert counters["filter.grep.d2h_bytes"] \
        == 50 * counters["filter.grep.device_records"]
    assert counters["lane.grep.fallback_segments"] == 0
    # (a launch may be in flight when the window's counters are read)
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
    # tenant 1's debug lines came through: the sink holds more than it may
    assert result["compared"]["output_bytes_less_expected"]["value"] > 0
    assert "failed check: output_equal_expected_survivors_in_order" \
        in done.stderr
