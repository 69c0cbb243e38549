"""A whole run of ``nexmark-q5.catchup``, the look for a chip skipped
(``--rehearse``, CPU), with the window's arithmetic broken underneath:
``correct`` has to come out false, by the ``exactness`` guarantee the
configuration states ("every window row equals a plain count over
exactly the frames between two closes"), and the sound run through the
same code reads ``correct: true`` with every compared number 0. The
faults are the two a windowed count can have: one frame's bids absorbed
twice, and integer keys falling into the null group (what the program
did before it keyed integers: one row a window, the total).
``test_broken_rule.py``'s method; not part of tier-1:

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
from fluentbit_tpu.flux import plugin, state

fault, calls = {fault!r}, [0]
absorb, stage_key = state.FluxState.absorb_batch, plugin.FluxFilter._stage_key

def absorbed_twice(self, n, *cols):
    calls[0] += 1
    if calls[0] == 7:
        absorb(self, n, *cols)
    return absorb(self, n, *cols)

def null_group(native, data, field, n, b, ln):
    return state.KeyCol.of_strings(b, ln)   # an integer is "missing"

if fault == "absorbed_twice":
    state.FluxState.absorb_batch = absorbed_twice
elif fault == "null_group":
    plugin.FluxFilter._stage_key = staticmethod(null_group)
rc = run.main(["--workload", "nexmark-q5.catchup", "--seed", "2900000041",
               "--seconds", "7", "--trace", "0", "--rehearse"])
sys.stdout.flush()
sys.stderr.flush()
import os
os._exit(rc)
"""


@pytest.mark.parametrize("fault,failing", [
    (None, ()),
    ("absorbed_twice", ("flux_absorbed_exactly_the_bids_acked",)),
    ("null_group", ("side_rows_are_auction_and_num_integers",
                    "panes_sum_to_the_bids_acked_nothing_missing_"
                    "nothing_twice")),
])
def test_a_broken_window_reads_not_correct(fault, failing):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "-c",
         DRIVER.format(bench=BENCH, root=ROOT, fault=fault)],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    lines = done.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    checks = next(json.loads(ln) for ln in lines
                  if ln.startswith('{"checks"'))
    assert result["device"]["platform"] == "cpu"
    # the window closed at least once and the drain came after it
    assert checks["reference"]["emissions"] >= 2
    if fault is None:
        assert result["correct"] is True and result["failed"] == 0
        assert done.returncode == 0 and checks["failed_checks"] == []
        assert all(c["value"] == 0 for c in result["compared"].values())
        assert checks["reference"]["rows_differing_from_reference"] == 0
        return
    assert result["correct"] is False and result["failed"] >= 1
    assert done.returncode == 1
    for name in failing:
        assert name in checks["failed_checks"]
        assert f"failed check: {name}" in done.stderr
    # the records themselves passed unharmed: only the window broke
    assert checks["checks"]["output_equal_expected_survivors_in_order"]
    exact = ("every_emission_total_falls_on_a_frame_boundary",
             "every_row_equal_reference_count",
             "panes_sum_to_the_bids_acked_nothing_missing_nothing_twice")
    assert not all(checks["checks"][name] for name in exact)
