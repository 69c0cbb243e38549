"""Multi-device grep: ``match_mesh`` over the virtual 8-device CPU mesh.

Validates the partitioned matcher (batch-dim sharding + psum match
counts) against the single-device kernel, including the
non-divisible-batch pad path.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jax.sharding import Mesh

from fluentbit_tpu.ops.batch import assemble
from fluentbit_tpu.ops.grep import program_for


def _mesh(n):
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip(f"need {n} devices, have {len(devs)}")
    return Mesh(np.asarray(devs[:n]), ("batch",))


def _stage(patterns, vals, L=64, pad_to=None):
    prog = program_for(tuple(patterns), L)
    b = assemble(vals, L, pad_to)
    R = len(patterns)
    return prog, np.stack([b.batch] * R), np.stack([b.lengths] * R)


CORPUS = [
    b"GET /index.html 200",
    b"POST /api/v1 500",
    b"kernel: panic",
    b"",
    None,  # missing field row
    b"DELETE /x 404",
] * 7  # 42 rows — not divisible by 8


@pytest.mark.parametrize("n_dev", [2, 8])
def test_sharded_matches_single_device(n_dev):
    mesh = _mesh(n_dev)
    prog, batch, lengths = _stage(["GET|POST", "^kernel:", "50[0-9]$"], CORPUS)
    mask, counts, padded = prog.match_mesh(mesh, batch, lengths)
    ref = prog.match(batch, lengths)
    assert padded % n_dev == 0
    assert np.array_equal(mask, ref)
    assert np.array_equal(counts, ref.sum(axis=1))


def test_sharded_counts_are_global():
    mesh = _mesh(8)
    vals = [b"hit"] * 16 + [b"miss"] * 16
    prog, batch, lengths = _stage(["hit"], vals)
    _, counts, _ = prog.match_mesh(mesh, batch, lengths)
    assert counts.tolist() == [16]
