"""fbtpu-armor: the device fault domain (ops/fault.py + the retry-world
attach controller in ops/device.py).

Covers: attach retry/backoff lifecycle (attempt counting, exhaustion
semantics, re-attach generations, status() reporting), the DeviceLane
launch guard (bit-exact CPU fallback on injected failures, deadline
soft-kill of hung launches, breaker open → short-circuit → half-open →
closed), mesh shrink on device loss + regrow on recovery, the
donated-buffer re-stage regression (a retry after a launch that
consumed its donated staged lengths buffer must re-stage from host
arrays, never touch the deleted aval), the grep mesh lane's re-attach
generation swap-in, and flux sketch re-materialization from the
host-pinned twins after device faults.
"""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from fluentbit_tpu import failpoints
from fluentbit_tpu.ops import device, fault
from fluentbit_tpu.ops import mesh as om
from fluentbit_tpu.ops.batch import assemble
from fluentbit_tpu.ops.grep import program_for

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.mesh


@pytest.fixture(autouse=True)
def _clean_plane():
    failpoints.reset()
    fault.reset()
    yield
    failpoints.reset()
    fault.reset()


def _subproc(code: str, env_extra: dict, timeout: float = 90):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, cwd=REPO,
                          timeout=timeout)


# ------------------------------------------- attach retry lifecycle


def test_attach_retries_then_succeeds():
    """Two injected refusals, third attempt lands: the device swaps in
    live (state ready) and status() records the retry history."""
    code = (
        "from fluentbit_tpu.ops import device\n"
        "assert device.wait(60), device.status()\n"
        "st = device.status()\n"
        "assert st['state'] == 'ready', st\n"
        "assert st['attempts'] == 3, st\n"
        "assert len(st['retry_history']) == 2, st\n"
        "assert st['generation'] == 1, st\n"
    )
    proc = _subproc(code, {
        "FBTPU_FAILPOINTS": "device.attach=2*return(flaky-terminal)",
        "FBTPU_ATTACH_RETRIES": "4",
        "FBTPU_ATTACH_BACKOFF_S": "0.05",
    })
    assert proc.returncode == 0, proc.stderr


def test_attach_exhausts_then_reattach_swaps_in():
    """failed() means EXHAUSTED (all attempts burned), the history
    names every attempt — and reattach_async() re-arms a fresh budget
    that can succeed later (a new attach generation)."""
    code = (
        "from fluentbit_tpu import failpoints\n"
        "from fluentbit_tpu.ops import device\n"
        "assert not device.wait(30)\n"
        "assert device.failed(), device.status()\n"
        "st = device.status()\n"
        "assert st['attempts'] == 2, st\n"
        "assert len(st['retry_history']) == 2, st\n"
        "assert st['next_retry_eta_s'] is None, st\n"
        "assert st['generation'] == 0, st\n"
        "failpoints.reset()\n"
        "assert device.reattach_async()\n"
        "assert device.wait(60), device.status()\n"
        "assert device.generation() == 1, device.status()\n"
    )
    proc = _subproc(code, {
        "FBTPU_FAILPOINTS": "device.attach=return(refused)",
        "FBTPU_ATTACH_RETRIES": "2",
        "FBTPU_ATTACH_BACKOFF_S": "0.05",
    })
    assert proc.returncode == 0, proc.stderr


def test_attach_status_mid_retry_reports_eta():
    """Between attempts the controller is ATTACHING (not failed) and
    status() exposes the next-retry ETA — the bench heartbeat's
    diagnosable block."""
    code = (
        "import time\n"
        "from fluentbit_tpu.ops import device\n"
        "device.attach_async()\n"
        "time.sleep(1.0)\n"  # first attempt failed; long backoff running
        "st = device.status()\n"
        "assert st['state'] == 'attaching', st\n"
        "assert not device.failed()\n"
        "assert st['attempts'] == 1, st\n"
        "assert st['next_retry_eta_s'] is not None, st\n"
    )
    proc = _subproc(code, {
        "FBTPU_FAILPOINTS": "device.attach=1*return(flaky)",
        "FBTPU_ATTACH_RETRIES": "2",
        "FBTPU_ATTACH_BACKOFF_S": "30",
    })
    assert proc.returncode == 0, proc.stderr


# ------------------------------------------------- lane fundamentals


PATTERNS = ("GET|POST", "^kernel:")
VALS = [b"GET /a HTTP/1.1", b"kernel: oops", None, b"POST /b",
        b"zzz", b""] * 5


def _staged(L=96):
    b = assemble(VALS, L)
    return (np.stack([b.batch] * len(PATTERNS)),
            np.stack([b.lengths] * len(PATTERNS)))


def _ref_mask(batch, lengths, cnt):
    from fluentbit_tpu.regex import FlbRegex

    out = np.zeros((len(PATTERNS), cnt), dtype=bool)
    for r, p in enumerate(PATTERNS):
        rx = FlbRegex(p)
        for i in range(cnt):
            li = int(lengths[r, i])
            if li >= 0:
                out[r, i] = rx.match(
                    bytes(batch[r, i, :li]).decode("utf-8"))
    return out


def _mesh_or_skip(n=8):
    assert device.wait(60), device.status()
    if len(jax.devices()) < n:
        pytest.skip(f"need {n} devices")
    return om.build_mesh(n)


def _grep_launch(prog, mesh, batch, lengths):
    def launch():
        m_i32, _, _b, _bp = prog.dispatch_mesh(
            mesh, batch, lengths, with_counts=False)
        return np.asarray(m_i32).astype(bool)

    return launch


def test_lane_fallback_bit_exact_after_post_donation_failure():
    """The donated-buffer regression: device.dispatch fires at the
    POST-launch boundary, i.e. after dispatch_mesh consumed the donated
    staged lengths buffer. The lane's fallback must produce the
    bit-exact verdict from the HOST arrays (re-stage, not the deleted
    aval), and the next launch (fresh device_put) must succeed."""
    mesh = _mesh_or_skip()
    prog = program_for(PATTERNS, 96)
    batch, lengths = _staged()
    cnt = batch.shape[1]
    ref = _ref_mask(batch, lengths, cnt)
    lane = fault.DeviceLane("t-donate", failures=5)
    launch = _grep_launch(prog, mesh, batch, lengths)
    fb = lambda: _ref_mask(batch, lengths, cnt)  # noqa: E731

    clean = lane.run(launch, fb)
    assert np.array_equal(clean[:, :cnt], ref)

    failpoints.enable("device.dispatch", "1*return(post-donation)")
    got = lane.run(launch, fb)
    assert np.array_equal(got[:, :cnt], ref), \
        "fallback verdict must be bit-exact"
    st = lane.stats()
    assert st["failures"] == 1 and st["fallback_segments"] == 1

    failpoints.reset()
    again = lane.run(launch, fb)  # retry re-stages: no deleted-aval read
    assert np.array_equal(again[:, :cnt], ref)
    assert lane.stats()["ok"] == 2


def test_donation_consumed_buffer_would_raise_without_restage():
    """The hazard the lane's re-stage protocol avoids, demonstrated
    directly: after one dispatch the donated lengths device buffer is
    deleted, and reading it raises instead of silently returning
    verdict bytes. (The launch closures re-device_put from host arrays
    on every attempt, so they never hit this.)

    The proof stops at the buffer: it does NOT launch the program a
    second time against the consumed buffer. On jax 0.9.0 the CPU
    client rejects that launch per partition ("Buffer has been deleted
    or donated ... partition 2") after the other partitions were
    enqueued; those never complete, and every later multi-device
    launch in the process blocks behind them in ``np.asarray`` — which
    is what wedged test_lane_device_lost_shrinks_then_regrows and the
    tier-1 run behind it."""
    mesh = _mesh_or_skip()
    prog = program_for(PATTERNS, 96)
    batch, lengths = _staged()
    h = prog._mesh_handle(mesh, "auto", False)
    Bp = om.pad_to_devices(batch.shape[1], h.n_devices)
    if Bp != batch.shape[1]:
        pad = Bp - batch.shape[1]
        batch = np.concatenate(
            [batch, np.zeros((2, pad, 96), dtype=np.uint8)], axis=1)
        lengths = np.concatenate(
            [lengths, np.full((2, pad), -1, dtype=np.int32)], axis=1)
    bd = jax.device_put(np.ascontiguousarray(batch), h.sh_b)
    ld = jax.device_put(np.ascontiguousarray(lengths), h.sh_l)
    np.asarray(h.fn(h.tables, bd, ld))
    assert ld.is_deleted(), "donation must consume the staged buffer"
    assert not bd.is_deleted(), "the u8 batch has no aliasable output"
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(ld)


def test_lane_deadline_soft_kills_hung_launch():
    """An armed device.launch_hang wedges the launch worker; the lane
    soft-kills at its deadline, the segment completes on the fallback,
    and the late worker's result is discarded (commit-on-finish)."""
    mesh = _mesh_or_skip()
    prog = program_for(PATTERNS, 96)
    batch, lengths = _staged()
    cnt = batch.shape[1]
    ref = _ref_mask(batch, lengths, cnt)
    lane = fault.DeviceLane("t-hang", deadline=0.4)
    failpoints.enable("device.launch_hang", "1*hang(3000)")
    t0 = time.time()
    got = lane.run(_grep_launch(prog, mesh, batch, lengths),
                   lambda: _ref_mask(batch, lengths, cnt))
    took = time.time() - t0
    assert took < 2.5, f"soft-kill did not engage ({took:.1f}s)"
    assert np.array_equal(got[:, :cnt], ref)
    st = lane.stats()
    assert st["timeouts"] == 1 and st["abandoned"] == 1


def test_lane_breaker_opens_short_circuits_and_recovers():
    """Consecutive failures open the breaker; open short-circuits
    straight to the fallback (no device touch); after the cooldown one
    half-open probe closes it on success."""
    lane = fault.DeviceLane("t-breaker", failures=2, cooldown=0.2)
    boom = lambda: (_ for _ in ()).throw(RuntimeError("xla boom"))  # noqa: E731
    fb = lambda: "cpu"  # noqa: E731
    assert lane.run(boom, fb) == "cpu"
    assert lane.run(boom, fb) == "cpu"
    assert lane.breaker.state_name() == "open"
    assert lane.stats()["breaker_trips"] == 1
    # open: the launch is never attempted (device untouched)
    ran = []
    assert lane.run(lambda: ran.append(1), fb) == "cpu"
    assert not ran and lane.stats()["short_circuits"] == 1
    time.sleep(0.25)
    assert lane.run(lambda: "device", fb) == "device"  # half-open probe
    assert lane.breaker.state_name() == "closed"


def test_lane_device_lost_shrinks_then_regrows():
    """mesh.device_lost shrinks the lane's mesh to the survivors
    (bit-exact verdicts continue); when the breaker re-closes the mesh
    regrows to the full device set."""
    mesh = _mesh_or_skip()
    prog = program_for(PATTERNS, 96)
    batch, lengths = _staged()
    cnt = batch.shape[1]
    ref = _ref_mask(batch, lengths, cnt)
    lane = fault.DeviceLane("t-lost", failures=1, cooldown=0.1)
    assert lane.current_mesh().devices.size == 8

    def launch():
        m = lane.current_mesh()
        m_i32, _, _b, _bp = prog.dispatch_mesh(
            m, batch, lengths, with_counts=False)
        return np.asarray(m_i32).astype(bool)

    fb = lambda: _ref_mask(batch, lengths, cnt)  # noqa: E731
    failpoints.enable("mesh.device_lost", "1*return(lost)")
    got = lane.run(launch, fb)
    assert np.array_equal(got[:, :cnt], ref)
    assert lane.stats()["device_lost"] == 1
    assert lane.current_mesh().devices.size == 7, \
        "mesh must shrink to the survivors"
    assert lane.breaker.state_name() == "open"  # failures=1
    # the shrunk mesh serves bit-exactly while the breaker recovers
    time.sleep(0.15)
    got2 = lane.run(launch, fb)  # half-open probe on the 7-device mesh
    assert np.array_equal(got2[:, :cnt], ref)
    assert lane.breaker.state_name() == "closed"
    assert lane.current_mesh().devices.size == 8, \
        "breaker re-close must regrow the mesh"


def test_lane_regrows_after_healthy_launches_without_breaker_trip():
    """A one-off device loss that never opens the breaker must not pin
    the shrunk mesh forever: after regrow_after consecutive healthy
    launches on the survivors, the lane probes the full set again."""
    _mesh_or_skip()
    lane = fault.DeviceLane("t-regrow", failures=5, regrow_after=3)
    assert lane.current_mesh().devices.size == 8
    failpoints.enable("mesh.device_lost", "1*return(lost)")
    lane.run(lambda: "dev", lambda: "cpu")
    failpoints.reset()
    assert lane.current_mesh().devices.size == 7
    assert lane.breaker.state_name() == "closed"  # one failure < 5
    for _ in range(3):
        assert lane.current_mesh().devices.size == 7
        assert lane.run(lambda: "dev", lambda: "cpu") == "dev"
    assert lane.current_mesh().devices.size == 8, \
        "healthy launches must probe a regrow"


def test_real_runtime_device_loss_is_classified():
    """A real loss surfaces as an XlaRuntimeError-shaped message, not
    our DeviceLostError — the classifier must map it to a shrink, and
    a transient kernel error must NOT."""
    class FakeXla(RuntimeError):
        pass

    assert fault.is_device_loss(FakeXla("DEVICE_LOST: tpu:3 went away"))
    assert fault.is_device_loss(fault.DeviceLostError("injected"))
    assert not fault.is_device_loss(FakeXla("RESOURCE_EXHAUSTED: hbm"))
    _mesh_or_skip()
    lane = fault.DeviceLane("t-realloss", failures=5)
    lane.run(lambda: (_ for _ in ()).throw(
        FakeXla("device_lost: link down")), lambda: "cpu")
    assert lane.stats()["device_lost"] == 1
    assert lane.current_mesh().devices.size == 7


def test_device_compute_variants_never_mutate_sketch_state():
    """The watched-worker protocol's foundation: computing from an
    explicit snapshot must not touch live sketch state (an abandoned
    worker resuming later would otherwise race the fallback's
    host-pinned commit)."""
    from fluentbit_tpu.ops.sketch import (CountMin, HyperLogLog,
                                          sharded_hll_registers)

    mesh = _mesh_or_skip()
    b = assemble([b"a", b"bb", None, b"ccc"] * 4, 32)
    hll = HyperLogLog(p=8)
    snap = hll.registers
    assert isinstance(snap, np.ndarray)
    got = hll.device_registers(b.batch, b.lengths, wait=True,
                               registers=snap)
    assert got is not None
    assert hll.registers is snap, "compute must not commit or convert"
    got2 = sharded_hll_registers(hll, mesh, b.batch, b.lengths,
                                 registers=snap)
    assert hll.registers is snap
    assert np.array_equal(np.asarray(got), np.asarray(got2))
    cms = CountMin(depth=2, width=64)
    tsnap = cms.table
    gott = cms.device_table(b.batch, b.lengths, wait=True, table=tsnap)
    assert gott is not None and cms.table is tsnap


def test_attach_retry_history_is_bounded():
    """A permanently-absent backend re-attached across many cycles
    must not grow the history (and every health/status copy)
    forever."""
    code = (
        "from fluentbit_tpu.ops import device\n"
        "assert not device.wait(60)\n"
        "st = device.status()\n"
        "assert st['attempts'] == 30, st['attempts']\n"
        "assert len(st['retry_history']) == 20, "
        "len(st['retry_history'])\n"
        "assert st['retry_history'][-1]['attempt'] == 30\n"
    )
    proc = _subproc(code, {
        "FBTPU_FAILPOINTS": "device.attach=return(refused)",
        "FBTPU_ATTACH_RETRIES": "30",
        "FBTPU_ATTACH_BACKOFF_S": "0",
    })
    assert proc.returncode == 0, proc.stderr


# ------------------------------------- grep mesh lane: re-attach swap


def test_grep_mesh_swaps_in_on_new_attach_generation(monkeypatch):
    """A plugin whose mesh resolution pinned OFF after an exhausted
    attach must re-resolve when a later attach generation lands
    (reattach_async / a retry attempt succeeding) — the mesh lane
    swaps in live instead of staying pinned for the plugin lifetime."""
    from fluentbit_tpu.ops import device as dev
    from fluentbit_tpu.plugins.filter_grep import GrepFilter

    monkeypatch.setenv("FBTPU_MESH", "force")
    plug = GrepFilter.__new__(GrepFilter)
    plug._program = object()
    plug._mesh = None
    plug._mesh_resolved = False
    plug._mesh_on = False
    plug._mesh_gen = None
    # attach exhausted at generation 0: resolution pins the mesh off
    monkeypatch.setattr(dev, "generation", lambda: 0)
    monkeypatch.setattr(dev, "wait", lambda *a, **k: False)
    monkeypatch.setattr(dev, "failed", lambda: True)
    assert plug._grep_mesh() is None
    assert plug._mesh_resolved is True
    # the same generation stays pinned (no re-probe per chunk)
    assert plug._grep_mesh() is None
    # a re-attach generation lands: resolution re-opens and engages
    monkeypatch.setattr(dev, "generation", lambda: 1)
    monkeypatch.setattr(dev, "wait", lambda *a, **k: True)
    monkeypatch.setattr(dev, "failed", lambda: False)
    assert plug._grep_mesh() is not None, \
        "mesh lane must swap in live on a new attach generation"
    assert plug._mesh_gen == 1 and plug._mesh_on is True


# --------------------------------------- flux: host re-materialization


def test_flux_sketch_failover_rematerializes_host_side():
    """flux.device_update faults force every sketch/count launch onto
    the host twins: the absorbed state is bit-identical to a clean
    mesh run, and the sketch state is re-materialized host-pinned
    (numpy registers/table — the snapshot/restore source)."""
    from fluentbit_tpu.flux.state import FluxSpec, FluxState

    if len(jax.devices()) < 8:
        pytest.skip("need the simulated 8-device mesh")
    bodies = [{"tenant": ["a", "b"][i % 2], "user": f"u{i % 13}",
               "size": float(i)} for i in range(150)]

    def absorb(state):
        strcols = {
            f: state._str_column(bodies, f)
            for f in state.spec.string_fields
        }
        numcols = {f: state._num_column(bodies, f)
                   for f in state.spec.numeric}
        state.absorb_batch(len(bodies), strcols, numcols)

    kw = dict(group_by=("tenant",), distinct=("user",),
              numeric=("size",), topk_field="user", mesh=True)
    clean = FluxState(FluxSpec("t", **kw))
    assert clean._mesh is not None
    absorb(clean)

    faulty = FluxState(FluxSpec("t", **kw))
    failpoints.enable("flux.device_update", "return(chaos)")
    absorb(faulty)
    failpoints.reset()

    lane = faulty._lane
    assert lane is not None and lane.stats()["fallback_segments"] > 0
    for key, g in faulty._groups.items():
        assert isinstance(g.hlls["user"].registers, np.ndarray), \
            "failed-over sketch state must be host-pinned"
        ref = clean._groups[key]
        assert np.array_equal(np.asarray(g.hlls["user"].registers),
                              np.asarray(ref.hlls["user"].registers))
        assert g.count == ref.count
        assert g.cols["size"].sum == ref.cols["size"].sum
    assert np.array_equal(np.asarray(faulty.cms.table),
                          np.asarray(clean.cms.table))


def test_flux_mesh_update_survives_intermittent_faults():
    """30% injected launch failures mid-absorb: the final sketch state
    is STILL bit-identical to a fault-free run (fallback and device
    math are the same math)."""
    from fluentbit_tpu.flux.state import FluxSpec, FluxState

    if len(jax.devices()) < 8:
        pytest.skip("need the simulated 8-device mesh")
    bodies = [{"user": f"u{i % 31}"} for i in range(64)]

    def absorb(state):
        for _ in range(6):
            strcols = {f: state._str_column(bodies, f)
                       for f in state.spec.string_fields}
            state.absorb_batch(len(bodies), strcols, {})

    clean = FluxState(FluxSpec("t", distinct=("user",), mesh=True))
    absorb(clean)
    faulty = FluxState(FluxSpec("t", distinct=("user",), mesh=True))
    failpoints.enable("flux.device_update", "30%return(chaos)")
    absorb(faulty)
    failpoints.reset()
    g1 = clean._groups[()].hlls["user"]
    g2 = faulty._groups[()].hlls["user"]
    assert np.array_equal(np.asarray(g1.registers),
                          np.asarray(g2.registers))
    assert g1.estimate() == g2.estimate()


# ----------------------------------------------- health / introspection


def test_health_block_shape():
    lane = fault.lane("t-health")
    lane.run(lambda: 1, lambda: 0)
    block = fault.health_block()
    assert block["attach"]["state"] in ("unattached", "attaching",
                                        "ready", "failed")
    assert "retries_max" in block["attach"]
    assert block["lanes"]["t-health"]["ok"] == 1
    assert block["lanes"]["t-health"]["breaker"] == "closed"


def test_engine_health_includes_device_block(tmp_path):
    import json

    import fluentbit_tpu as flb

    ctx = flb.create(flush="50ms", grace="1")
    ctx.input("lib", tag="t")
    ctx.output("null", match="t")
    ctx.start()
    try:
        h = ctx.engine.guard.health()
        assert "device" in h
        assert "attach" in h["device"] and "lanes" in h["device"]
        json.dumps(h)  # the admin endpoint must be able to serialize it
    finally:
        ctx.stop()
