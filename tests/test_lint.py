"""fbtpu-lint: the analyzer gates the package tree, and the analyzer
itself is pinned by fixtures — every rule must fire on a known-bad
snippet, stay quiet on the known-good twin, and honor the
``# fbtpu-lint: allow(...)`` suppression path.

The fixture paths matter: guarded-by findings key off the registry's
module paths, so the bad snippets are linted *as if* they lived in
core/engine.py etc. — a deliberately-introduced guarded-attribute
access, an await-under-lock, or a host-sync-in-traced-code would fail
this file exactly like it fails `python -m fluentbit_tpu.analysis`.
"""

import os
import subprocess
import sys

from fluentbit_tpu.analysis import lint_paths, lint_source
from fluentbit_tpu.analysis.registry import GuardEntry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "fluentbit_tpu")


def rules(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------
# the gate: the shipped tree must be clean
# ---------------------------------------------------------------------

def test_package_tree_clean():
    # the committed launch/transfer budget (analysis/launch_budget.json)
    # is the one sanctioned baseline: its recorded launch-graph debt
    # (ROADMAP item 1) is subtracted exactly — anything else fails, and
    # a stale baseline entry that no longer matches the tree fails too
    # ... and since the locksmith pack, analysis/lock_baseline.json is
    # the second sanctioned baseline, since the memscope pack,
    # analysis/copy_budget.json the third, and since the fuseplan
    # pack, analysis/fusion_plan.json the fourth — all are subtracted
    # EXACTLY
    import json

    from fluentbit_tpu.analysis.__main__ import _canon
    from fluentbit_tpu.analysis.registry import budget_path, \
        copy_budget_path, fusion_plan_path, lock_baseline_path

    recorded = set()
    for bpath in (budget_path(), lock_baseline_path(),
                  copy_budget_path(), fusion_plan_path()):
        with open(bpath, "r", encoding="utf-8") as fh:
            recorded |= {(d["path"], d["rule"], d["message"])
                         for d in json.load(fh)["findings"]}
    findings = lint_paths([PKG])
    keys = {(_canon(f.path), f.rule, f.message) for f in findings}
    fresh = [f for f in findings
             if (_canon(f.path), f.rule, f.message) not in recorded]
    assert not fresh, "\n".join(f.render() for f in fresh)
    stale = recorded - keys
    assert not stale, f"stale baseline entries: {stale}"


def test_cli_exit_codes(tmp_path):
    proc = subprocess.run(
        [sys.executable, "-m", "fluentbit_tpu.analysis", PKG],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    bad = tmp_path / "fluentbit_tpu" / "plugins"
    bad.mkdir(parents=True)
    (bad / "x.py").write_text(
        "try:\n    f()\nexcept Exception:\n    pass\n")
    proc = subprocess.run(
        [sys.executable, "-m", "fluentbit_tpu.analysis", str(bad)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 1
    assert "swallowed-error" in proc.stdout


def test_list_rules():
    proc = subprocess.run(
        [sys.executable, "-m", "fluentbit_tpu.analysis", "--list-rules"],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0
    for name in ("guarded-by", "await-in-lock", "swallowed-error",
                 "batch-decline-after-commit", "batch-commit-replay",
                 "batch-no-fallback", "batch-unordered-emit",
                 "decline-swallow", "dtype-narrowing",
                 "await-no-deadline",
                 "device-multi-launch-chain", "device-undonated-buffer",
                 "device-host-roundtrip", "device-sync-in-staging-loop",
                 "stage-redundant-copy",
                 "shard-unmatched-leaf", "shard-shadowed-rule",
                 "shard-indivisible-axis", "donation-aval-mismatch",
                 "shard-implicit-reshard", "jit-dynamic-shape-retrace",
                 "codec-balance", "codec-bounds", "codec-leak",
                 "untrusted-bounds",
                 "lock-order-cycle", "guarded-field-unlocked",
                 "guarded-by-missing", "atomicity-check-then-act",
                 "lock-held-across-dispatch", "cow-swap-aliasing",
                 "host-redundant-copy", "host-decode-then-restage",
                 "host-mutable-view-escape", "mmap-lifetime-escape",
                 "fusable-unfused-boundary",
                 "fusion-blocked-by-host-compact",
                 "cross-launch-restage", "fused-effect-violation",
                 "fusion-plan-regression", "stale-suppression"):
        assert name in proc.stdout


# ---------------------------------------------------------------------
# guarded-by (lock discipline)
# ---------------------------------------------------------------------

BAD_GUARDED = """
class Engine:
    def park(self, chunks):
        self._backlog.extend(chunks)
"""

GOOD_GUARDED = """
class Engine:
    def park(self, chunks):
        with self._ingest_lock:
            self._backlog.extend(chunks)
"""


def test_guarded_attr_fires_off_lock():
    got = lint_source(BAD_GUARDED, "fluentbit_tpu/core/engine.py")
    assert rules(got) == ["guarded-by"]
    assert "_ingest_lock" in got[0].message


def test_guarded_attr_quiet_under_lock():
    assert lint_source(GOOD_GUARDED, "fluentbit_tpu/core/engine.py") == []


def test_guarded_attr_suppression():
    src = BAD_GUARDED.replace(
        "self._backlog.extend(chunks)",
        "self._backlog.extend(chunks)  # fbtpu-lint: allow(guarded-by)")
    assert lint_source(src, "fluentbit_tpu/core/engine.py") == []


def test_guarded_attr_init_exempt_and_alias():
    src = """
class Engine:
    def __init__(self):
        self._backlog = []

    def drain(self, ins, parallel):
        lock = ins.ingest_lock if parallel else self._ingest_lock
        with lock:
            self._backlog.append(1)
"""
    assert lint_source(src, "fluentbit_tpu/core/engine.py") == []


def test_guarded_closure_under_lock_still_flagged():
    # a closure born inside the lock runs later, without it
    src = """
class Engine:
    def sched(self):
        with self._ingest_lock:
            def later():
                self._backlog.append(1)
        return later
"""
    got = lint_source(src, "fluentbit_tpu/core/engine.py")
    assert rules(got) == ["guarded-by"]


def test_alias_is_function_scoped():
    # an alias minted in one function must not legitimize `with lock:`
    # in a sibling that bound the same NAME to a different lock
    src = """
class Engine:
    def a(self):
        lock = self._ingest_lock
        with lock:
            self._backlog.append(1)

    def b(self):
        lock = self._other_mutex
        with lock:
            self._task_map.clear()
"""
    got = lint_source(src, "fluentbit_tpu/core/engine.py")
    assert rules(got) == ["guarded-by"]
    assert len(got) == 1 and "_task_map" in got[0].message  # b() only


def test_lambda_under_lock_still_flagged():
    # a lambda born under the lock runs later, without it
    src = """
class Engine:
    def sched(self):
        with self._ingest_lock:
            cb = lambda: self._task_map.pop(1, None)
        return cb
"""
    got = lint_source(src, "fluentbit_tpu/core/engine.py")
    assert rules(got) == ["guarded-by"]


def test_cli_bad_path_fails_loudly():
    proc = subprocess.run(
        [sys.executable, "-m", "fluentbit_tpu.analysis",
         "fluentbit_tpu/core/engine.pyy"],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 2
    assert "not a directory or .py file" in proc.stderr


def test_guarded_global_and_writes_only():
    guards = (GuardEntry("mod.py", "_lock", ("_state",),
                         writes_only=True, kind="global"),)
    bad = "def f():\n    global _state\n    _state = 'x'\n"
    good = ("import threading\n_lock = threading.Lock()\n_state = None\n"
            "def probe():\n    return _state\n"
            "def set_it(v):\n    global _state\n"
            "    with _lock:\n        _state = v\n")
    assert rules(lint_source(bad, "mod.py", guards)) == ["guarded-by"]
    assert lint_source(good, "mod.py", guards) == []


# ---------------------------------------------------------------------
# await-in-lock
# ---------------------------------------------------------------------

BAD_AWAIT = """
import asyncio
class E:
    async def main(self):
        with self._ingest_lock:
            await asyncio.sleep(0.1)
"""

GOOD_AWAIT = """
import asyncio
class E:
    async def main(self):
        with self._ingest_lock:
            x = 1
        await asyncio.sleep(0.1)
        async with self._aio_lock:
            await asyncio.sleep(0.1)
"""


def test_await_under_threading_lock_fires():
    got = lint_source(BAD_AWAIT, "fluentbit_tpu/core/engine.py")
    assert rules(got) == ["await-in-lock"]


def test_await_outside_lock_and_async_with_quiet():
    assert lint_source(GOOD_AWAIT, "fluentbit_tpu/core/engine.py") == []


def test_await_in_nested_def_not_attributed_to_outer_lock():
    src = """
import asyncio
class E:
    def make(self):
        with self._ingest_lock:
            async def later():
                await asyncio.sleep(0)
        return later
"""
    assert lint_source(src, "fluentbit_tpu/core/engine.py") == []


# ---------------------------------------------------------------------
# jax purity / retrace
# ---------------------------------------------------------------------

BAD_HOST_SYNC = """
import jax
import numpy as np

@jax.jit
def kernel(batch):
    host = np.asarray(batch)
    return batch + host.sum()
"""

BAD_TRACED_CHAIN = """
import jax
from jax import lax

class P:
    def _materialize(self):
        impl = self._assoc if self.kernel else self._scan
        self._jit = jax.jit(impl)

    def _scan(self, batch, lengths):
        def step(s, c):
            print("tracing")
            return s, None
        out, _ = lax.scan(step, batch, lengths)
        return out.block_until_ready()

    def _assoc(self, batch, lengths):
        if batch.shape[0] > 128:
            return batch
        return lengths
"""

GOOD_KERNEL = """
import jax
import jax.numpy as jnp
from jax import lax

@jax.jit
def kernel(batch, lengths):
    pad = jnp.arange(batch.shape[1]) >= lengths[:, None]
    cls = jnp.where(pad, 0, batch)

    def step(s, c):
        return s + c.sum(), None

    out, _ = lax.scan(step, jnp.zeros(()), cls.T)
    return out


def host_wrapper(batch, lengths):
    import numpy as np
    return np.asarray(kernel(batch, lengths))
"""


def test_host_sync_in_jitted_fn_fires():
    got = lint_source(BAD_HOST_SYNC, "fluentbit_tpu/ops/fixture.py")
    assert rules(got) == ["jax-host-sync"]


def test_traced_chain_through_alias_scan_and_shape_branch():
    got = lint_source(BAD_TRACED_CHAIN, "fluentbit_tpu/ops/fixture.py")
    assert rules(got) == ["jax-host-sync", "jax-retrace",
                          "jax-side-effect"]


def test_pure_kernel_quiet_and_host_wrapper_untraced():
    # np.asarray is fine OUTSIDE traced code (host_wrapper)
    assert lint_source(GOOD_KERNEL, "fluentbit_tpu/ops/fixture.py") == []


def test_purity_suppression():
    src = BAD_HOST_SYNC.replace(
        "host = np.asarray(batch)",
        "host = np.asarray(batch)  # fbtpu-lint: allow(jax-host-sync)")
    assert lint_source(src, "fluentbit_tpu/ops/fixture.py") == []


# batched filter entry points (process_batch): the retrace rule fires
# on shape branches even though the def itself is not traced — a shape
# branch there re-specializes every kernel the batch feeds

BAD_PROCESS_BATCH = """
import numpy as np

class F:
    def process_batch(self, chunk):
        staged = self._stage(chunk)
        if staged.shape[0] > 128:
            return self._kernel_big(staged)
        return self._kernel_small(staged)
"""

GOOD_PROCESS_BATCH = """
import numpy as np

class F:
    def process_batch(self, chunk):
        staged = self._stage(chunk)           # bucketed upstream
        host = np.asarray(staged)             # host sync is legal here
        if chunk.n is None:
            return None
        return self._kernel(host)
"""


def test_process_batch_shape_branch_fires():
    got = lint_source(BAD_PROCESS_BATCH,
                      "fluentbit_tpu/plugins/filter_x.py")
    assert rules(got) == ["jax-retrace"]
    assert "process_batch" in got[0].message


def test_process_batch_host_code_quiet():
    # host syncs and branches on plain ints stay legal in batched
    # entries — only array-shape branches re-specialize kernels
    assert lint_source(GOOD_PROCESS_BATCH,
                       "fluentbit_tpu/plugins/filter_x.py") == []


def test_process_batch_suppression():
    src = BAD_PROCESS_BATCH.replace(
        "if staged.shape[0] > 128:",
        "if staged.shape[0] > 128:  # fbtpu-lint: allow(jax-retrace)")
    assert lint_source(src, "fluentbit_tpu/plugins/filter_x.py") == []


# pjit / shard_map coverage (the partitioned mesh plane): decorated and
# call-arg forms both seed tracing, and host-callback escapes fire —
# a callback inside a sharded program blocks every device's step

BAD_PJIT_DECORATED = """
import jax
import numpy as np
from jax.experimental.pjit import pjit

@pjit
def kernel(tables, batch):
    host = np.asarray(batch)
    return batch + host.sum()
"""

BAD_SHARD_MAP_CALLBACK = """
import jax
import jax.numpy as jnp
from jax.experimental.shard_map import shard_map

def build(mesh, specs):
    def step(t, batch, lengths):
        extra = jax.pure_callback(lambda x: x + 1, batch, batch)
        return extra + t["starts"]

    return jax.jit(shard_map(step, mesh=mesh, in_specs=specs,
                             out_specs=specs))
"""

GOOD_MESH_PROGRAM = """
import re
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax.experimental.shard_map import shard_map

def match_partition_rules(rules, tree):
    # host-side partition-rules layer: np use is legal here (untraced)
    def pick(name, leaf):
        if np.prod(getattr(leaf, "shape", ())) == 1:
            return P()
        for rule, spec in rules:
            if re.search(rule, name):
                return spec
        raise ValueError(name)
    return {k: pick(k, v) for k, v in tree.items()}

def build(mesh, tspecs, axis):
    def step(t, batch, lengths):
        # pytree-structure membership is static per jit cache entry,
        # not tracer boolification — must stay quiet
        if "bias" in t:
            base = t["bias"]
        else:
            base = t["starts"]
        mask = (batch.sum(axis=2) + base[:, None] > 0) & (lengths >= 0)
        return mask.astype(jnp.int32)

    return jax.jit(shard_map(step, mesh=mesh,
                             in_specs=(tspecs, P(None, axis, None),
                                       P(None, axis)),
                             out_specs=P(None, axis)))
"""


def test_pjit_decorated_host_sync_fires():
    got = lint_source(BAD_PJIT_DECORATED, "fluentbit_tpu/ops/fixture.py")
    assert rules(got) == ["jax-host-sync"]


def test_shard_map_arg_callback_fires():
    got = lint_source(BAD_SHARD_MAP_CALLBACK,
                      "fluentbit_tpu/ops/fixture.py")
    assert rules(got) == ["jax-host-sync"]
    assert "callback" in got[0].message


def test_mesh_program_with_partition_rules_quiet():
    # the partition-rules layer is host code (np/re legal); the
    # shard_map'd step's dict-membership branch is pytree structure
    assert lint_source(GOOD_MESH_PROGRAM,
                       "fluentbit_tpu/ops/fixture.py") == []


def test_membership_over_traced_array_param_still_fires():
    # the pytree-membership exemption is scoped to params the kernel
    # also string-subscripts (dict pytrees); `"GET" in batch` over a
    # traced ARRAY iterates the tracer at trace time and must fire
    src = """
import jax

@jax.jit
def kernel(batch, lengths):
    if "GET" in batch:
        return lengths
    return batch
"""
    got = lint_source(src, "fluentbit_tpu/ops/fixture.py")
    assert rules(got) == ["jax-retrace"]


# ---------------------------------------------------------------------
# swallowed-error
# ---------------------------------------------------------------------

BAD_SWALLOW = """
def flush(x):
    try:
        send(x)
    except Exception:
        pass
"""


def test_broad_swallow_fires_on_data_path():
    got = lint_source(BAD_SWALLOW, "fluentbit_tpu/plugins/out_x.py")
    assert rules(got) == ["swallowed-error"]


def test_narrow_or_observable_handlers_quiet():
    src = """
def flush(x, m):
    try:
        send(x)
    except OSError:
        pass
    try:
        send(x)
    except Exception:
        m.inc(1)
"""
    assert lint_source(src, "fluentbit_tpu/plugins/out_x.py") == []


def test_swallow_off_data_path_quiet():
    assert lint_source(BAD_SWALLOW, "fluentbit_tpu/luart/interp.py") == []


def test_swallow_suppression_on_pass_line():
    src = BAD_SWALLOW.replace(
        "        pass",
        "        pass  # fbtpu-lint: allow(swallowed-error)")
    assert lint_source(src, "fluentbit_tpu/plugins/out_x.py") == []


def test_bare_and_tuple_broad_excepts_fire():
    src = """
def a(x):
    try:
        go(x)
    except:
        pass

def b(x):
    try:
        go(x)
    except (ValueError, Exception):
        pass
"""
    got = lint_source(src, "fluentbit_tpu/core/x.py")
    assert len(got) == 2 and rules(got) == ["swallowed-error"]


# ---------------------------------------------------------------------
# batch exactness (process_batch contract dataflow)
# ---------------------------------------------------------------------

BAD_DECLINE_AFTER_COMMIT = """
class F:
    stateful_batch = True

    def can_process_batch(self):
        return True

    def process_batch(self, chunk):
        n = chunk.n
        self.metric.inc(n, ())
        if n > 100:
            return None
        return (n, chunk.data, n)
"""

GOOD_DECLINE_BEFORE_COMMIT = """
class F:
    stateful_batch = True

    def can_process_batch(self):
        return True

    def process_batch(self, chunk):
        n = chunk.n
        if n is None:
            return None
        self.metric.inc(n, ())
        return (n, chunk.data, n)
"""


def test_decline_after_commit_fires():
    got = lint_source(BAD_DECLINE_AFTER_COMMIT,
                      "fluentbit_tpu/plugins/filter_x.py")
    assert "batch-decline-after-commit" in rules(got)


def test_decline_before_commit_quiet():
    assert lint_source(GOOD_DECLINE_BEFORE_COMMIT,
                       "fluentbit_tpu/plugins/filter_x.py") == []


def test_decline_after_commit_interprocedural():
    # the commit hides inside a self-method, the decline in a tail call
    src = """
class F:
    stateful_batch = True

    def can_process_batch(self):
        return True

    def _bump(self, n):
        self.metric.inc(n, ())

    def _finish(self, chunk):
        if chunk.n is None:
            return None
        return (chunk.n, chunk.data, chunk.n)

    def process_batch(self, chunk):
        self._bump(chunk.n)
        return self._finish(chunk)
"""
    got = lint_source(src, "fluentbit_tpu/plugins/filter_x.py")
    assert "batch-decline-after-commit" in rules(got)


def test_fallback_error_raise_after_commit_fires():
    src = BAD_DECLINE_AFTER_COMMIT.replace(
        "return None", "raise FallbackError('decline')")
    got = lint_source(src, "fluentbit_tpu/plugins/filter_x.py")
    assert "batch-decline-after-commit" in rules(got)


def test_tail_call_decline_before_commit_quiet():
    # the GOOD pattern refactored into a helper: the tail callee
    # declines BEFORE committing — must not be double-inlined into a
    # false decline-after-commit
    src = """
class F:
    stateful_batch = True

    def can_process_batch(self):
        return True

    def _impl(self, chunk):
        if chunk.n is None:
            return None
        self.metric.inc(chunk.n, ())
        return (chunk.n, chunk.data, chunk.n)

    def process_batch(self, chunk):
        return self._impl(chunk)
"""
    assert lint_source(src, "fluentbit_tpu/plugins/filter_x.py") == []


BAD_COMMIT_REPLAY = """
class F:
    stateful_batch = True

    def can_process_batch(self):
        return True

    def process_batch(self, chunk):
        if chunk.n is None:
            return None
        for tag, payload in chunk.groups:
            self.emitter.add_record(tag, payload, 1)
        return (chunk.n, chunk.data, chunk.n)
"""


def test_unguarded_emit_loop_replay_fires():
    # iteration N+1's add_record raising replays iteration N's emit
    got = lint_source(BAD_COMMIT_REPLAY,
                      "fluentbit_tpu/plugins/filter_x.py")
    assert "batch-commit-replay" in rules(got)


def test_guarded_emit_loop_quiet():
    src = BAD_COMMIT_REPLAY.replace(
        "            self.emitter.add_record(tag, payload, 1)",
        "            try:\n"
        "                self.emitter.add_record(tag, payload, 1)\n"
        "            except Exception:\n"
        "                log.exception('append failed')")
    assert lint_source(src, "fluentbit_tpu/plugins/filter_x.py") == []


def test_stateful_unmarked_fires():
    src = BAD_COMMIT_REPLAY.replace("    stateful_batch = True\n", "")
    got = lint_source(src, "fluentbit_tpu/plugins/filter_x.py")
    assert "batch-stateful-unmarked" in rules(got)


def test_no_fallback_fires_only_with_can_process_batch():
    src = """
class F:
    def can_process_batch(self):
        return True

    def process_batch(self, chunk):
        return (chunk.n, chunk.data, chunk.n)
"""
    got = lint_source(src, "fluentbit_tpu/plugins/filter_x.py")
    assert rules(got) == ["batch-no-fallback"]
    # without the advertisement the hook is inert: no contract to break
    src2 = src.replace("    def can_process_batch(self):\n"
                       "        return True\n\n", "")
    assert lint_source(src2, "fluentbit_tpu/plugins/filter_x.py") == []


def test_unordered_emit_fires_and_sorted_groups_quiet():
    bad = """
class F:
    stateful_batch = True

    def can_process_batch(self):
        return True

    def process_batch(self, chunk):
        if chunk.n is None:
            return None
        for tag in set(chunk.tags):
            try:
                self.emitter.add_record(tag, b"", 1)
            except Exception:
                log.exception("x")
        return (chunk.n, chunk.data, chunk.n)
"""
    got = lint_source(bad, "fluentbit_tpu/plugins/filter_x.py")
    assert "batch-unordered-emit" in rules(got)
    good = bad.replace(
        "set(chunk.tags)",
        "sorted(groups.items(), key=lambda kv: kv[1]['first'])")
    assert lint_source(good, "fluentbit_tpu/plugins/filter_x.py") == []
    # output-buffer concatenation over a set is flagged too...
    concat = """
class F:
    def can_process_batch(self):
        return True

    def process_batch(self, chunk):
        if chunk.n is None:
            return None
        out = bytearray()
        for tag in set(chunk.tags):
            out += chunk.spans[tag]
        return (chunk.n, bytes(out), chunk.n)
"""
    got = lint_source(concat, "fluentbit_tpu/plugins/filter_x.py")
    assert "batch-unordered-emit" in rules(got)
    # ...but an order-INDEPENDENT reduction over a set is not
    reduction = concat.replace(
        "        out = bytearray()\n", "        total = 0\n").replace(
        "            out += chunk.spans[tag]",
        "            total += chunk.counts[tag]").replace(
        "        return (chunk.n, bytes(out), chunk.n)",
        "        return (chunk.n, chunk.data, chunk.n)")
    assert lint_source(reduction,
                       "fluentbit_tpu/plugins/filter_x.py") == []


def test_batch_rule_suppression():
    src = BAD_DECLINE_AFTER_COMMIT.replace(
        "            return None",
        "            return None  "
        "# fbtpu-lint: allow(batch-decline-after-commit)")
    assert lint_source(src, "fluentbit_tpu/plugins/filter_x.py") == []


# ---------------------------------------------------------------------
# decline-swallow
# ---------------------------------------------------------------------

BAD_DECLINE_SWALLOW = """
class F:
    def init(self):
        try:
            self._tables = build()
        except Exception:
            self._tables = None
"""


def test_decline_swallow_fires_on_data_path():
    got = lint_source(BAD_DECLINE_SWALLOW,
                      "fluentbit_tpu/plugins/filter_x.py")
    assert rules(got) == ["decline-swallow"]
    assert got[0].severity == "warning"


def test_decline_swallow_quiet_when_logged_or_narrow():
    logged = BAD_DECLINE_SWALLOW.replace(
        "            self._tables = None",
        "            log.warning('fast path disabled', exc_info=True)\n"
        "            self._tables = None")
    assert lint_source(logged, "fluentbit_tpu/plugins/filter_x.py") == []
    narrow = BAD_DECLINE_SWALLOW.replace("except Exception:",
                                         "except ValueError:")
    assert lint_source(narrow, "fluentbit_tpu/plugins/filter_x.py") == []


def test_decline_swallow_off_data_path_quiet():
    assert lint_source(BAD_DECLINE_SWALLOW,
                       "fluentbit_tpu/luart/interp.py") == []


def test_decline_swallow_does_not_double_report_pass_bodies():
    # pass-only bodies stay swallowed-error territory
    got = lint_source(BAD_SWALLOW, "fluentbit_tpu/plugins/out_x.py")
    assert rules(got) == ["swallowed-error"]


# ---------------------------------------------------------------------
# await-no-deadline (flush-path I/O deadlines)
# ---------------------------------------------------------------------

BAD_NO_DEADLINE = """
class FooOutput(OutputPlugin):
    async def _connect(self):
        self._reader, self._writer = await open_connection(
            self.instance, self.host, self.port)

    async def flush(self, data, tag, engine):
        self._writer.write(data)
        await self._writer.drain()
        return FlushResult.OK
"""

GOOD_DEADLINE = """
class FooOutput(OutputPlugin):
    async def _connect(self):
        self._reader, self._writer = await open_connection(
            self.instance, self.host, self.port, timeout=10)

    async def flush(self, data, tag, engine):
        self._writer.write(data)
        await io_deadline(self._writer.drain())
        line = await asyncio.wait_for(self._reader.readline(), 5.0)
        return FlushResult.OK
"""


def test_await_no_deadline_fires_on_raw_flush_io():
    got = lint_source(BAD_NO_DEADLINE, "fluentbit_tpu/plugins/out_x.py")
    assert rules(got) == ["await-no-deadline"]
    assert len(got) == 2  # unbounded dial + raw drain
    assert all(f.severity == "warning" for f in got)
    assert "task-map slot" in got[1].message


def test_await_no_deadline_quiet_when_wrapped():
    assert lint_source(GOOD_DEADLINE,
                       "fluentbit_tpu/plugins/out_x.py") == []


def test_await_no_deadline_scope_and_suppression():
    # off the data path → quiet
    assert lint_source(BAD_NO_DEADLINE,
                       "fluentbit_tpu/luart/interp.py") == []
    # a non-output class's reader loop → out of scope (functions NAMED
    # flush/_flush* stay in scope wherever they live)
    reader = BAD_NO_DEADLINE.replace(
        "class FooOutput(OutputPlugin):", "class FooReader:").replace(
        "async def flush(self, data, tag, engine):",
        "async def serve(self, data, tag, engine):")
    assert lint_source(reader, "fluentbit_tpu/plugins/in_x.py") == []
    # a justified unbounded await (long-poll reader) → suppressible
    src = BAD_NO_DEADLINE.replace(
        "        await self._writer.drain()",
        "        # server-push loop: unbounded by design\n"
        "        await self._writer.drain()"
        "  # fbtpu-lint: allow(await-no-deadline)")
    got = lint_source(src, "fluentbit_tpu/plugins/out_x.py")
    assert [f.rule for f in got] == ["await-no-deadline"]  # dial only


def test_await_no_deadline_module_level_flush_helpers():
    src = """
async def _flush_stream(writer, data):
    writer.write(data)
    await writer.drain()
"""
    got = lint_source(src, "fluentbit_tpu/plugins/out_y.py")
    assert rules(got) == ["await-no-deadline"]


# ---------------------------------------------------------------------
# dtype-narrowing
# ---------------------------------------------------------------------

def test_dtype_narrowing_fires_on_offsets():
    src = """
import numpy as np

def pack(offsets, lens):
    a = np.asarray(offsets, dtype=np.int32)
    b = offsets.astype(np.int32)
    c = np.cumsum(lens, dtype=np.int32)
    return a, b, c
"""
    got = lint_source(src, "fluentbit_tpu/plugins/filter_x.py")
    assert rules(got) == ["dtype-narrowing"] and len(got) == 3


def test_dtype_narrowing_quiet_on_bounded_values():
    src = """
import numpy as np

def pack(offsets, verdict, class_map):
    a = np.asarray(offsets, dtype=np.int64)   # wide is fine
    b = class_map.astype(np.int32)            # bounded domain
    c = verdict.astype(np.uint8)              # not offset-flavored
    return a, b, c
"""
    assert lint_source(src, "fluentbit_tpu/plugins/filter_x.py") == []


def test_dtype_narrowing_suppression():
    src = """
import numpy as np

def pack(offsets):
    # fbtpu-lint: allow(dtype-narrowing)
    return np.asarray(offsets, dtype=np.int32)
"""
    assert lint_source(src, "fluentbit_tpu/plugins/filter_x.py") == []


# ---------------------------------------------------------------------
# severity + JSON plumbing
# ---------------------------------------------------------------------

def test_findings_carry_severity_and_json_mode(tmp_path):
    bad = tmp_path / "fluentbit_tpu" / "plugins"
    bad.mkdir(parents=True)
    (bad / "x.py").write_text(BAD_DECLINE_SWALLOW)
    proc = subprocess.run(
        [sys.executable, "-m", "fluentbit_tpu.analysis", "--json",
         str(bad)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 1
    import json as _json

    data = _json.loads(proc.stdout)
    assert data and data[0]["rule"] == "decline-swallow"
    assert data[0]["severity"] == "warning"


# ---------------------------------------------------------------------
# batch-exactness: the fbtpu-flux commit surface (absorb_batch /
# absorb_events are state commits — a decline after them makes the
# decoded rerun double-aggregate the same records)
# ---------------------------------------------------------------------

BAD_FLUX_DECLINE_AFTER_ABSORB = """
class FluxLike:
    stateful_batch = True

    def can_process_batch(self):
        return True

    def process_batch(self, chunk):
        data = chunk.as_bytes()
        self.state.absorb_batch(chunk.n, self.mm, {}, {})
        cols = stage(data)
        if cols is None:
            return None
        return (chunk.n, data, chunk.n)
"""

GOOD_FLUX_COMMIT_LAST = """
class FluxLike:
    stateful_batch = True

    def can_process_batch(self):
        return True

    def process_batch(self, chunk):
        data = chunk.as_bytes()
        cols = stage(data)
        if cols is None:
            return None
        self.state.absorb_batch(chunk.n, self.mm, cols, {})
        return (chunk.n, data, chunk.n)
"""

BAD_FLUX_UNMARKED_STATEFUL = """
class FluxLike:
    def can_process_batch(self):
        return True

    def process_batch(self, chunk):
        cols = stage(chunk.as_bytes())
        if cols is None:
            return None
        self.state.absorb_events(cols)
        return (chunk.n, chunk.data, chunk.n)
"""


def test_flux_absorb_is_a_commit():
    got = lint_source(BAD_FLUX_DECLINE_AFTER_ABSORB,
                      "fluentbit_tpu/flux/fixture.py")
    assert "batch-decline-after-commit" in rules(got)


def test_flux_commit_last_quiet():
    assert lint_source(GOOD_FLUX_COMMIT_LAST,
                       "fluentbit_tpu/flux/fixture.py") == []


def test_flux_unmarked_stateful_fires():
    got = lint_source(BAD_FLUX_UNMARKED_STATEFUL,
                      "fluentbit_tpu/flux/fixture.py")
    assert "batch-stateful-unmarked" in rules(got)


def test_shipped_flux_plugin_passes_the_gate():
    # the real filter_flux must satisfy its own contract
    import fluentbit_tpu.flux.plugin as fp

    assert lint_paths([fp.__file__]) == []


# ---------------------------------------------------------------------
# qos-unmetered-ingest (fbtpu-qos metered-ingest invariant)
# ---------------------------------------------------------------------

_QOS_PATH = "fluentbit_tpu/core/ingest_fixture.py"

BAD_UNMETERED = """
class Engine:
    def ingest_fast(self, ins, tag, data):
        with ins.ingest_lock:
            return ins.pool.append(tag, data, 1)
"""

GOOD_METERED = """
class Engine:
    def ingest_fast(self, ins, tag, data):
        if self.qos.admit(ins, len(data)):
            return -1
        with ins.ingest_lock:
            return ins.pool.append(tag, data, 1)
"""


def test_unmetered_ingest_fires():
    got = lint_source(BAD_UNMETERED, _QOS_PATH)
    assert "qos-unmetered-ingest" in rules(got)


def test_metered_ingest_quiet():
    assert lint_source(GOOD_METERED, _QOS_PATH) == []


BAD_UNMETERED_INTERPROC = """
class Engine:
    def ingest_fast(self, ins, tag, data):
        return self._write(ins, tag, data)

    def _write(self, ins, tag, data):
        with ins.ingest_lock:
            return ins.pool.append(tag, data, 1)
"""

GOOD_METERED_INTERPROC = """
class Engine:
    def ingest_fast(self, ins, tag, data):
        if self.qos.admit(ins, len(data)):
            return -1
        return self._write(ins, tag, data)

    def _write(self, ins, tag, data):
        with ins.ingest_lock:
            return ins.pool.append(tag, data, 1)
"""


def test_unmetered_ingest_interprocedural():
    got = lint_source(BAD_UNMETERED_INTERPROC, _QOS_PATH)
    assert [f.rule for f in got] == ["qos-unmetered-ingest"]
    # the finding lands on the PUBLIC entry point, not the helper
    assert got[0].line == 3
    assert lint_source(GOOD_METERED_INTERPROC, _QOS_PATH) == []


def test_unmetered_ingest_private_only_quiet():
    # a private helper with no public caller is reachable only through
    # an admitted entry point in some other module — not flagged here
    helper_only = """
class Engine:
    def _write(self, ins, tag, data):
        with ins.ingest_lock:
            return ins.pool.append(tag, data, 1)
"""
    assert lint_source(helper_only, _QOS_PATH) == []


def test_unmetered_ingest_scope_and_suppression():
    # plugins ingest through Engine.input_*_append (already metered):
    # out of scope
    assert lint_source(BAD_UNMETERED,
                       "fluentbit_tpu/plugins/fixture.py") == []
    suppressed = BAD_UNMETERED.replace(
        "def ingest_fast(self, ins, tag, data):",
        "def ingest_fast(self, ins, tag, data):  "
        "# fbtpu-lint: allow(qos-unmetered-ingest) replay path, "
        "admitted at first ingest")
    assert lint_source(suppressed, _QOS_PATH) == []


def test_shipped_engine_ingest_is_metered():
    # the real entry points must keep calling qos.admit — deleting the
    # admission from input_log_append would fail THIS, not just the
    # behavior suite
    import fluentbit_tpu.core.engine as eng

    assert "qos-unmetered-ingest" not in rules(lint_paths([eng.__file__]))


NESTED_CLOSURE_METERED = """
class Engine:
    def ingest_batched(self, ins, tag, data):
        if self.qos.admit(ins, len(data)):
            return -1
        def flush(chunk):
            return ins.pool.append(tag, data, 1)
        return flush(data)
"""


def test_qos_rule_ignores_nested_closures():
    """A non-underscore closure inside a metered public function must
    not be flagged as its own unmetered entry point — the admit call
    lives in its container."""
    got = lint_source(NESTED_CLOSURE_METERED, _QOS_PATH)
    assert "qos-unmetered-ingest" not in rules(got), [
        f.message for f in got]


# ---------------------------------------------------------------------
# device-unguarded-dispatch (fbtpu-armor DeviceLane invariant)
# ---------------------------------------------------------------------

_DEV_PATH = "fluentbit_tpu/plugins/filter_fixture.py"

BAD_UNGUARDED_DISPATCH = """
class F:
    def process_batch(self, chunk):
        mask = self._program.dispatch_mesh(self._mesh, chunk.data, chunk.n)
        return mask
"""

GOOD_GUARDED_DISPATCH = """
class F:
    def process_batch(self, chunk):
        lane = self._lane()
        return lane.run(
            lambda: self._program.dispatch_mesh(self._mesh, chunk.data,
                                                chunk.n),
            lambda: self._host_mask(chunk.data, chunk.n),
        )
"""


def test_unguarded_dispatch_fires():
    got = lint_source(BAD_UNGUARDED_DISPATCH, _DEV_PATH)
    assert "device-unguarded-dispatch" in rules(got)


def test_guarded_dispatch_quiet():
    assert "device-unguarded-dispatch" not in rules(
        lint_source(GOOD_GUARDED_DISPATCH, _DEV_PATH))


BAD_UNGUARDED_INTERPROC = """
class F:
    def filter(self, events, tag, engine):
        return self._match(events)

    def _match(self, events):
        return self._program.match(self._batch, self._lengths)
"""

GOOD_GUARDED_INTERPROC = """
class F:
    def filter(self, events, tag, engine):
        return self._match(events)

    def _match(self, events):
        lane = self._lane()
        return lane.run(
            lambda: self._program.match(self._batch, self._lengths),
            lambda: self._host(events),
        )
"""


def test_unguarded_dispatch_interprocedural():
    got = lint_source(BAD_UNGUARDED_INTERPROC, _DEV_PATH)
    assert [f.rule for f in got] == ["device-unguarded-dispatch"]
    # the finding lands on the PUBLIC entry point, not the helper
    assert got[0].line == 3
    assert lint_source(GOOD_GUARDED_INTERPROC, _DEV_PATH) == []


def test_unguarded_dispatch_sharded_sketch_names():
    bad = """
def absorb(state, batch, lengths):
    sharded_hll_update(state.hll, state.mesh, batch, lengths)
"""
    got = lint_source(bad, "fluentbit_tpu/flux/fixture.py")
    assert "device-unguarded-dispatch" in rules(got)
    guarded = """
def absorb(lane, state, batch, lengths):
    return lane.run(
        lambda: sharded_hll_update(state.hll, state.mesh, batch,
                                   lengths),
        lambda: state.hll.host_update(batch, lengths),
    )
"""
    assert lint_source(guarded, "fluentbit_tpu/flux/fixture.py") == []


def test_unguarded_dispatch_scope_and_suppression():
    # ops/ is the kernel layer the lanes wrap: out of scope
    assert lint_source(BAD_UNGUARDED_DISPATCH,
                       "fluentbit_tpu/ops/fixture.py") == []
    suppressed = BAD_UNGUARDED_DISPATCH.replace(
        "def process_batch(self, chunk):",
        "def process_batch(self, chunk):  "
        "# fbtpu-lint: allow(device-unguarded-dispatch) "
        "diagnostic path, raw failure wanted")
    # (the launch-graph pack's structural undonated-buffer warning on
    # the bare dispatch_mesh site is a different rule and stays)
    assert "device-unguarded-dispatch" not in rules(
        lint_source(suppressed, _DEV_PATH))


def test_unguarded_dispatch_plain_match_needs_program_chain():
    # .match( on a non-program chain (a regex, a dict) is not a device
    # dispatch — the rule must not fire on everyday string matching
    benign = """
class F:
    def filter(self, events, tag, engine):
        return [e for e in events if self.regex.match(e.body)]
"""
    assert lint_source(benign, _DEV_PATH) == []


def test_shipped_device_planes_are_lane_guarded():
    # the real grep/rewrite_tag/flux device paths must keep their lane
    # wrapping — stripping DeviceLane from filter_grep would fail THIS,
    # not just the chaos suite
    import fluentbit_tpu.flux.kernels as fk
    import fluentbit_tpu.flux.state as fs
    import fluentbit_tpu.plugins.filter_grep as fg
    import fluentbit_tpu.plugins.filter_rewrite_tag as frt

    for mod in (fg, frt, fs, fk):
        assert "device-unguarded-dispatch" not in rules(
            lint_paths([mod.__file__])), mod.__name__


# ---------------------------------------------------------------------
# grep-unminimized-dfa (fbtpu-shrink minimizer invariant)
# ---------------------------------------------------------------------

_SHRINK_PATH = "fluentbit_tpu/plugins/filter_fixture.py"

BAD_RAW_DFA_TO_TABLES = """
import numpy as np


class F:
    def init(self, instance, engine):
        dfa = DFA(trans=np.zeros((2, 2), np.int32),
                  class_map=np.zeros(257, np.uint8),
                  start=0, n_states=2, n_classes=2, pattern="x")
        self._tables = GrepTables([(b"log", dfa)])
"""

BAD_UNMINIMIZED_COMPILE = """
class F:
    def init(self, instance, engine):
        self._program = GrepProgram(
            [compile_dfa(p, minimize=False) for p in self.patterns], 512)
"""

GOOD_MINIMIZED_COMPILE = """
class F:
    def init(self, instance, engine):
        self._program = GrepProgram(
            [compile_dfa(p) for p in self.patterns], 512)
        self._tables = GrepTables(
            [(b"log", compile_dfa(p)) for p in self.patterns])
"""


def test_unminimized_dfa_raw_construction_fires():
    got = lint_source(BAD_RAW_DFA_TO_TABLES, _SHRINK_PATH)
    assert "grep-unminimized-dfa" in rules(got)


def test_unminimized_dfa_minimize_false_fires():
    got = lint_source(BAD_UNMINIMIZED_COMPILE, _SHRINK_PATH)
    assert "grep-unminimized-dfa" in rules(got)


def test_minimized_compile_quiet():
    assert "grep-unminimized-dfa" not in rules(
        lint_source(GOOD_MINIMIZED_COMPILE, _SHRINK_PATH))


def test_unminimized_dfa_interprocedural():
    # the source hides in a same-module helper; the sink lives in the
    # caller — the closure still connects them
    bad = """
class F:
    def init(self, instance, engine):
        self._tables = GrepTables(self._rules())

    def _rules(self):
        return [(b"log", compile_dfa("x", minimize=False))]
"""
    got = lint_source(bad, _SHRINK_PATH)
    assert "grep-unminimized-dfa" in rules(got)


def test_unminimized_dfa_scope_and_suppression():
    # regex/ is the definition site (the minimizer builds raw tables)
    assert lint_source(BAD_RAW_DFA_TO_TABLES,
                       "fluentbit_tpu/regex/fixture.py") == []
    suppressed = BAD_UNMINIMIZED_COMPILE.replace(
        "[compile_dfa(p, minimize=False) for p in self.patterns], 512)",
        "[compile_dfa(p, minimize=False)  "
        "# fbtpu-lint: allow(grep-unminimized-dfa) differential\n"
        "             for p in self.patterns], 512)")
    assert "grep-unminimized-dfa" not in rules(
        lint_source(suppressed, _SHRINK_PATH))


def test_unminimized_dfa_source_without_sink_quiet():
    # compiling an unminimized DFA for a NON-kernel purpose (a property
    # test oracle, a doc example) is not the bug class
    benign = """
def oracle(pattern):
    return compile_dfa(pattern, minimize=False)
"""
    assert "grep-unminimized-dfa" not in rules(
        lint_source(benign, _SHRINK_PATH))


def test_shipped_kernel_paths_use_minimized_dfas():
    # the real program/table builders must stay on the compile_dfa
    # default path — wiring minimize=False into filter_grep would fail
    # THIS, not just a bench round three PRs later
    import fluentbit_tpu.ops.grep as og
    import fluentbit_tpu.plugins.filter_grep as fg
    import fluentbit_tpu.plugins.filter_parser as fp

    for mod in (og, fg, fp):
        assert "grep-unminimized-dfa" not in rules(
            lint_paths([mod.__file__])), mod.__name__


# ---------------------------------------------------------------------
# the census of environment switches (ROADMAP D4)
# ---------------------------------------------------------------------

#: every ``FBTPU_*`` name the package reads or documents. A new one is
#: an option: justify it by two callers that need different values, or
#: derive the value from what the program observes (ROADMAP D4); one
#: that goes is deleted here with its last use.
FBTPU_NAMES = frozenset("""
FBTPU_ACCEL FBTPU_ATTACH_BACKOFF_S FBTPU_ATTACH_RETRIES
FBTPU_ATTACH_WAIT_S FBTPU_COPY_WITNESS FBTPU_DEVICE_BREAKER_COOLDOWN
FBTPU_DEVICE_BREAKER_FAILURES FBTPU_DEVICE_REGROW_AFTER
FBTPU_DSO_API_PROBE FBTPU_FAILPOINTS FBTPU_FAILPOINTS_HTTP
FBTPU_FAILPOINTS_SEED FBTPU_FLUX_MESH FBTPU_FLUX_SQL FBTPU_K4_BUDGET
FBTPU_KTABLE_BUDGET FBTPU_LAUNCH_DEADLINE_S FBTPU_LOCK_WITNESS
FBTPU_MESH FBTPU_MESH_RULE_SHARD_R FBTPU_NO_NATIVE FBTPU_NO_SIDECAR
FBTPU_PLUGIN_ABI_VERSION FBTPU_SEGMENT_RECORDS FBTPU_STAGE_THREADS
""".split())


def test_environment_switch_census():
    import re

    seen = set()
    for root, dirs, files in os.walk(os.path.join(REPO, "fluentbit_tpu")):
        dirs[:] = [d for d in dirs if d not in ("__pycache__", "build")]
        for name in files:
            if name.endswith((".pyc", ".so", ".o")):
                continue
            with open(os.path.join(root, name), errors="ignore") as f:
                seen.update(re.findall(r"FBTPU_[A-Z0-9_]+", f.read()))
    assert len(FBTPU_NAMES) == 25
    assert seen == FBTPU_NAMES, (sorted(seen - FBTPU_NAMES),
                                 sorted(FBTPU_NAMES - seen))
