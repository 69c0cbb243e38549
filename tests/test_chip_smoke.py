"""chip_smoke.py and the launchers around it, from the no-chip side.

What only the chip can show (``ok: true``) is shown there; here: the
script refuses a CPU at once and alone in a directory, the compile
cache goes where it is told, and no launcher's parent process
initialises a backend that a child would need.
"""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, cwd=REPO, timeout=120, **env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("XLA_FLAGS", None)  # conftest's 8 virtual devices: not here
    t0 = time.time()
    proc = subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)
    return proc, time.time() - t0


def test_chip_smoke_refuses_a_cpu_before_building_a_corpus():
    proc, took = _run([os.path.join(REPO, "chip_smoke.py")])
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    last = json.loads(lines[-1])
    assert proc.returncode != 0
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert "no accelerator" in last["error"]
    stages = [json.loads(ln).get("stage") for ln in lines[:-1]]
    assert stages == ["attach"], stages  # stopped before any corpus
    assert took < 30, f"took {took:.1f}s"  # ~4 s alone; workers share cores


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc, _ = _run(["chip_smoke.py"], cwd=str(tmp_path))
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert "fluentbit_tpu" in last["error"]


def test_chip_smoke_sets_no_platform_and_no_flags():
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        src = f.read()
    for name in ("JAX_PLATFORMS", "XLA_FLAGS", "jax_platforms"):
        assert name not in src.split('"""', 2)[2], name


def test_launcher_parents_never_initialise_a_backend():
    """A chip belongs to one process: the supervisor forks the worker,
    and must not have touched a backend by then (importing jax is fine,
    initialising is not)."""
    code = (
        "import sys\n"
        "import fluentbit_tpu.__main__, fluentbit_tpu.supervisor\n"
        "from fluentbit_tpu.__main__ import main\n"
        "assert main(['--supervisor', '--help']) in (0, 1)\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "print('clean')\n")
    proc, _ = _run(["-c", code])
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip().endswith("clean")


@pytest.mark.parametrize("env_dir,platform,want", [
    (None, "tpu", "checkout"),
    (None, "cpu", "untouched"),
    ("/some/dir", "tpu", "untouched"),
    ("/some/dir", "cpu", "untouched"),
], ids=["chip-default", "cpu-default", "chip-env", "cpu-env"])
def test_compile_cache_is_placed_once_and_from_outside(monkeypatch, env_dir,
                                                       platform, want):
    """JAX_COMPILATION_CACHE_DIR set: no directory is set in code. Unset:
    one fixed path inside the checkout for an accelerator, none for the
    CPU backend. The thresholds drop whenever a cache is in play."""
    import jax

    from fluentbit_tpu.ops import device

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        jax.config.update("jax_compilation_cache_dir", "sentinel")
        device._configure_compile_cache(platform)
        got = jax.config.jax_compilation_cache_dir
        if want == "checkout":
            assert got == os.path.join(REPO, ".jax_cache")
            assert got == device.COMPILE_CACHE_DIR
        else:
            assert got == "sentinel"
        lowered = jax.config.jax_persistent_cache_min_compile_time_secs == 0
        assert lowered == (env_dir is not None or platform != "cpu")
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)


def test_compile_cache_dir_is_ignored_by_git():
    with open(os.path.join(REPO, ".gitignore")) as f:
        ignored = f.read().split()
    assert ".jax_cache/" in ignored and "chiprun_out/" in ignored
