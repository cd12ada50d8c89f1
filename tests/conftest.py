import os
import subprocess
import sys

import pytest

# Tests pin the CPU: Pallas kernels run in interpret mode there, and the
# TPU path is exercised by ``chip_smoke.py`` on the chip and by the
# described-topology compiles of ``tests/test_tpu_compile.py``.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

# ... and stay off the persistent compilation cache that the entry
# points (``launch.env.enable_compile_cache``) turn on.
jax.config.update("jax_enable_compilation_cache", False)


def run_py_subprocess(code: str, devices: int = 8, timeout: int = 600):
    """Run python code in a subprocess with N fake XLA host devices.

    Multi-device tests need this because jax locks the device count at
    first init; the main pytest process keeps the default single device
    (per the dry-run isolation requirement)."""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
        "PYTHONPATH": os.path.join(repo_root, "src"),
        "PATH": os.environ.get("PATH", "/usr/bin:/bin:/usr/local/bin"),
        "HOME": os.environ.get("HOME", "/root"),
        # the child runs on the same pinned platform as the tests
        "JAX_PLATFORMS": os.environ["JAX_PLATFORMS"],
    }
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env, cwd=repo_root)
    if r.returncode != 0:
        raise AssertionError(f"subprocess failed:\nSTDOUT:{r.stdout}\nSTDERR:{r.stderr}")
    return r.stdout


@pytest.fixture
def subproc():
    return run_py_subprocess


@pytest.fixture(autouse=True)
def _trace_counts_end_with_the_test():
    """What a test ticks in the process-global ``TRACE_COUNTS`` ends with
    it: test files share worker processes, and the benchmark's harness
    reads the fallback counters as absolute counts (bench/tests)."""
    from repro.kernels.registry import TRACE_COUNTS

    saved = dict(TRACE_COUNTS)
    yield
    TRACE_COUNTS.clear()
    TRACE_COUNTS.update(saved)
