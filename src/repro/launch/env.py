"""Host-level launch set-up: allocator, log levels and the compile cache.

TPU training repositories commonly start their launchers from a shell
script that preloads tcmalloc and quiets the TF/XLA host logging before
python starts. We do the equivalent in-process so
``python -m repro.launch.serve_loop`` needs no wrapper:

  * env flags (``TF_CPP_MIN_LOG_LEVEL=4``,
    ``TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD``) are set if absent --
    these are read at backend init, which is lazy, so setting them at
    the top of ``main()`` is early enough;
  * ``REPRO_XLA_HOST_DEVICES=N`` (explicit opt-in for fake CPU devices,
    ``--xla_force_host_platform_device_count``) is appended to
    ``XLA_FLAGS`` -- never set implicitly, because the fake-device count
    locks at first jax init and tests own that knob;
  * tcmalloc's ``LD_PRELOAD`` only takes effect at process start, so
    when a known tcmalloc exists and the process was not already
    preloaded, the CLI entry points re-exec themselves once
    (``reexec=True``; guarded by a marker env var). The re-exec happens
    in the ``__main__`` block, before anything touches a JAX backend, so
    the replaced process never held a device. Library callers and tests
    use ``reexec=False``: flags only, never a re-exec.

Opt-out: ``REPRO_NO_ENV_HARDEN=1`` makes ``harden_host_env`` a no-op.

``enable_compile_cache`` turns on JAX's persistent compilation cache for
the entry points (tests stay off it; see ``tests/conftest.py``).
"""
from __future__ import annotations

import os
import sys
from pathlib import Path
from typing import Dict, Optional

_TCMALLOC_CANDIDATES = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib/libtcmalloc.so.4",
)
_MARKER = "REPRO_ENV_HARDENED"

CACHE_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
MIN_COMPILE_ENV_VAR = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"
# A fixed path inside the checkout: the cache key includes nothing about
# the directory, but a directory that moves between runs never hits.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_DEFAULT_FLAGS = {
    "TF_CPP_MIN_LOG_LEVEL": "4",                    # silence TF host stack
    "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD": "60000000000",
}


def find_tcmalloc() -> Optional[str]:
    for path in _TCMALLOC_CANDIDATES:
        if os.path.exists(path):
            return path
    return None


def harden_host_env(*, reexec: bool = False,
                    environ: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """Apply the launch-hardening env. Returns {name: value} of every
    variable this call actually set (empty when opted out or nothing was
    missing). ``environ`` defaults to ``os.environ`` (injectable for
    tests). With ``reexec=True`` (CLI ``__main__`` blocks ONLY -- never
    from a library/test, it replaces the process image) the process
    re-execs once with tcmalloc preloaded when available."""
    env = os.environ if environ is None else environ
    if env.get("REPRO_NO_ENV_HARDEN") == "1":
        return {}
    applied: Dict[str, str] = {}
    for name, value in _DEFAULT_FLAGS.items():
        if name not in env:
            env[name] = value
            applied[name] = value
    ndev = env.get("REPRO_XLA_HOST_DEVICES")
    if ndev:
        flag = f"--xla_force_host_platform_device_count={int(ndev)}"
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = f"{flags} {flag}".strip()
            applied["XLA_FLAGS"] = env["XLA_FLAGS"]

    tcmalloc = find_tcmalloc()
    if tcmalloc and tcmalloc not in env.get("LD_PRELOAD", "") \
            and _MARKER not in env:
        preload = " ".join(p for p in (env.get("LD_PRELOAD"), tcmalloc) if p)
        env["LD_PRELOAD"] = preload
        env[_MARKER] = "1"
        applied["LD_PRELOAD"] = preload
        if reexec and environ is None:
            # LD_PRELOAD is consumed by the dynamic loader at process
            # start; apply it by replacing this process once (marker
            # guards against loops)
            os.execv(sys.executable, [sys.executable] + sys.argv)
    return applied


def enable_compile_cache(environ: Optional[Dict[str, str]] = None) -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
    itself and no other directory is set here; otherwise the cache goes
    to ``DEFAULT_CACHE_DIR`` (``<checkout>/.jax_cache``). Every program
    is kept, however fast it compiled, unless
    ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` says otherwise: JAX's
    default keeps only programs that took a second or more, and the
    kernels and small step programs of a start-up each take less. Call
    before the first compile."""
    import jax

    env = os.environ if environ is None else environ
    if not env.get(MIN_COMPILE_ENV_VAR):
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if env.get(CACHE_ENV_VAR):
        return env[CACHE_ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
