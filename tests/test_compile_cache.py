"""The one place the persistent compile cache is configured."""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = (
    "import sys; sys.path.insert(0, %r)\n"
    "import jax, json\n"
    "from torchsnapshot_tpu.compile_cache import enable_compilation_cache\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "path = enable_compilation_cache()\n"
    "print(json.dumps([before, path, jax.config.jax_compilation_cache_dir,\n"
    "      jax.config.jax_persistent_cache_min_compile_time_secs,\n"
    "      jax.config.jax_include_full_tracebacks_in_locations]))\n" % REPO
)


def _probe(env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run(
        [sys.executable, "-c", _PROBE], env=env, check=True,
        capture_output=True, text=True, timeout=120,
    ).stdout
    return json.loads(out.strip().splitlines()[-1])


def test_a_set_cache_dir_is_left_alone(tmp_path):
    placed = str(tmp_path / "placed")
    before, returned, after, floor, full_tb = _probe(placed)
    assert before == returned == after == placed
    assert floor == 0 and full_tb is False


def test_unset_it_is_the_fixed_checkout_path():
    before, returned, after, _, _ = _probe(None)
    assert before is None  # importing the library set nothing
    assert returned == after == os.path.join(REPO, ".jax_cache")


def test_importing_the_library_sets_no_cache():
    code = (
        "import sys; sys.path.insert(0, %r); import jax, torchsnapshot_tpu; "
        "assert jax.config.jax_compilation_cache_dir is None" % REPO
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    subprocess.run([sys.executable, "-c", code], env=env, check=True, timeout=120)


def test_only_the_helper_and_its_callers_name_the_cache():
    """``grep -rn compilation_cache`` finds the helper, calls to it, and
    the environment variable a test or the smoke passes through."""
    offenders = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".") and d not in ("chiprun_out", "build")]
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            rel = os.path.relpath(path, REPO)
            if rel in ("torchsnapshot_tpu/compile_cache.py", "tests/test_compile_cache.py"):
                continue
            with open(path) as f:
                for n, line in enumerate(f, 1):
                    if "compilation_cache" not in line.lower():
                        continue
                    allowed = (
                        "enable_compilation_cache" in line
                        or "JAX_COMPILATION_CACHE_DIR" in line
                        or "/jax/compilation_cache/" in line  # jax.monitoring event names
                    )
                    if not allowed:
                        offenders.append(f"{rel}:{n}: {line.strip()}")
    assert not offenders, offenders
