"""The persistent compile cache: one fixed directory, read by a later process.

``enable_compile_cache`` leaves ``JAX_COMPILATION_CACHE_DIR`` to JAX when it
is set and otherwise uses ``<checkout>/.jax_cache``.  Each case runs two
fresh processes (CPU) that compile the same program: the first must write the
directory, the second must hit it.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.launch import compile_cache

_ROOT = Path(__file__).resolve().parents[1]

_CHILD = textwrap.dedent(
    """
    import json, sys
    from collections import Counter
    from pathlib import Path
    import jax, jax.numpy as jnp
    from repro.launch import compile_cache

    events = Counter()
    jax.monitoring.register_event_listener(
        lambda e, **_: events.update([e.rsplit("/", 1)[-1]]))
    if sys.argv[1]:  # stand-in for the checkout, so the test writes no repo file
        compile_cache.CHECKOUT_CACHE_DIR = Path(sys.argv[1])
    where = compile_cache.enable_compile_cache()
    jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((8, 8))).block_until_ready()
    print(json.dumps({"dir": where, "hits": events["cache_hits"],
                      "misses": events["cache_misses"]}))
    """
)


def _run(env_dir, checkout_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(_ROOT / "src"))
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", _CHILD, str(checkout_dir or "")], env=env,
        capture_output=True, text=True, timeout=300, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_checkout_cache_dir_is_fixed_and_ignored():
    assert compile_cache.CHECKOUT_CACHE_DIR == _ROOT / ".jax_cache"
    ignored = (_ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


@pytest.mark.parametrize("source", ["env", "checkout"])
def test_second_process_reads_the_cache(tmp_path, source):
    env_dir = tmp_path / "env" if source == "env" else None
    checkout_dir = tmp_path / "checkout"
    want = env_dir or checkout_dir
    first = _run(env_dir, checkout_dir)
    assert first["dir"] == str(want)
    assert first["misses"] > 0 and first["hits"] == 0
    assert any(want.iterdir())
    if env_dir is not None:  # the variable wins: nothing lands elsewhere
        assert not checkout_dir.exists()
    second = _run(env_dir, checkout_dir)
    assert second["hits"] == first["misses"] and second["misses"] == 0
