"""``enable_compile_cache`` places JAX's persistent cache: where
``JAX_COMPILATION_CACHE_DIR`` says, else at one fixed path in the
checkout.  Each case runs in a fresh interpreter, as an entry point
would."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

PROBE = """
import json
import jax, jax.numpy as jnp
from repro.launch.compile_cache import CHECKOUT_CACHE, enable_compile_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
path = enable_compile_cache()
if COMPILE:
    jax.jit(lambda x: x * 3.0 + 1.0)(jnp.arange(5.0)).block_until_ready()
print(json.dumps({"path": path, "jax": jax.config.jax_compilation_cache_dir,
                  "checkout": str(CHECKOUT_CACHE)}))
"""


def _probe(cache_dir: str | None, compile_: bool) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    out = subprocess.run(
        [sys.executable, "-c", f"COMPILE = {compile_}\n" + PROBE], env=env,
        cwd=ROOT, capture_output=True, text=True, timeout=120, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def _entries(path: Path) -> set[str]:
    return set(os.listdir(path)) if path.is_dir() else set()


def test_cache_goes_where_the_environment_says(tmp_path):
    checkout = ROOT / ".jax_cache"
    before = _entries(checkout)
    got = _probe(str(tmp_path), compile_=True)
    assert got["path"] == got["jax"] == str(tmp_path)
    assert _entries(tmp_path), "the compile was not cached there"
    assert _entries(checkout) == before


def test_cache_defaults_to_one_fixed_path_in_the_checkout():
    first, second = (_probe(None, compile_=False) for _ in range(2))
    assert first == second
    assert first["path"] == first["jax"] == str(ROOT / ".jax_cache")
