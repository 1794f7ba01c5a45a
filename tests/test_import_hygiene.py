"""`import paddle_tpu` leaves the process's backend choice alone.

Run in fresh interpreters: the package must not set JAX_PLATFORMS, update
`jax_platforms`, initialise or edit jax's backends, or place the compile
cache anywhere but where it is told (JAX_COMPILATION_CACHE_DIR) or, unset,
at the fixed `<checkout>/.jax_cache`.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROBE = """
import json, os, sys
sys.path.insert(0, %r)
import jax
import jax._src.xla_bridge as xb
before = dict(env=os.environ.get("JAX_PLATFORMS"),
              platforms=jax.config.jax_platforms,
              cache=jax.config.jax_compilation_cache_dir,
              backends=sorted(xb._backends),
              factories=sorted(xb._backend_factories))
import paddle_tpu
after = dict(env=os.environ.get("JAX_PLATFORMS"),
             platforms=jax.config.jax_platforms,
             cache=jax.config.jax_compilation_cache_dir,
             backends=sorted(xb._backends),
             factories=sorted(xb._backend_factories))
print(json.dumps([before, after]))
""" % REPO


def _probe(env_overrides):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR",
                        "JAX_ENABLE_COMPILATION_CACHE")}
    env.update(env_overrides)
    out = subprocess.run([sys.executable, "-c", PROBE], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("platforms", [None, "cpu"])
def test_import_leaves_backend_choice_untouched(platforms, tmp_path):
    cache = str(tmp_path / "xla_cache")
    env = {"JAX_COMPILATION_CACHE_DIR": cache}
    if platforms is not None:
        env["JAX_PLATFORMS"] = platforms
    before, after = _probe(env)
    assert after == before
    assert after["env"] == platforms
    assert after["backends"] == []          # no backend was initialised
    assert after["cache"] == cache          # placed from outside, untouched
    assert not os.path.exists(os.path.join(REPO, ".jax_cache", "xla_cache"))


def test_unset_cache_dir_is_fixed_inside_the_checkout():
    before, after = _probe({"JAX_PLATFORMS": "cpu"})
    assert before["cache"] is None
    assert after["cache"] == os.path.join(REPO, ".jax_cache")
    assert {k: v for k, v in after.items() if k != "cache"} == \
        {k: v for k, v in before.items() if k != "cache"}
