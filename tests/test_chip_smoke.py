"""chip_smoke.py and the compile-cache rule, as far as a CPU box can pin them.

The smoke's real run needs a TPU (the driver runs it there); what tier-1
holds is the refusal contract — no TPU means a non-zero exit, a message
naming the missing TPU, no result line, and nothing compiled — and the one
cache rule every process of the repo follows (runtime/compile_cache.py).
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def run_smoke(tmp_path, **env_extra):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("AATPU_PALLAS")}
    # a fresh cache directory: "compiled nothing" is then "wrote nothing"
    env.update(JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               **env_extra)
    return subprocess.run([sys.executable, SMOKE], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=120)


def test_no_tpu_fails_before_compiling_anything(tmp_path):
    r = run_smoke(tmp_path)
    assert r.returncode != 0
    assert "no TPU" in r.stderr and "'cpu'" in r.stderr
    assert r.stdout.strip() == ""  # no result line, nothing to mistake
    cache = tmp_path / "cache"
    assert not cache.exists() or not any(cache.iterdir())


def test_kernel_switch_in_the_environment_is_refused(tmp_path):
    r = run_smoke(tmp_path, AATPU_PALLAS_FLASH_ATTENTION="0")
    assert r.returncode != 0
    assert "AATPU_PALLAS_FLASH_ATTENTION" in r.stderr
    assert r.stdout.strip() == ""


def _cache_dir_in_child(env_overrides):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", **env_overrides)
    code = ("import json; "
            "from akka_allreduce_tpu.runtime.compile_cache import "
            "CHECKOUT_CACHE_DIR, enable_compile_cache; "
            "print(json.dumps([enable_compile_cache(), "
            "CHECKOUT_CACHE_DIR]))")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout
    return json.loads(out.strip().splitlines()[-1])


def test_cache_dir_is_the_variable_when_set(tmp_path):
    used, _ = _cache_dir_in_child(
        {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert used == str(tmp_path)


def test_cache_dir_is_fixed_inside_the_checkout_when_unset():
    used, fixed = _cache_dir_in_child({})
    assert used == fixed == os.path.join(REPO, ".jax_cache")
