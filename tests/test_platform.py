"""What the platform decides: the kernel path, and where compiles are cached."""
import os
import subprocess
import sys

from repro.config import RuntimeConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_runtime_config_resolves_to_xla_reference_on_cpu():
    rcfg = RuntimeConfig()
    assert not rcfg.use_pallas and not rcfg.interpret
    # an explicit choice (kernel tests, reference runs) still wins
    assert RuntimeConfig(use_pallas=True, interpret=True).use_pallas


def _cache_dir(env_dir):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    code = ("import jax; from repro.launch.compile_cache import "
            "enable_compile_cache; d = enable_compile_cache(); "
            "print(d); print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                         capture_output=True, check=True, timeout=120)
    return out.stdout.split()


def test_compile_cache_follows_env_else_fixed_checkout_dir(tmp_path):
    assert _cache_dir(str(tmp_path)) == [str(tmp_path)] * 2
    fixed = os.path.join(REPO, ".jax_cache")
    assert _cache_dir(None) == [fixed] * 2
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
