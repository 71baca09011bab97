"""Entry-point behaviour that needs a fresh interpreter: where the persistent
compilation cache lands, and that ``repro.launch.serve --smoke`` serves on
the CPU."""
import os
import subprocess
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run(args, env_update, drop=()):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    for k in drop:
        env.pop(k, None)
    env.update(env_update)
    return subprocess.run([sys.executable] + args, env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=600)


def test_serve_smoke_writes_cache_where_env_says(tmp_path):
    cache = tmp_path / "cache"
    proc = _run(["-m", "repro.launch.serve", "--smoke", "--ls",
                 "stablelm-1.6b", "--be", "stablelm-1.6b", "--requests", "1",
                 "--max-new", "2", "--paged", "--use-flash",
                 "--chunk-size", "4"],
                {"JAX_COMPILATION_CACHE_DIR": str(cache),
                 "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "engine quanta executed" in proc.stdout
    assert any(cache.iterdir())


def test_compile_cache_defaults_to_repo_dir():
    script = (
        "import jax\n"
        "from repro.launch.compile_cache import use_compile_cache\n"
        "print(use_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n")
    proc = _run(["-c", script], {}, drop=("JAX_COMPILATION_CACHE_DIR",))
    assert proc.returncode == 0, proc.stderr[-3000:]
    want = os.path.join(REPO, ".jax_cache")
    assert proc.stdout.split() == [want, want]
