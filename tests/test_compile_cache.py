"""Persistent compilation cache wiring (hyperspace_tpu/compile_cache.py).

The contract of ISSUE 13 pillar 1: run #2 of the same program shapes
with the same ``compile_cache_dir`` deserializes executables instead of
re-invoking XLA — proven HERE as a real subprocess pair through the
serve CLI (the telemetry summary carries ``ctr/jax/compile_cache_hit``
and the compile counters), with the cache-disabled path bit-identical
and a bad directory a clean usage error."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from hyperspace_tpu import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_activation_state():
    """The cache state is process-global and other suites legitimately
    activate it in-process (the bench CLI contract tests call
    bench.main()) — these tests assert on activation state, so they
    start and end deactivated (deactivate restores whatever config the
    prior activation replaced, so the harness's own cache survives)."""
    compile_cache.deactivate()
    yield
    compile_cache.deactivate()


# --- resolution rules (pure, no jax) -----------------------------------------


def test_resolution_precedence(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.delenv(compile_cache.JAX_ENV_VAR, raising=False)
    # default ON, under the repo's .cache
    d = compile_cache.resolve_dir(None)
    assert d is not None and d.endswith(os.path.join(".cache", "jax_compile"))
    # env overrides the default; flag overrides the env
    monkeypatch.setenv(compile_cache.ENV_VAR, "/env/dir")
    assert compile_cache.resolve_dir(None) == "/env/dir"
    assert compile_cache.resolve_dir("/flag/dir") == "/flag/dir"
    # 0 disables at either level
    assert compile_cache.resolve_dir("0") is None
    monkeypatch.setenv(compile_cache.ENV_VAR, "0")
    assert compile_cache.resolve_dir(None) is None
    # an explicit flag still wins over a disabling env
    assert compile_cache.resolve_dir("/flag/dir") == "/flag/dir"


def test_jax_env_var_decides_where(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, that is where the cache
    lives: no flag, own env var or default names another directory
    (a disagreeing path is an error, not a second cache), and only
    the off values still apply."""
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.setenv(compile_cache.JAX_ENV_VAR, "/jax/dir")
    assert compile_cache.resolve_dir(None) == "/jax/dir"
    assert compile_cache.resolve_dir("/jax/dir") == "/jax/dir"
    with pytest.raises(ValueError, match="disagrees"):
        compile_cache.resolve_dir("/flag/dir")
    monkeypatch.setenv(compile_cache.ENV_VAR, "/env/dir")
    with pytest.raises(ValueError, match="disagrees"):
        compile_cache.resolve_dir(None)
    monkeypatch.setenv(compile_cache.ENV_VAR, "0")
    assert compile_cache.resolve_dir(None) is None
    assert compile_cache.resolve_dir("off") is None


def test_off_spellings():
    for v in ("0", "false", "no", "off", "OFF", " 0 "):
        assert compile_cache.resolve_dir(v) is None


def test_disable_switches_a_configured_cache_off(tmp_path):
    """compile_cache_dir=0 must switch the cache off even where jax was
    already given a directory (JAX_COMPILATION_CACHE_DIR does that
    before any code runs), and deactivate restores what it found."""
    import jax

    prev = jax.config.jax_compilation_cache_dir  # the suite's own cache
    assert prev is not None
    assert compile_cache.activate("0") is None
    assert jax.config.jax_compilation_cache_dir is None
    assert not compile_cache.is_enabled()
    compile_cache.deactivate()
    assert jax.config.jax_compilation_cache_dir == prev


def test_bad_dir_is_a_clean_error(tmp_path):
    f = tmp_path / "a_file"
    f.write_text("not a directory")
    with pytest.raises(ValueError, match="compile_cache_dir"):
        compile_cache.activate(str(f))
    assert not compile_cache.is_enabled()


def test_activate_points_jax_and_deactivate_unpoints(tmp_path):
    import jax

    prev = jax.config.jax_compilation_cache_dir  # the suite's own cache
    try:
        d = compile_cache.activate(str(tmp_path / "cc"))
        assert d == str(tmp_path / "cc") and os.path.isdir(d)
        assert compile_cache.is_enabled()
        assert jax.config.jax_compilation_cache_dir == d
        # a jitted call lands entries on disk (the cache-everything
        # policy: even a trivial sub-second executable persists)
        import jax.numpy as jnp

        jax.jit(lambda x: x * 3 + 1)(jnp.ones((4, 4))).block_until_ready()
        files = [os.path.join(r, f) for r, _, fs in os.walk(d) for f in fs]
        assert files, "no cache entries written"
    finally:
        compile_cache.deactivate()
    assert not compile_cache.is_enabled()
    # deactivate RESTORES the pre-activation config (the test harness
    # points the suite at its own cache — blanking it would slow every
    # test after this one), it does not blank it
    assert jax.config.jax_compilation_cache_dir == prev


# --- the subprocess pair (the ISSUE's acceptance shape) ----------------------


def _query(art: str, cache_dir: str, how: str = "flag"):
    """One serve-CLI query subprocess → (stdout record, telemetry ctrs).
    ``how`` names the cache directory through the CLI flag or through
    ``JAX_COMPILATION_CACHE_DIR`` (no flag at all)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop(compile_cache.ENV_VAR, None)
    env.pop(compile_cache.JAX_ENV_VAR, None)
    extra = [f"compile_cache_dir={cache_dir}"]
    if how == "env":
        env[compile_cache.JAX_ENV_VAR] = cache_dir
        extra = []
    res = subprocess.run(
        [sys.executable, "-m", "hyperspace_tpu.cli.serve", "query",
         f"artifact={art}", "ids=0,1,2", "k=3", "telemetry=1", *extra],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=240)
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    summary = None
    for line in res.stderr.strip().splitlines():
        try:
            doc = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(doc, dict) and "telemetry_summary" in doc:
            summary = doc["telemetry_summary"]
    assert summary is not None, res.stderr[-2000:]
    return out, summary


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    from hyperspace_tpu.serve import export_artifact

    rng = np.random.default_rng(0)
    table = np.tanh(rng.standard_normal((96, 6)).astype(np.float32) * 0.3) * 0.7
    out = str(tmp_path_factory.mktemp("cc") / "artifact")
    export_artifact(out, table, ("poincare", 1.0), model_config={"c": 1.0})
    return out


@pytest.mark.parametrize("how", ["flag", "env"])
def test_subprocess_pair_hits_and_disabled_bitwise(tmp_path, artifact, how):
    cache = str(tmp_path / "cc")
    out1, t1 = _query(artifact, cache, how)
    # the entries landed in the named directory
    assert os.listdir(cache)
    # run #1: a cold cache has nothing to hit, and every compile missed
    # into it (entries written)
    assert t1.get("ctr/jax/compile_cache_hit", 0) == 0
    assert t1.get("ctr/jax/compile_cache_miss", 0) > 0
    assert t1.get("ctr/jax/recompiles", 0) > 0

    out2, t2 = _query(artifact, cache, how)
    # run #2, same dir: executables deserialize — hits recorded, fewer
    # misses, and LOWER compile counters (this jax times the hit's
    # deserialization under the same backend_compile event, so
    # recompiles stays <= while compile_s collapses — the honest win)
    assert t2.get("ctr/jax/compile_cache_hit", 0) > 0
    assert (t2.get("ctr/jax/compile_cache_miss", 0)
            < t1["ctr/jax/compile_cache_miss"])
    assert t2.get("ctr/jax/recompiles", 0) <= t1["ctr/jax/recompiles"]
    assert t2.get("ctr/jax/compile_s", 0) < t1["ctr/jax/compile_s"]
    # cached answers are the same executables: identical results
    assert out2 == out1

    out3, t3 = _query(artifact, "0")
    # cache-disabled path: no cache counters at all, results
    # bit-identical to the cached runs (tolist round-trips f32 exactly)
    assert "ctr/jax/compile_cache_hit" not in t3
    assert "ctr/jax/compile_cache_miss" not in t3
    assert out3 == out1


def test_subprocess_bad_dir_clean_error(tmp_path, artifact):
    f = tmp_path / "occupied"
    f.write_text("file, not dir")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, "-m", "hyperspace_tpu.cli.serve", "query",
         f"artifact={artifact}", "ids=0", "k=1",
         f"compile_cache_dir={f}"],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=240)
    assert res.returncode != 0
    assert "compile_cache_dir" in res.stderr
    assert "Traceback" not in res.stderr
