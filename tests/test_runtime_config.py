"""repro.runtime_config: precedence, flag merging, and backend-init
ordering.

The pure half (``resolve`` / ``merge_xla_flags`` / ``_parse_bool``) runs
everywhere, including the no-jax matrix — the module is deliberately
importable without jax. The jax-touching half pins the two ordering
contracts that motivated the module: ``REPRO_FAKE_DEVICES`` really
changes ``len(jax.devices())`` when applied before backend init (checked
in a subprocess so this process's locked backend doesn't interfere), and
calling ``fake_devices`` *after* init raises instead of silently doing
nothing.
"""
import os
import subprocess
import sys

import pytest

from repro import runtime_config as rc
from repro.core.accel import jax_available


# ----------------------------------------------------------------------
# resolve(): explicit > environment > default
# ----------------------------------------------------------------------

def test_resolve_defaults_all_none(monkeypatch):
    for var in (rc.ENV_BACKEND, rc.ENV_FAKE_DEVICES, rc.ENV_X64,
                rc.ENV_DEBUG_NANS):
        monkeypatch.delenv(var, raising=False)
    cfg = rc.resolve()
    assert cfg == rc.RuntimeConfig()
    assert cfg.backend is None and cfg.fake_devices is None
    assert cfg.x64 is None and cfg.debug_nans is None


def test_resolve_env_wins_over_default(monkeypatch):
    monkeypatch.setenv(rc.ENV_BACKEND, "cpu")
    monkeypatch.setenv(rc.ENV_FAKE_DEVICES, "8")
    monkeypatch.setenv(rc.ENV_X64, "yes")
    monkeypatch.setenv(rc.ENV_DEBUG_NANS, "off")
    cfg = rc.resolve()
    assert cfg.backend == "cpu"
    assert cfg.fake_devices == 8
    assert cfg.x64 is True
    assert cfg.debug_nans is False


def test_resolve_explicit_wins_over_env(monkeypatch):
    monkeypatch.setenv(rc.ENV_BACKEND, "tpu")
    monkeypatch.setenv(rc.ENV_FAKE_DEVICES, "2")
    monkeypatch.setenv(rc.ENV_X64, "0")
    cfg = rc.resolve(backend="cpu", fake_devices=16, x64=True)
    assert cfg.backend == "cpu"
    assert cfg.fake_devices == 16
    assert cfg.x64 is True
    assert cfg.debug_nans is None      # untouched field stays default


def test_resolve_blank_env_is_default(monkeypatch):
    monkeypatch.setenv(rc.ENV_FAKE_DEVICES, "   ")
    assert rc.resolve().fake_devices is None


def test_resolve_bad_bool_raises(monkeypatch):
    monkeypatch.setenv(rc.ENV_X64, "maybe")
    with pytest.raises(ValueError, match="maybe"):
        rc.resolve()


def test_parse_bool_spellings():
    for raw in ("1", "true", "YES", " on "):
        assert rc._parse_bool(raw) is True
    for raw in ("0", "False", "no", "OFF"):
        assert rc._parse_bool(raw) is False


# ----------------------------------------------------------------------
# merge_xla_flags(): append, never clobber
# ----------------------------------------------------------------------

def test_merge_preserves_unrelated_flags():
    merged = rc.merge_xla_flags("--xla_cpu_enable_fast_math=false", 8)
    assert "--xla_cpu_enable_fast_math=false" in merged.split()
    assert f"{rc._COUNT_FLAG}=8" in merged.split()


def test_merge_replaces_existing_count():
    merged = rc.merge_xla_flags(
        f"--a=1 {rc._COUNT_FLAG}=4 --b=2", 8)
    parts = merged.split()
    assert parts.count(f"{rc._COUNT_FLAG}=8") == 1
    assert f"{rc._COUNT_FLAG}=4" not in parts
    assert "--a=1" in parts and "--b=2" in parts


def test_merge_empty_flags():
    assert rc.merge_xla_flags("", 3) == f"{rc._COUNT_FLAG}=3"


def test_flag_count_roundtrip():
    assert rc._flag_count(rc.merge_xla_flags("--x=1", 5)) == 5
    assert rc._flag_count("--x=1") is None
    assert rc._flag_count("") is None


def test_fake_devices_rejects_nonpositive():
    with pytest.raises(ValueError, match=">= 1"):
        rc.fake_devices(0)


# ----------------------------------------------------------------------
# ordering contracts (jax-touching half)
# ----------------------------------------------------------------------

_SUBPROCESS_PROBE = """\
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["REPRO_FAKE_DEVICES"] = "6"
from repro import runtime_config
runtime_config.apply_env()
import jax
print(len(jax.devices()))
"""


@pytest.mark.skipif(not jax_available(), reason="needs jax")
def test_fake_devices_visible_to_jax_subprocess():
    """apply_env() before backend init really multiplies the visible
    device count — checked in a subprocess because this process's
    backend (and so its device count) is already locked."""
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_PROBE], env=env,
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "6"


@pytest.mark.skipif(not jax_available(), reason="needs jax")
def test_fake_devices_after_init_raises():
    """Once a backend exists the count is locked: a *differing* request
    must raise (naming the env-var remedy), while re-requesting the
    already-in-force count stays an idempotent no-op."""
    import jax
    jax.devices()                       # force backend init
    assert rc.jax_initialised()
    current = rc._flag_count(os.environ.get("XLA_FLAGS", ""))
    in_force = current if current is not None else None
    with pytest.raises(RuntimeError, match=rc.ENV_FAKE_DEVICES):
        rc.fake_devices((in_force or 1) + 1)
    if in_force is not None:            # idempotent path
        assert rc.fake_devices(in_force) == in_force


@pytest.mark.skipif(not jax_available(), reason="needs jax")
def test_set_backend_after_init():
    import jax
    jax.devices()
    name = jax.default_backend()
    assert rc.set_backend(name) == name          # matching: no-op
    with pytest.raises(RuntimeError, match="locked|initialised"):
        rc.set_backend("nonexistent_platform")


@pytest.mark.skipif(not jax_available(), reason="needs jax")
def test_device_mesh_bounds():
    import jax
    n = len(jax.devices())
    mesh = rc.device_mesh()
    assert mesh.axis_names == ("dev",)
    assert mesh.devices.size == n
    assert rc.device_mesh(1).devices.size == 1
    with pytest.raises(ValueError, match=">= 1"):
        rc.device_mesh(0)
    with pytest.raises(ValueError, match="fake_devices"):
        rc.device_mesh(n + 1)


@pytest.mark.skipif(not jax_available(), reason="needs jax")
@pytest.mark.parametrize("where", ["env", "checkout", "no_jax"])
def test_compilation_cache_directory(where, monkeypatch, tmp_path):
    """$JAX_COMPILATION_CACHE_DIR is used as is (jax reads it itself);
    without it the cache goes to the fixed git-ignored <checkout>/.jax_cache;
    without jax nothing is set. jax.config is stubbed: the suite keeps no
    persistent cache."""
    import jax
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.__setitem__(name, value))
    monkeypatch.delenv(rc.ENV_CACHE_DIR, raising=False)
    if where == "env":
        monkeypatch.setenv(rc.ENV_CACHE_DIR, str(tmp_path))
        assert rc.compilation_cache() == str(tmp_path)
        assert "jax_compilation_cache_dir" not in updates
    elif where == "checkout":
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert rc.compilation_cache() == os.path.join(root, ".jax_cache")
        assert updates["jax_compilation_cache_dir"] == rc.DEFAULT_CACHE_DIR
        with open(os.path.join(root, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    else:
        monkeypatch.setenv("REPRO_NO_JAX", "1")
        assert rc.compilation_cache() is None
        assert updates == {}
    if where != "no_jax":
        assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0
