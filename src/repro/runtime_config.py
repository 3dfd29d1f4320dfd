"""First-class runtime configuration for the jax engine stack.

One place to pick the backend platform, the float width, NaN debugging
and — the piece everything multi-device hangs off — a *fake device*
count for the CPU backend. jax locks the host platform's device count
the moment it initialises a backend, and the knob that sets it
(``--xla_force_host_platform_device_count`` inside ``XLA_FLAGS``) is an
environment variable, so ordering is everything: this module is
import-free of jax and must be consulted BEFORE the first ``jax.devices()``
/ jit dispatch of the process. Three entry styles, strongest first:

  explicit call      ``runtime_config.fake_devices(8)`` — scripts and
                     launchers (``launch/dryrun.py`` routes through this
                     instead of clobbering ``XLA_FLAGS`` wholesale).
  environment        ``REPRO_FAKE_DEVICES=8 python -m pytest ...`` —
                     consumed by ``tests/conftest.py`` and
                     ``benchmarks/run.py`` via :func:`apply_env`; how the
                     CI ``shard`` job gives a 1-core runner 8 devices.
  defaults           nothing set -> nothing touched. ``apply_env`` is a
                     strict no-op without ``REPRO_*`` variables, so the
                     ordinary single-device test/bench runs are
                     byte-for-byte what they were.

Precedence is explicit argument > environment variable > default
(:func:`resolve` is the pure resolution step; tests pin it).

``fake_devices`` APPENDS to / replaces its own flag within any existing
``XLA_FLAGS`` value — it never overwrites unrelated flags (the historic
``launch/dryrun.py`` bug this module absorbs). Calling it after jax has
already initialised a backend cannot take effect; it raises a
``RuntimeError`` naming the fix (set the env var, or call earlier)
instead of silently doing nothing. :func:`jax_initialised` performs that
check without importing jax, so this module stays importable in the
``REPRO_NO_JAX`` matrix.

``device_mesh`` and ``compilation_cache`` are the jax-touching helpers
(lazy imports): the 1-D ``Mesh`` over the ``"dev"`` axis that the sharded
engines (``core/accel/search_loops.py`` / ``core/accel/fleet.py``, see
docs/distributed.md) consume, and the persistent compilation cache that
``chip_smoke.py`` and ``benchmarks/run.py`` turn on (the test suite does
not: its compiles are per-process and small).
"""
from __future__ import annotations

import dataclasses
import os
import sys
from typing import Callable, Optional, TypeVar

__all__ = [
    "RuntimeConfig", "resolve", "configure", "apply_env", "fake_devices",
    "merge_xla_flags", "set_backend", "enable_x64", "set_debug_nans",
    "jax_initialised", "device_mesh", "compilation_cache",
    "ENV_BACKEND", "ENV_FAKE_DEVICES", "ENV_X64", "ENV_DEBUG_NANS",
    "ENV_CACHE_DIR", "DEFAULT_CACHE_DIR",
]

ENV_BACKEND = "REPRO_BACKEND"
ENV_FAKE_DEVICES = "REPRO_FAKE_DEVICES"
ENV_X64 = "REPRO_X64"
ENV_DEBUG_NANS = "REPRO_DEBUG_NANS"
#: jax's own variable for the persistent compilation cache directory
ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset: a fixed
#: path under the checkout (src/repro/ -> root), so every run of the same
#: checkout finds the executables the previous run wrote (.gitignore'd)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_COUNT_FLAG = "--xla_force_host_platform_device_count"

T = TypeVar("T")


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    """Resolved runtime settings. ``None`` means "leave jax's default
    alone" — the zero-surprise state for settings nobody asked about."""

    backend: Optional[str] = None       # "cpu" | "gpu" | "tpu"
    fake_devices: Optional[int] = None  # host-platform device count
    x64: Optional[bool] = None          # jax_enable_x64
    debug_nans: Optional[bool] = None   # jax_debug_nans


def _parse_bool(raw: str) -> bool:
    low = raw.strip().lower()
    if low in ("1", "true", "yes", "on"):
        return True
    if low in ("0", "false", "no", "off"):
        return False
    raise ValueError(f"can't parse {raw!r} as a boolean "
                     f"(use 1/0, true/false, yes/no, on/off)")


def _resolve_one(explicit: Optional[T], env_name: str,
                 parse: Callable[[str], T]) -> Optional[T]:
    """explicit argument > environment variable > default (None)."""
    if explicit is not None:
        return explicit
    raw = os.environ.get(env_name)
    if raw is None or raw.strip() == "":
        return None
    return parse(raw)


def resolve(backend: Optional[str] = None,
            fake_devices: Optional[int] = None,
            x64: Optional[bool] = None,
            debug_nans: Optional[bool] = None) -> RuntimeConfig:
    """Pure precedence resolution — no side effects, no jax.

    Each field resolves independently: the explicit argument wins, else
    the ``REPRO_*`` environment variable, else ``None`` (untouched).
    """
    return RuntimeConfig(
        backend=_resolve_one(backend, ENV_BACKEND, str),
        fake_devices=_resolve_one(fake_devices, ENV_FAKE_DEVICES, int),
        x64=_resolve_one(x64, ENV_X64, _parse_bool),
        debug_nans=_resolve_one(debug_nans, ENV_DEBUG_NANS, _parse_bool),
    )


# ----------------------------------------------------------------------
# jax state probes (no jax import)
# ----------------------------------------------------------------------

def jax_initialised() -> bool:
    """True once jax has initialised a backend (device count locked).

    Reads ``jax._src.xla_bridge``'s backend cache out of ``sys.modules``
    — merely *importing* jax does not initialise backends, so this stays
    False until the first ``jax.devices()`` / dispatch, and the check
    itself never imports jax (``REPRO_NO_JAX`` matrix).
    """
    xb = sys.modules.get("jax._src.xla_bridge")
    return bool(xb is not None and getattr(xb, "_backends", None))


def _flag_count(flags: str) -> Optional[int]:
    """The fake-device count currently requested in an XLA_FLAGS string."""
    for part in flags.split():
        if part.startswith(_COUNT_FLAG + "="):
            try:
                return int(part.split("=", 1)[1])
            except ValueError:
                return None
    return None


def merge_xla_flags(flags: str, n: int) -> str:
    """``flags`` with the fake-device-count flag set to ``n``.

    Replaces an existing ``--xla_force_host_platform_device_count`` entry
    and preserves every other flag verbatim — the append-don't-clobber
    contract ``fake_devices`` is built on (pure; tests pin it).
    """
    kept = [p for p in flags.split()
            if not p.startswith(_COUNT_FLAG + "=") and p != _COUNT_FLAG]
    kept.append(f"{_COUNT_FLAG}={int(n)}")
    return " ".join(kept)


# ----------------------------------------------------------------------
# the individual switches
# ----------------------------------------------------------------------

def fake_devices(n: int) -> int:
    """Request ``n`` fake host-platform devices (CPU backend).

    Must run before jax initialises its backends; afterwards the count is
    locked and this raises ``RuntimeError`` (unless the requested count
    is already in force, which is a no-op — ``apply_env`` may legally run
    twice). Other ``XLA_FLAGS`` content is preserved.
    """
    n = int(n)
    if n < 1:
        raise ValueError(f"fake_devices needs n >= 1, got {n}")
    flags = os.environ.get("XLA_FLAGS", "")
    if jax_initialised():
        if _flag_count(flags) == n:
            return n                      # already in force: idempotent
        raise RuntimeError(
            f"fake_devices({n}) called after jax initialised its backends "
            f"— the host device count is locked for this process. Call it "
            f"(or runtime_config.apply_env()) before the first jax.devices()"
            f"/jit dispatch, or launch with {ENV_FAKE_DEVICES}={n}.")
    os.environ["XLA_FLAGS"] = merge_xla_flags(flags, n)
    return n


def set_backend(name: str) -> str:
    """Pin the jax platform (``cpu`` / ``gpu`` / ``tpu``).

    Uses ``jax.config.update("jax_platforms", ...)`` when jax is already
    imported, else the ``JAX_PLATFORMS`` environment variable (picked up
    at import, and the module stays jax-free). After backend init the
    platform is locked: a differing request raises ``RuntimeError``.
    """
    name = str(name).lower()
    if jax_initialised():
        import jax
        if jax.default_backend() == name:
            return name
        raise RuntimeError(
            f"set_backend({name!r}) called after jax initialised "
            f"{jax.default_backend()!r} — pick the platform before the "
            f"first jax use, or launch with JAX_PLATFORMS={name}.")
    if "jax" in sys.modules:
        import jax
        jax.config.update("jax_platforms", name)
    else:
        os.environ["JAX_PLATFORMS"] = name
    return name


def _jax_config_toggle(jax_name: str, env_name: str, on: bool) -> bool:
    on = bool(on)
    if "jax" in sys.modules:
        import jax
        jax.config.update(jax_name, on)
    else:
        os.environ[env_name] = "1" if on else "0"
    return on


def enable_x64(on: bool = True) -> bool:
    """Toggle ``jax_enable_x64`` (f64 device arrays; flippable anytime)."""
    return _jax_config_toggle("jax_enable_x64", "JAX_ENABLE_X64", on)


def set_debug_nans(on: bool = True) -> bool:
    """Toggle ``jax_debug_nans`` (re-runs NaN-producing ops un-jitted)."""
    return _jax_config_toggle("jax_debug_nans", "JAX_DEBUG_NANS", on)


# ----------------------------------------------------------------------
# the composite entry points
# ----------------------------------------------------------------------

def configure(backend: Optional[str] = None,
              fake_devices: Optional[int] = None,
              x64: Optional[bool] = None,
              debug_nans: Optional[bool] = None) -> RuntimeConfig:
    """Resolve (explicit > env > default) and apply in dependency order:
    device count first (it must precede backend init), then platform,
    then the config toggles. Fields resolving to ``None`` are untouched.
    """
    cfg = resolve(backend, fake_devices, x64, debug_nans)
    if cfg.fake_devices is not None:
        globals()["fake_devices"](cfg.fake_devices)
    if cfg.backend is not None:
        set_backend(cfg.backend)
    if cfg.x64 is not None:
        enable_x64(cfg.x64)
    if cfg.debug_nans is not None:
        set_debug_nans(cfg.debug_nans)
    return cfg


def apply_env() -> RuntimeConfig:
    """Apply whatever ``REPRO_*`` runtime variables are set — a strict
    no-op without them. The harness hook: ``tests/conftest.py`` and
    ``benchmarks/run.py`` call this before any jax backend init, which is
    how ``REPRO_FAKE_DEVICES=8`` turns a 1-core CI runner into an
    8-device shard-testing box without touching ordinary runs."""
    return configure()


# ----------------------------------------------------------------------
# the device mesh the sharded engines consume
# ----------------------------------------------------------------------

def device_mesh(devices: Optional[int] = None):
    """1-D ``jax.sharding.Mesh`` over the first ``devices`` devices,
    axis name ``"dev"`` — the mesh every sharded engine axis maps over
    (docs/distributed.md). ``None`` takes every visible device. Asking
    for more devices than exist raises with the ``fake_devices`` recipe
    in the message (lazy jax import: this is the module's only
    jax-touching function)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    n = len(devs) if devices is None else int(devices)
    if n < 1:
        raise ValueError(f"device_mesh needs >= 1 device, got {n}")
    if n > len(devs):
        raise ValueError(
            f"device_mesh({n}) but only {len(devs)} device(s) visible — "
            f"for CPU testing call runtime_config.fake_devices({n}) (or "
            f"set {ENV_FAKE_DEVICES}={n}) before the first jax use.")
    return Mesh(np.asarray(devs[:n]), ("dev",))


# ----------------------------------------------------------------------
# the persistent compilation cache
# ----------------------------------------------------------------------

def compilation_cache() -> Optional[str]:
    """Turn on jax's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is jax's own setting and is
    left alone: the cache lives there and nowhere else. Otherwise the
    cache goes to ``DEFAULT_CACHE_DIR`` (``<checkout>/.jax_cache``). Every
    compile is cached, however quick: jax's default skips compiles under a
    second, which on CPU is most of the engine programs. Without jax
    (absent, or masked by ``REPRO_NO_JAX``) nothing is set and the result
    is None."""
    from repro.core.accel import jax_available
    if not jax_available():
        return None
    import jax

    path = os.environ.get(ENV_CACHE_DIR) or DEFAULT_CACHE_DIR
    if not os.environ.get(ENV_CACHE_DIR):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
