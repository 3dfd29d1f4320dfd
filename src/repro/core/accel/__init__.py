"""Accelerator-resident design-space search (the Table-IV hot path on JAX).

The cost model's batched form is one array program
(``core/array_program.py``) over a lowered record; the numpy engine runs it
on the host, this package jits it onto the device and builds candidates
there. It holds four layers:

  lowering.py      BatchedEvaluator flat numpy arrays -> the program's
                   record (``DeviceArrays``: float64 on the host, padded
                   device constants for jax) + a hashable ``StaticSpec``
                   so the jitted programs cache across Problem instances.
                   Architecture structure (kind columns, scan groups) is
                   array data, not trace structure, and the node axis can
                   be padded bit-neutrally — which is what lets fleet.py
                   vmap one executable over many problems.
  eval_jax.py      the array program on jnp (dense one-hot segment
                   reductions for partition times) and the jitted
                   ``evaluate_batch``.
  search_loops.py  on-device candidate *construction*: mixed-radix digit
                   decode for brute-force chunks, a ``jax.random``-driven
                   multi-chain simulated-annealing sweep on ``lax.scan``
                   with infeasible moves repaired on device (masked
                   clamp-and-propagate — zero host round-trips mid-sweep),
                   and the rule-based optimiser's whole greedy descent as
                   one ``lax.while_loop`` program (bit-identical move
                   sequence to the scalar Algorithm 2).
  fleet.py         multi-problem sweeps: bucket problems by trace
                   signature, pad + stack their device constants, and vmap
                   the brute-force chunks / SA sweeps / rule-based greedy
                   descents across the problem axis — one XLA executable
                   searches the whole portfolio (platforms and objectives
                   are data, so buckets mix both), with per-problem
                   results bit-identical to the per-problem loops
                   (``pipeline.optimise_portfolio``).

Engine registry
---------------
The optimisers select an evaluation engine by name:

  scalar   the original one-design-at-a-time reference (perfmodel.py)
  numpy    the array program on the host (batched_eval.py)
  jax      this package: jitted, accelerator-resident construction + eval

``resolve_engine`` maps names (plus the aliases ``auto`` and the legacy
``batched``) onto an available engine and raises ``EngineUnavailable`` with
the missing extra spelled out instead of an ImportError mid-search.
"""
from __future__ import annotations

import importlib.util
import os

ENGINES = ("scalar", "numpy", "jax")

#: legacy / convenience aliases accepted everywhere an engine name is
_ALIASES = {"batched": "numpy", "auto": "auto"}


class EngineUnavailable(RuntimeError):
    """A search engine was requested whose dependency is not installed."""


def jax_available() -> bool:
    """True when the ``jax`` engine can be used in this environment.

    ``REPRO_NO_JAX=1`` masks an installed jax — CI and local runs use it
    to exercise the numpy-fallback / EngineUnavailable paths without
    uninstalling anything (``REPRO_NO_JAX=1 ./ci.sh``).
    """
    if os.environ.get("REPRO_NO_JAX", "").lower() not in ("", "0", "false"):
        return False
    return importlib.util.find_spec("jax") is not None


def require_jax(feature: str = "the 'jax' search engine"):
    """Import and return jax, or raise a clear EngineUnavailable.

    The EngineUnavailable chains the real ImportError (``raise ... from``)
    so the actionable message survives while the underlying cause — a
    broken install, a missing CUDA lib — stays on the traceback. Under
    ``REPRO_NO_JAX`` masking there is no import failure to chain; the
    mask behaves exactly like an absent package.
    """
    msg = (f"{feature} requires jax, which is not installed in this "
           f"environment. Install the 'jax' extra (pip install jax) or "
           f"select engine='numpy' / engine='scalar' instead.")
    if os.environ.get("REPRO_NO_JAX", "").lower() not in ("", "0", "false"):
        raise EngineUnavailable(f"{msg} (masked by REPRO_NO_JAX)")
    try:
        import jax
    except ImportError as err:       # genuinely missing, or broken install
        raise EngineUnavailable(msg) from err
    if not jax_available():          # availability hook says no (tests)
        raise EngineUnavailable(msg)
    return jax


def resolve_engine(name: str) -> str:
    """Normalise an engine name and check availability.

    ``auto`` picks ``jax`` when available, else ``numpy``. An explicit
    ``jax`` request with jax missing raises ``EngineUnavailable``: it never
    degrades to a host engine.
    """
    name = _ALIASES.get(name, name)
    if name == "auto":
        return "jax" if jax_available() else "numpy"
    if name not in ENGINES:
        raise ValueError(f"unknown engine {name!r}; known: "
                         f"{ENGINES + tuple(a for a in _ALIASES if a != 'auto')}")
    if name == "jax" and not jax_available():
        require_jax()
    return name


__all__ = ["ENGINES", "EngineUnavailable", "jax_available", "require_jax",
           "resolve_engine"]
