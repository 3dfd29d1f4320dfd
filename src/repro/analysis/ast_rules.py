"""AST lint pack: repo rules checkable without importing (or having) jax.

Three rules, each encoding an invariant the engine stack already relies on
but until now only enforced by convention or by runtime failure in one CI
matrix cell:

  ast/eager-jax-import     modules the ``REPRO_NO_JAX`` import matrix must
                           be able to import (``repro.core.*``,
                           ``repro.configs.*``, ``repro.data.*`` — minus
                           the three jax-subject accel modules) must not
                           import jax at module scope. A violation here is
                           exactly the failure mode the no-jax CI job
                           exists to catch, surfaced at lint time instead
                           of as an ImportError in a different matrix cell.

  ast/traced-python-branch Python control flow on traced values inside a
                           jitted body (``if x:``/``while x:`` or
                           ``bool(x)``/``float(x)``/``int(x)`` where ``x``
                           is a traced parameter) raises
                           ``TracerBoolConversionError`` at trace time on
                           some paths — or worse, silently bakes one
                           branch into the executable when the value is a
                           concrete example under ``make_jaxpr``. The rule
                           reads ``static_argnums`` from the decorator, so
                           branching on genuinely static parameters stays
                           legal; un-decorated helpers that the jitted
                           entry points call are covered via
                           ``TRACED_HELPERS`` (name -> static parameter
                           names).

  ast/unseeded-random      tests, benchmarks and the mapping service
                           (``repro/service/``) must not draw from global
                           random state (``np.random.<draw>(...)``,
                           ``random.<draw>`` module calls): the randomized
                           differential suite's reproducibility — and the
                           determinism of the threaded service tests —
                           depends on every draw flowing from an explicit
                           seed (``random.Random(seed)``,
                           ``np.random.default_rng(seed)``).

The pack is pure ``ast`` — the no-jax CI lane runs it with nothing but the
standard library and numpy installed. Paths in findings are repo-relative
with ``/`` separators so baselines are platform-stable.
"""
from __future__ import annotations

import ast
import os
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from repro.analysis import Violation

# ----------------------------------------------------------------------
# rule configuration (data, so tests can override and docs can quote)
# ----------------------------------------------------------------------

#: src/-relative prefixes of the REPRO_NO_JAX import matrix: every module
#: here must import with jax absent (tests/conftest.py skips only the
#: *test* modules whose subject is jax; the library side must hold).
NO_JAX_PREFIXES: Tuple[str, ...] = (
    "repro/core/",
    "repro/configs/",
    "repro/data/",
    "repro/analysis/",
    "repro/obs/",
    # the mapping service must serve host-engine requests without jax;
    # its lockstep engine reaches jax lazily inside the function
    "repro/service/",
    # must stay importable (and callable, bar device_mesh) without jax:
    # it is the thing that configures the process BEFORE jax loads
    "repro/runtime_config.py",
)

#: the jax-subject accel modules — the only core files allowed to import
#: jax eagerly (everything reaches them through the lazy engine registry)
NO_JAX_EXCEPTIONS: Tuple[str, ...] = (
    "repro/core/accel/eval_jax.py",
    "repro/core/accel/search_loops.py",
    "repro/core/accel/fleet.py",
    "repro/analysis/jaxpr_audit.py",
)

#: src/-relative prefixes of the code that jitted programs trace: the
#: accel package and the array program it shares with the numpy engine
TRACED_SCOPES: Tuple[str, ...] = (
    "repro/core/accel/",
    "repro/core/array_program.py",
)

#: helpers called from inside jitted programs that are not themselves
#: decorated: function name -> parameter names that are trace-static
#: (everything else is traced). Keyed by bare name; scoped to
#: ``TRACED_SCOPES``.
TRACED_HELPERS: Dict[str, Set[str]] = {
    "eval_core": {"static", "single_partition"},
    "_eval_core": {"static", "single_partition"},
    "_collective_bytes": {"static"},
    "_realizable": {"static"},
    "propagate_jax": {"static", "single_partition"},
    "_scope_mask": {"g"},
    "_scatter_triple": {"static", "gran"},
    "repair_jax": {"static"},
    "_bf_decode_digits": {"B", "idt"},
    "_bf_eval_part": {"static", "B", "no_cut"},
    "_bf_chunk_core": {"static", "B", "no_cut"},
    "_bf_shard_chunk": {"static", "B", "no_cut", "D"},
    "_fleet_bf_chunk_core": {"static", "B", "no_cut"},
    "_fleet_sa_sweeps_core": {"static", "gran", "has_cut_edges", "n_sweeps"},
    "_fleet_rb_descend_core": {"static", "gran"},
    "_sa_sweep_step": {"static", "gran", "has_cut_edges"},
    "_sa_scan": {"static", "gran", "has_cut_edges", "n_sweeps"},
    "_rb_step": {"static", "gran"},
    "_rb_descend_core": {"static", "gran"},
    "_masked_choice": set(),
}

#: module-level draws from global random state (the unseeded set); module
#: attribute access like ``np.random.default_rng`` / ``SeedSequence`` /
#: ``Random(seed)`` constructors are explicitly NOT here.
UNSEEDED_NP_RANDOM: Tuple[str, ...] = (
    "rand", "randn", "randint", "random", "random_sample", "ranf",
    "sample", "choice", "shuffle", "permutation", "uniform", "normal",
    "standard_normal", "zipf", "poisson", "exponential", "beta", "gamma",
    "binomial", "bytes", "integers",
)
UNSEEDED_STDLIB_RANDOM: Tuple[str, ...] = (
    "random", "randint", "randrange", "uniform", "choice", "choices",
    "shuffle", "sample", "gauss", "normalvariate", "betavariate",
    "expovariate", "triangular", "getrandbits", "randbytes",
)


def _rel(path: str, root: str) -> str:
    return os.path.relpath(path, root).replace(os.sep, "/")


# ----------------------------------------------------------------------
# rule: eager jax import
# ----------------------------------------------------------------------

def _in_no_jax_matrix(rel_src: str) -> bool:
    if rel_src in NO_JAX_EXCEPTIONS:
        return False
    return rel_src.startswith(NO_JAX_PREFIXES)


def check_eager_jax_import(tree: ast.Module, rel_src: str) -> List[Violation]:
    """Flag module-scope ``import jax`` / ``from jax... import`` in modules
    the no-jax matrix must import. Imports inside functions (lazy), inside
    ``if TYPE_CHECKING:`` blocks, or guarded by ``try:`` with an
    ``ImportError`` handler are fine — they are exactly the sanctioned
    gating idioms."""
    if not _in_no_jax_matrix(rel_src):
        return []
    out: List[Violation] = []

    def _guarded(stack: Sequence[ast.AST]) -> bool:
        for anc in stack:
            if isinstance(anc, ast.Try) and any(
                    _names_import_error(h) for h in anc.handlers):
                return True
            if isinstance(anc, ast.If) and _is_type_checking(anc.test):
                return True
        return False

    def _names_import_error(handler: ast.ExceptHandler) -> bool:
        t = handler.type
        names = []
        if isinstance(t, ast.Name):
            names = [t.id]
        elif isinstance(t, ast.Tuple):
            names = [e.id for e in t.elts if isinstance(e, ast.Name)]
        return any(n in ("ImportError", "ModuleNotFoundError", "Exception")
                   for n in names)

    def _is_type_checking(test: ast.AST) -> bool:
        return (isinstance(test, ast.Name) and test.id == "TYPE_CHECKING") \
            or (isinstance(test, ast.Attribute)
                and test.attr == "TYPE_CHECKING")

    def walk(node: ast.AST, stack: Tuple[ast.AST, ...]) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                continue                     # function-scope imports: lazy
            mods: List[str] = []
            if isinstance(child, ast.Import):
                mods = [a.name for a in child.names]
            elif isinstance(child, ast.ImportFrom) and not child.level:
                mods = [child.module or ""]
            hits = [m for m in mods
                    if m == "jax" or m.startswith("jax.")]
            if hits and not _guarded(stack + (node,)):
                out.append(Violation(
                    rule="ast/eager-jax-import",
                    where=f"src/{rel_src}",
                    line=child.lineno,
                    message=(
                        f"module-scope import of {hits[0]!r} in a module "
                        f"the REPRO_NO_JAX matrix must import — move it "
                        f"inside the function that needs it (see "
                        f"core/exporter._pspec for the idiom)")))
            walk(child, stack + (node,))

    walk(tree, ())
    return out


# ----------------------------------------------------------------------
# rule: Python control flow on traced values in jitted bodies
# ----------------------------------------------------------------------

def _jit_static_argnums(deco: ast.AST) -> Optional[Set[int]]:
    """If ``deco`` is a jax.jit decoration, return its static_argnums set
    (empty for bare ``@jax.jit``); else None.

    Recognised shapes: ``@jax.jit``, ``@jit``,
    ``@functools.partial(jax.jit, static_argnums=(...))`` and
    ``@partial(jax.jit, ...)``.
    """
    def is_jit(node: ast.AST) -> bool:
        return (isinstance(node, ast.Name) and node.id == "jit") or \
            (isinstance(node, ast.Attribute) and node.attr == "jit")

    if is_jit(deco):
        return set()
    if isinstance(deco, ast.Call):
        f = deco.func
        is_partial = (isinstance(f, ast.Name) and f.id == "partial") or \
            (isinstance(f, ast.Attribute) and f.attr == "partial")
        if is_partial and deco.args and is_jit(deco.args[0]):
            for kw in deco.keywords:
                if kw.arg in ("static_argnums", "static_argnames"):
                    try:
                        val = ast.literal_eval(kw.value)
                    except ValueError:
                        return set()
                    if isinstance(val, int):
                        return {val}
                    return {v for v in val if isinstance(v, int)}
            return set()
        if is_jit(f):                        # @jax.jit(static_argnums=...)
            for kw in deco.keywords:
                if kw.arg in ("static_argnums", "static_argnames"):
                    try:
                        val = ast.literal_eval(kw.value)
                    except ValueError:
                        return set()
                    if isinstance(val, int):
                        return {val}
                    return {v for v in val if isinstance(v, int)}
            return set()
    return None


def _traced_params(fn: ast.FunctionDef) -> Optional[Set[str]]:
    """Parameter names of ``fn`` that are traced inside its body, or None
    when ``fn`` is neither jit-decorated nor a registered traced helper."""
    statics: Optional[Set[int]] = None
    for deco in fn.decorator_list:
        s = _jit_static_argnums(deco)
        if s is not None:
            statics = s
            break
    names = [a.arg for a in fn.args.posonlyargs + fn.args.args]
    if statics is not None:
        return {n for i, n in enumerate(names) if i not in statics}
    if fn.name in TRACED_HELPERS:
        return set(names) - TRACED_HELPERS[fn.name]
    return None


_CASTS = ("bool", "float", "int")


def check_traced_python_branch(tree: ast.Module,
                               rel_src: str) -> List[Violation]:
    """Inside jitted bodies (and registered traced helpers) in
    ``TRACED_SCOPES``: flag ``if``/``while`` tests, ``assert`` tests and
    ``bool()``/``float()``/``int()`` casts that reference a traced
    parameter by name. Conservative by construction — locals derived from
    traced values are not tracked — so every hit is a real one."""
    if not rel_src.startswith(TRACED_SCOPES):
        return []
    out: List[Violation] = []

    for fn in [n for n in ast.walk(tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]:
        traced = _traced_params(fn)
        if not traced:
            continue
        # names rebound inside the body stop being "the traced parameter"
        rebound = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign, ast.For)):
                targets = [node.target]
            else:
                continue
            for t in targets:
                rebound |= {leaf.id for leaf in ast.walk(t)
                            if isinstance(leaf, ast.Name)}
        live = traced - rebound

        def refs(node: ast.AST) -> List[str]:
            return sorted({n.id for n in ast.walk(node)
                           if isinstance(n, ast.Name) and n.id in live})

        for node in ast.walk(fn):
            test = None
            what = None
            if isinstance(node, (ast.If, ast.While)):
                test, what = node.test, type(node).__name__.lower()
            elif isinstance(node, ast.Assert):
                test, what = node.test, "assert"
            elif isinstance(node, ast.IfExp):
                test, what = node.test, "conditional expression"
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Name) \
                    and node.func.id in _CASTS and node.args:
                test, what = node.args[0], f"{node.func.id}() cast"
            if test is None:
                continue
            hit = refs(test)
            if hit:
                out.append(Violation(
                    rule="ast/traced-python-branch",
                    where=f"src/{rel_src}:{fn.name}",
                    line=node.lineno,
                    message=(
                        f"Python {what} on traced parameter(s) "
                        f"{', '.join(hit)} inside a jitted body — use "
                        f"jnp.where / lax.cond, or declare the argument "
                        f"in static_argnums")))
    return out


# ----------------------------------------------------------------------
# rule: unseeded randomness in tests
# ----------------------------------------------------------------------

def check_unseeded_random(tree: ast.Module, rel_path: str) -> List[Violation]:
    """Flag draws from global random state in test files: any
    ``np.random.<draw>(...)`` / ``numpy.random.<draw>(...)`` and any
    ``random.<draw>(...)`` module call. Explicit generators —
    ``random.Random(seed)``, ``np.random.default_rng(seed)``,
    ``np.random.RandomState(seed)`` — are the sanctioned forms."""
    out: List[Violation] = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)):
            continue
        f = node.func
        base = f.value
        # np.random.<draw> / numpy.random.<draw>
        if isinstance(base, ast.Attribute) and base.attr == "random" \
                and isinstance(base.value, ast.Name) \
                and base.value.id in ("np", "numpy") \
                and f.attr in UNSEEDED_NP_RANDOM:
            out.append(Violation(
                rule="ast/unseeded-random",
                where=f"{rel_path}",
                line=node.lineno,
                message=(f"np.random.{f.attr}(...) draws from global "
                         f"state — use np.random.default_rng(seed)")))
        # random.<draw>
        elif isinstance(base, ast.Name) and base.id == "random" \
                and f.attr in UNSEEDED_STDLIB_RANDOM:
            out.append(Violation(
                rule="ast/unseeded-random",
                where=f"{rel_path}",
                line=node.lineno,
                message=(f"random.{f.attr}(...) draws from global state "
                         f"— use random.Random(seed)")))
    return out


# ----------------------------------------------------------------------
# pack driver
# ----------------------------------------------------------------------

def _py_files(root: str, sub: str) -> Iterable[str]:
    base = os.path.join(root, sub)
    for dirpath, _, files in os.walk(base):
        for f in sorted(files):
            if f.endswith(".py"):
                yield os.path.join(dirpath, f)


def run(repo_root: str) -> Dict[str, List[Violation]]:
    """Run the whole pack over a checkout; {rule: violations}."""
    by_rule: Dict[str, List[Violation]] = {
        "ast/eager-jax-import": [],
        "ast/traced-python-branch": [],
        "ast/unseeded-random": [],
    }
    src_root = os.path.join(repo_root, "src")
    for path in _py_files(repo_root, "src"):
        rel_src = _rel(path, src_root)
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read(), filename=path)
        by_rule["ast/eager-jax-import"] += \
            check_eager_jax_import(tree, rel_src)
        by_rule["ast/traced-python-branch"] += \
            check_traced_python_branch(tree, rel_src)
    # the service package joins the seeded-randomness surface: flaky
    # thread scheduling must never hide behind nondeterministic draws
    for sub in ("tests", "benchmarks", os.path.join("src", "repro",
                                                    "service")):
        for path in _py_files(repo_root, sub):
            rel = _rel(path, repo_root)
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read(), filename=path)
            by_rule["ast/unseeded-random"] += \
                check_unseeded_random(tree, rel)
    return by_rule


__all__ = [
    "NO_JAX_PREFIXES", "NO_JAX_EXCEPTIONS", "TRACED_HELPERS",
    "check_eager_jax_import", "check_traced_python_branch",
    "check_unseeded_random", "run",
]
