"""AST lint for JAX/TPU hazards (``planlint`` rules).

Static, import-free analysis over Python sources (by default
``ballista_tpu/ops/`` and ``ballista_tpu/exec/``) that flags the coding
patterns that silently destroy TPU throughput or fail only at trace time:

==================  =========================================================
rule                rationale
==================  =========================================================
tracer-branch       Python ``if``/``while`` on a traced array argument inside
                    a jitted function raises ConcretizationTypeError at best
                    and forces a host sync at worst. Branch on static args
                    (``static_argnames``) or use ``jnp.where``/``lax.cond``.
host-sync           ``.item()``, ``np.asarray``/``np.array``, ``float()/
                    int()/bool()`` on a traced argument, and
                    ``jax.device_get`` inside a jitted kernel block the
                    device queue for a full host round trip per call.
missing-static      An argument used in a shape position (``jnp.zeros(n)``,
                    ``x.reshape(n, -1)``, ``jnp.arange(n)``...) must be in
                    ``static_argnames`` — a traced shape either fails to
                    compile or retraces per distinct value without caching.
dynamic-shape       ``jnp.nonzero``/``jnp.unique``/``jnp.flatnonzero``/
                    one-argument ``jnp.where`` without ``size=`` have
                    value-dependent output shapes: illegal under jit, and a
                    retrace-per-shape hazard outside it. Pad to a static
                    bound and pass ``size=``.
==================  =========================================================

Suppression: append ``# planlint: disable=<rule>[,<rule>...]`` (or
``disable=all``) to the offending line, or to the ``def`` line of a jitted
function to suppress within the whole function. The tier-1 suite asserts
suppressions stay rare.

Also exposed: :func:`static_signature_report` — a per-kernel report of
every jitted function's parameters and which are static, consumable by
``parallel/dryrun.py`` to print the compiled-kernel surface next to the
multi-chip pipeline check.
"""

from __future__ import annotations

import ast
import dataclasses
import pathlib
import re

RULES: dict[str, str] = {
    "tracer-branch": "Python branch on a traced argument inside a jitted "
    "function (use static_argnames, jnp.where, or lax.cond)",
    "host-sync": "host materialization (.item()/float()/np.asarray/"
    "device_get) inside a jitted function",
    "missing-static": "argument used as a shape but not listed in "
    "static_argnames",
    "dynamic-shape": "value-dependent output shape (nonzero/unique/"
    "1-arg where) without size= inside a jitted function",
}

_SUPPRESS_RE = re.compile(r"#\s*planlint:\s*disable=([A-Za-z0-9_,\- ]+)")

# call names (as dotted strings) that force a host round trip
_HOST_SYNC_CALLS = {
    "np.asarray",
    "np.array",
    "numpy.asarray",
    "numpy.array",
    "jax.device_get",
}
# jnp constructors whose FIRST positional argument is a shape/length
_SHAPE_FIRST_ARG = {
    "jnp.zeros",
    "jnp.ones",
    "jnp.empty",
    "jnp.full",
    "jnp.arange",
    "jax.numpy.zeros",
    "jax.numpy.ones",
    "jax.numpy.full",
    "jax.numpy.arange",
}
# methods whose arguments are shapes
_SHAPE_METHODS = {"reshape", "broadcast_to"}
# value-dependent-output-shape primitives needing size=
_DYNAMIC_SHAPE_CALLS = {
    "jnp.nonzero",
    "jnp.flatnonzero",
    "jnp.unique",
    "jax.numpy.nonzero",
    "jax.numpy.flatnonzero",
    "jax.numpy.unique",
}


@dataclasses.dataclass(frozen=True)
class LintDiagnostic:
    file: str
    line: int
    rule: str
    message: str
    kernel: str = ""  # enclosing jitted function, when any

    def __str__(self) -> str:
        where = f" [{self.kernel}]" if self.kernel else ""
        return f"{self.file}:{self.line}: {self.rule}{where}: {self.message}"


@dataclasses.dataclass
class JitKernel:
    """One statically-discovered jitted function."""

    name: str
    file: str
    line: int
    params: tuple[str, ...]
    static: frozenset[str]
    hazards: tuple[LintDiagnostic, ...] = ()


def _dotted(node: ast.AST) -> str | None:
    """'jax.numpy.zeros' for Attribute/Name chains, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _static_argnames(call: ast.Call) -> frozenset[str] | None:
    """The static_argnames tuple of a jax.jit/partial(jax.jit) call, or
    None when absent/undecidable."""
    for kw in call.keywords:
        if kw.arg != "static_argnames":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, str):
            return frozenset({v.value})
        if isinstance(v, (ast.Tuple, ast.List)):
            names = set()
            for elt in v.elts:
                if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                    names.add(elt.value)
            return frozenset(names)
        return None  # computed dynamically: treat every arg as static
    return frozenset()


def _is_jit_name(node: ast.AST) -> bool:
    d = _dotted(node)
    return d in ("jax.jit", "jit")


def _jit_decoration(
    fn: ast.FunctionDef,
) -> tuple[bool, frozenset[str] | None]:
    """(is-jitted, static_argnames) for a decorated function; static
    None = jitted but statics undecidable (computed expression).

    Recognizes ``@jax.jit``, ``@jax.jit(...)``, and
    ``@[functools.]partial(jax.jit, ...)``."""
    for dec in fn.decorator_list:
        if _is_jit_name(dec):
            return True, frozenset()
        if isinstance(dec, ast.Call):
            if _is_jit_name(dec.func):
                return True, _static_argnames(dec)
            if _dotted(dec.func) in ("partial", "functools.partial"):
                if dec.args and _is_jit_name(dec.args[0]):
                    return True, _static_argnames(dec)
    return False, None


def _jit_call_sites(tree: ast.Module) -> dict[str, frozenset[str] | None]:
    """function-name -> static_argnames for every ``jax.jit(f, ...)`` /
    ``partial(jax.jit, ...)``-style call anywhere in the module (module
    level, class bodies, inside wrapper functions)."""
    sites: dict[str, frozenset[str] | None] = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not _is_jit_name(node.func):
            continue
        if node.args and isinstance(node.args[0], ast.Name):
            sites[node.args[0].id] = _static_argnames(node)
    return sites


def _suppressed(source_lines: list[str], lineno: int) -> frozenset[str]:
    line = source_lines[lineno - 1] if 0 < lineno <= len(source_lines) else ""
    m = _SUPPRESS_RE.search(line)
    if not m:
        return frozenset()
    return frozenset(p.strip() for p in m.group(1).split(","))


class _KernelLinter(ast.NodeVisitor):
    """Lints ONE jitted function body."""

    def __init__(
        self,
        fn: ast.FunctionDef,
        static: frozenset[str] | None,
        file: str,
        source_lines: list[str],
    ):
        self.fn = fn
        self.file = file
        self.lines = source_lines
        args = fn.args
        self.params = tuple(
            a.arg
            for a in (args.posonlyargs + args.args + args.kwonlyargs)
            if a.arg not in ("self", "cls")
        )
        # static_argnames undecidable -> assume everything static (no
        # false positives from computed static sets)
        self.static = frozenset(self.params) if static is None else static
        self.traced = frozenset(self.params) - self.static
        self.fn_suppress = _suppressed(source_lines, fn.lineno)
        self.diags: list[LintDiagnostic] = []

    # -- helpers -------------------------------------------------------------
    def _traced_in(self, node: ast.AST) -> set[str]:
        """Traced parameter names used BY VALUE under ``node``.

        Skips two statically-safe shapes: attribute access on a traced
        name (``x.shape``, ``batch.capacity`` — aux/structure data, not a
        tracer), and ``is``/``is not`` identity comparisons (``x is None``
        branches on pytree structure, which jit specializes on)."""
        out: set[str] = set()

        def walk(n: ast.AST) -> None:
            if isinstance(n, ast.Attribute):
                return  # x.attr is static metadata, not the traced value
            if isinstance(n, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in n.ops
            ):
                return
            if isinstance(n, ast.Name) and n.id in self.traced:
                out.add(n.id)
            for c in ast.iter_child_nodes(n):
                walk(c)

        walk(node)
        return out

    def _emit(self, rule: str, lineno: int, message: str) -> None:
        sup = _suppressed(self.lines, lineno) | self.fn_suppress
        if rule in sup or "all" in sup:
            return
        self.diags.append(
            LintDiagnostic(self.file, lineno, rule, message, self.fn.name)
        )

    # -- rules ---------------------------------------------------------------
    def visit_If(self, node: ast.If) -> None:
        traced = self._traced_in(node.test)
        if traced:
            self._emit(
                "tracer-branch",
                node.lineno,
                f"if-branch on traced argument(s) {sorted(traced)}",
            )
        self.generic_visit(node)

    def visit_While(self, node: ast.While) -> None:
        traced = self._traced_in(node.test)
        if traced:
            self._emit(
                "tracer-branch",
                node.lineno,
                f"while-loop on traced argument(s) {sorted(traced)}",
            )
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        d = _dotted(node.func)
        # .item() on anything inside a jitted body
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr == "item"
            and not node.args
        ):
            self._emit("host-sync", node.lineno, ".item() inside jitted kernel")
        if d in _HOST_SYNC_CALLS:
            self._emit(
                "host-sync", node.lineno, f"{d}() inside jitted kernel"
            )
        if (
            isinstance(node.func, ast.Name)
            and node.func.id in ("float", "int", "bool")
            and node.args
            and isinstance(node.args[0], ast.Name)
            and node.args[0].id in self.traced
        ):
            self._emit(
                "host-sync",
                node.lineno,
                f"{node.func.id}({node.args[0].id}) materializes a traced "
                "argument",
            )
        # shape positions fed by traced params
        if d in _SHAPE_FIRST_ARG and node.args:
            traced = self._traced_in(node.args[0])
            if traced:
                self._emit(
                    "missing-static",
                    node.lineno,
                    f"{d}() shape uses traced argument(s) {sorted(traced)} "
                    "— add them to static_argnames",
                )
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in _SHAPE_METHODS
        ):
            traced = set()
            for a in node.args:
                traced |= self._traced_in(a)
            if traced:
                self._emit(
                    "missing-static",
                    node.lineno,
                    f".{node.func.attr}() shape uses traced argument(s) "
                    f"{sorted(traced)} — add them to static_argnames",
                )
        # value-dependent output shapes
        has_size = any(kw.arg == "size" for kw in node.keywords)
        if d in _DYNAMIC_SHAPE_CALLS and not has_size:
            self._emit(
                "dynamic-shape",
                node.lineno,
                f"{d}() without size= has a value-dependent output shape",
            )
        if (
            d in ("jnp.where", "jax.numpy.where")
            and len(node.args) == 1
            and not has_size
        ):
            self._emit(
                "dynamic-shape",
                node.lineno,
                "one-argument jnp.where() without size= has a "
                "value-dependent output shape",
            )
        self.generic_visit(node)


def lint_source(
    source: str, filename: str = "<string>"
) -> tuple[list[LintDiagnostic], list[JitKernel]]:
    """Lint one module's source. Returns (diagnostics, jitted kernels)."""
    tree = ast.parse(source, filename=filename)
    lines = source.splitlines()
    sites = _jit_call_sites(tree)
    diags: list[LintDiagnostic] = []
    kernels: list[JitKernel] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.FunctionDef):
            continue
        jitted, static = _jit_decoration(node)
        if not jitted and node.name in sites:
            jitted, static = True, sites[node.name]
        if not jitted:
            continue
        linter = _KernelLinter(node, static, filename, lines)
        for stmt in node.body:
            linter.visit(stmt)
        kernels.append(
            JitKernel(
                name=node.name,
                file=filename,
                line=node.lineno,
                params=linter.params,
                static=frozenset(linter.static & set(linter.params)),
                hazards=tuple(linter.diags),
            )
        )
        diags.extend(linter.diags)
    return diags, kernels


_DEFAULT_TARGETS = ("ops", "exec", "obs")


def _target_files(paths=None) -> list[pathlib.Path]:
    if paths is not None:
        out = []
        for p in paths:
            p = pathlib.Path(p)
            out.extend(sorted(p.rglob("*.py")) if p.is_dir() else [p])
        return out
    root = pathlib.Path(__file__).resolve().parent.parent
    files: list[pathlib.Path] = []
    for sub in _DEFAULT_TARGETS:
        files.extend(sorted((root / sub).rglob("*.py")))
    return files


def lint_paths(paths=None) -> list[LintDiagnostic]:
    """Lint files/directories (default: ballista_tpu/{ops,exec})."""
    diags: list[LintDiagnostic] = []
    for f in _target_files(paths):
        d, _ = lint_source(f.read_text(), str(f))
        diags.extend(d)
    return diags


def static_signature_report(paths=None) -> dict[str, dict]:
    """Per-kernel static signature report over the target sources:
    ``{"module.function": {"file", "line", "params", "static",
    "hazards"}}``. parallel/dryrun.py prints this next to the multi-chip
    pipeline check so the compiled-kernel surface (and its static/traced
    split) is visible in the same place mesh placement is asserted."""
    report: dict[str, dict] = {}
    for f in _target_files(paths):
        _, kernels = lint_source(f.read_text(), str(f))
        for k in kernels:
            p = pathlib.Path(k.file)
            # qualify with the package dir: ops/aggregate.py and
            # exec/aggregate.py must not collide in the report
            key = f"{p.parent.name}.{p.stem}.{k.name}"
            report[key] = {
                "file": k.file,
                "line": k.line,
                "params": list(k.params),
                "static": sorted(k.static),
                "hazards": [str(h) for h in k.hazards],
            }
    return report


def suppression_count(paths=None) -> int:
    """Number of ``# planlint: disable=`` escape hatches in the targets
    (the tier-1 suite asserts this stays rare)."""
    n = 0
    for f in _target_files(paths):
        n += len(_SUPPRESS_RE.findall(f.read_text()))
    return n
