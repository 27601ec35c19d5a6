"""Layer 1: repo-aware AST lint over the jit-traced package source.

The rules only fire inside *traced* modules — the files whose code is
reachable from the jitted step functions (``TRACED_PREFIXES``).  Host-side
code (data loading, evaluation, CLIs) legitimately calls ``float()`` on
device scalars it already fetched; the same call inside ``detection/graph``
would be a silent per-step device->host sync, which is exactly the failure
mode the reference repo's CustomOp sandwich had and this repo exists to
eliminate.

Static analysis cannot prove a value is a tracer, so each rule is a
*reviewed* heuristic: pre-existing findings are frozen in the committed
baseline (``tpulint_baseline.json``) after human review, and only NEW
findings fail ``tools/tpulint.py --check``.  The baseline keys on
(rule, path, stripped source line) with a count, so moving a line is free
but adding another occurrence of a frozen pattern still fails.

Rules
-----
TPU001 host-cast        float()/int()/bool() on a non-literal, ``.item()``
                        / ``.tolist()``, and ``np.asarray``/``np.array`` —
                        each forces a device sync on a traced value.
TPU002 numpy-call       any other ``np.*`` computation in traced code
                        (numpy silently pulls tracers to host or bakes
                        trace-time constants).
TPU003 tracer-branch    Python ``if``/``while``/``assert`` whose test
                        calls ``jnp.*``/``jax.nn.*``/``lax.*`` — branching
                        on a tracer raises at trace time or, worse, bakes
                        one branch in silently via a concrete aval.
TPU004 dict-order       iterating ``.items()/.keys()/.values()`` without
                        ``sorted()`` in traced code — trace order (and so
                        the compiled program hash) then depends on dict
                        insertion history; the recompilation guard
                        (layer 2) can only catch in-process instances.
TPU005 unscoped-mxu     conv/dot-emitting calls in a plain function with
                        no enclosing ``jax.named_scope`` and no flax
                        module scope — their FLOPs land in hlo_profile's
                        "other" bucket, breaking per-component MFU
                        attribution.
TPU007 host-in-trace    any import of ``mx_rcnn_tpu.obs`` or
                        ``mx_rcnn_tpu.ctrl`` in traced code.  The
                        observability and control planes are host-side by
                        contract (journal writes, HTTP endpoint, wall
                        clocks, fleet mutation): an emit/span/counter or
                        autoscaler call inside a jitted module would at
                        best bake trace-time values and at worst sync or
                        do I/O per step.  (TPU006 is the dynamic bf16
                        upcast walk in tools/tpulint.py.)
TPU008 no-interpret     a ``pallas_call(...)`` without an explicit
                        ``interpret=`` keyword.  Every Pallas kernel in
                        this repo must declare its CPU fallback posture
                        at the call site (threaded from graph.py's
                        ``_pallas_interpret()`` gate): an implicit
                        default means the kernel silently fails to lower
                        off-TPU, and the CI interpret-mode parity suites
                        (test_roi_align, test_pallas) can't reach
                        it.
"""

from __future__ import annotations

import ast
import dataclasses
import hashlib
import os
from typing import Iterable, Optional

# Modules whose code is reachable from the jitted step functions
# (forward_train / forward_inference / forward_proposals / make_train_step).
# Paths are repo-root-relative with "/" separators; a trailing "/" marks a
# package prefix.
TRACED_PREFIXES: tuple[str, ...] = (
    "mx_rcnn_tpu/detection/",
    "mx_rcnn_tpu/models/",
    "mx_rcnn_tpu/geometry/",
    "mx_rcnn_tpu/ops/",
    "mx_rcnn_tpu/parallel/step.py",
    "mx_rcnn_tpu/train/state.py",
    "mx_rcnn_tpu/train/optim.py",
)

RULES: dict[str, str] = {
    "TPU001": "host-sync cast (float/int/bool/.item/.tolist/np.asarray) "
              "in jit-traced code",
    "TPU002": "raw numpy computation in jit-traced code",
    "TPU003": "Python branch on a jnp/lax expression (tracer branching)",
    "TPU004": "unsorted dict iteration in jit-traced code "
              "(trace-order nondeterminism)",
    "TPU005": "MXU-emitting op outside any jax.named_scope / flax module "
              "(unattributable FLOPs)",
    "TPU007": "mx_rcnn_tpu.obs/ctrl imported in jit-traced code (the "
              "observability and control planes are host-side only)",
    "TPU008": "pallas_call without an explicit interpret= kwarg (every "
              "kernel must declare its CPU-fallback posture at the call "
              "site)",
}

# Host-only top-level packages TPU007 fences out of traced code.
_HOST_ONLY_PKGS: tuple[str, ...] = ("obs", "ctrl")
_HOST_ONLY_MODULES: tuple[str, ...] = tuple(
    f"mx_rcnn_tpu.{p}" for p in _HOST_ONLY_PKGS
)

# TPU001: numpy calls that materialize/cast an array on host.
_HOST_CAST_NP = {"asarray", "array"}
# TPU002 allowlist: attribute uses of numpy that are constants/dtypes, not
# computations (np.float32 as a dtype argument, np.pi, np.inf, ...).
# Includes the dtype-introspection calls (issubdtype/iinfo/finfo): static
# host dispatch on an aval's dtype, never a computation on traced values.
_NP_CONST_ATTRS = {
    "float32", "float16", "bfloat16", "int32", "int8", "uint8", "bool_",
    "pi", "inf", "nan", "newaxis", "ndarray", "dtype", "integer",
    "floating", "inexact", "issubdtype", "iinfo", "finfo",
}
# TPU005: calls that emit MXU (conv/dot) work.
_MXU_CALL_NAMES = {
    "conv_general_dilated", "dot_general", "dot", "matmul", "einsum",
    "tensordot", "conv", "conv_transpose",
}


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str        # repo-root-relative, "/" separators
    line: int
    col: int
    snippet: str     # stripped source line (fingerprint material)
    message: str

    def fingerprint(self) -> str:
        """Stable id for the baseline: survives line moves, not edits."""
        key = f"{self.rule}:{self.path}:{self.snippet}"
        return hashlib.sha1(key.encode()).hexdigest()[:12]

    def format(self) -> str:
        return (
            f"{self.path}:{self.line}:{self.col}: {self.rule} "
            f"{RULES[self.rule]}\n    {self.snippet}"
        )


def is_traced_path(rel_path: str) -> bool:
    p = rel_path.replace(os.sep, "/")
    return any(
        p.startswith(pref) if pref.endswith("/") else p == pref
        for pref in TRACED_PREFIXES
    )


def _attr_root(node: ast.expr) -> Optional[str]:
    """Leftmost name of an attribute chain (``np.linalg.norm`` -> "np")."""
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _is_literal(node: ast.expr) -> bool:
    """Constant-foldable at trace time — casts of these never sync."""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.UnaryOp):
        return _is_literal(node.operand)
    if isinstance(node, ast.BinOp):
        return _is_literal(node.left) and _is_literal(node.right)
    return False


class _ImportTracker:
    """Module aliases seen in the file (``import numpy as np`` -> np)."""

    def __init__(self) -> None:
        self.numpy: set[str] = set()
        self.jnp: set[str] = set()
        self.lax: set[str] = set()
        self.jax: set[str] = set()

    def visit_import(self, node: ast.Import) -> None:
        for a in node.names:
            alias = a.asname or a.name.split(".")[0]
            if a.name == "numpy":
                self.numpy.add(alias)
            elif a.name in ("jax.numpy",):
                self.jnp.add(a.asname or "jnp")
            elif a.name == "jax":
                self.jax.add(alias)

    def visit_import_from(self, node: ast.ImportFrom) -> None:
        if node.module == "jax":
            for a in node.names:
                if a.name == "numpy":
                    self.jnp.add(a.asname or "numpy")
                elif a.name == "lax":
                    self.lax.add(a.asname or "lax")
        elif node.module == "jax.numpy":
            pass  # from jax.numpy import X — X calls are rule-invisible


class _Linter(ast.NodeVisitor):
    def __init__(self, path: str, lines: list[str]) -> None:
        self.path = path
        self.lines = lines
        self.imports = _ImportTracker()
        self.findings: list[Finding] = []
        # Lexical context stacks.
        self._scope_depth = 0          # inside `with jax.named_scope(...)`
        self._class_stack: list[ast.ClassDef] = []
        self._branch_depth = 0         # inside an if/while/assert test expr

    # -- helpers ----------------------------------------------------------

    def _emit(self, rule: str, node: ast.AST, message: str = "") -> None:
        line = getattr(node, "lineno", 1)
        snippet = (
            self.lines[line - 1].strip() if line - 1 < len(self.lines) else ""
        )
        self.findings.append(
            Finding(
                rule=rule,
                path=self.path,
                line=line,
                col=getattr(node, "col_offset", 0),
                snippet=snippet,
                message=message or RULES[rule],
            )
        )

    def _in_flax_module(self) -> bool:
        """Flax modules name-scope their ops for free — TPU005 exempts
        them.  Heuristic: any enclosing class whose bases mention Module."""
        for cls in self._class_stack:
            for base in cls.bases:
                name = base.attr if isinstance(base, ast.Attribute) else (
                    base.id if isinstance(base, ast.Name) else ""
                )
                if "Module" in name:
                    return True
        return False

    def _is_named_scope_with(self, node: ast.With) -> bool:
        for item in node.items:
            call = item.context_expr
            if (
                isinstance(call, ast.Call)
                and isinstance(call.func, ast.Attribute)
                and call.func.attr == "named_scope"
            ):
                return True
        return False

    # -- structure --------------------------------------------------------

    def visit_Import(self, node: ast.Import) -> None:
        self.imports.visit_import(node)
        for a in node.names:
            if any(
                a.name == mod or a.name.startswith(mod + ".")
                for mod in _HOST_ONLY_MODULES
            ):
                self._emit("TPU007", node)
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        self.imports.visit_import_from(node)
        mod = node.module or ""
        if any(
            mod == m or mod.startswith(m + ".") for m in _HOST_ONLY_MODULES
        ):
            self._emit("TPU007", node)
        elif mod == "mx_rcnn_tpu" and any(
            a.name in _HOST_ONLY_PKGS for a in node.names
        ):
            self._emit("TPU007", node)
        self.generic_visit(node)

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._class_stack.append(node)
        self.generic_visit(node)
        self._class_stack.pop()

    def visit_With(self, node: ast.With) -> None:
        if self._is_named_scope_with(node):
            self._scope_depth += 1
            self.generic_visit(node)
            self._scope_depth -= 1
        else:
            self.generic_visit(node)

    # -- TPU003: tracer branching ----------------------------------------

    def _check_branch_test(self, test: ast.expr) -> None:
        for sub in ast.walk(test):
            if isinstance(sub, ast.Call):
                root = _attr_root(sub.func)
                if root in self.imports.jnp or root in self.imports.lax:
                    self._emit("TPU003", test)
                    return

    def visit_If(self, node: ast.If) -> None:
        self._check_branch_test(node.test)
        self.generic_visit(node)

    def visit_While(self, node: ast.While) -> None:
        self._check_branch_test(node.test)
        self.generic_visit(node)

    def visit_Assert(self, node: ast.Assert) -> None:
        self._check_branch_test(node.test)
        self.generic_visit(node)

    # -- TPU004: dict-order iteration ------------------------------------

    def _check_dict_iter(self, it: ast.expr) -> None:
        if (
            isinstance(it, ast.Call)
            and isinstance(it.func, ast.Attribute)
            and it.func.attr in ("items", "keys", "values")
            and not it.args
        ):
            self._emit("TPU004", it)

    def visit_For(self, node: ast.For) -> None:
        self._check_dict_iter(node.iter)
        self.generic_visit(node)

    def visit_comprehension(self, node: ast.comprehension) -> None:
        self._check_dict_iter(node.iter)
        self.generic_visit(node)

    # -- calls: TPU001 / TPU002 / TPU005 ---------------------------------

    def visit_Call(self, node: ast.Call) -> None:
        func = node.func
        # sorted(x.items()) is the sanctioned form — don't descend into the
        # sorted() argument with the TPU004 comprehension check (handled in
        # _check_dict_iter callers, which only see raw loop iterables).
        if isinstance(func, ast.Name):
            if (
                func.id in ("float", "int", "bool")
                and len(node.args) == 1
                and not _is_literal(node.args[0])
            ):
                self._emit("TPU001", node)
        elif isinstance(func, ast.Attribute):
            root = _attr_root(func)
            if func.attr in ("item", "tolist") and not node.args:
                self._emit("TPU001", node)
            elif root in self.imports.numpy:
                if func.attr in _HOST_CAST_NP:
                    self._emit("TPU001", node)
                elif func.attr not in _NP_CONST_ATTRS:
                    self._emit("TPU002", node)
            if (
                func.attr in _MXU_CALL_NAMES
                and root in (
                    self.imports.jnp | self.imports.lax | self.imports.jax
                )
                and self._scope_depth == 0
                and not self._in_flax_module()
            ):
                self._emit("TPU005", node)
            # TPU008: pallas_call must state its interpret posture.
            if func.attr == "pallas_call" and not any(
                kw.arg == "interpret" for kw in node.keywords
            ):
                self._emit("TPU008", node)
        self.generic_visit(node)

    def visit_BinOp(self, node: ast.BinOp) -> None:
        # a @ b is a dot_general like any other (TPU005).
        if (
            isinstance(node.op, ast.MatMult)
            and self._scope_depth == 0
            and not self._in_flax_module()
        ):
            self._emit("TPU005", node)
        self.generic_visit(node)


def lint_source(src: str, path: str) -> list[Finding]:
    """Lint one file's source; ``path`` (repo-relative) decides traced-ness.

    Returns [] for non-traced paths — the rules only mean anything where
    code runs under trace.
    """
    if not is_traced_path(path):
        return []
    tree = ast.parse(src, filename=path)
    linter = _Linter(path.replace(os.sep, "/"), src.splitlines())
    linter.visit(tree)
    return sorted(linter.findings, key=lambda f: (f.path, f.line, f.col))


def traced_files(repo_root: str) -> list[str]:
    """All repo-relative python files under the traced prefixes."""
    out = []
    for pref in TRACED_PREFIXES:
        full = os.path.join(repo_root, pref)
        if pref.endswith("/"):
            for dirpath, _dirnames, filenames in os.walk(full):
                for name in sorted(filenames):
                    if name.endswith(".py"):
                        rel = os.path.relpath(
                            os.path.join(dirpath, name), repo_root
                        )
                        out.append(rel.replace(os.sep, "/"))
        elif os.path.exists(full):
            out.append(pref)
    return sorted(set(out))


def lint_paths(
    repo_root: str, paths: Optional[Iterable[str]] = None
) -> list[Finding]:
    """Lint the given repo-relative paths (default: every traced file)."""
    findings: list[Finding] = []
    for rel in paths if paths is not None else traced_files(repo_root):
        with open(os.path.join(repo_root, rel)) as f:
            findings.extend(lint_source(f.read(), rel))
    return findings
