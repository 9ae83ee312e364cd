"""JAX staging audit: rules for code inside `jax.jit`-staged functions in
the device consensus engine (docs/analysis.md).

Staged functions are discovered two ways, matching the idioms in
babble_tpu/tpu/:

- decorated:  `@jax.jit` or `@functools.partial(jax.jit, ...)`
- wrapped:    `g = jax.jit(f)` / `g = functools.partial(jax.jit, ...)(f)`
  at module level, where `f` is a module function.
- shard_mapped: `shard_map(f, mesh=..., in_specs=..., out_specs=...)`
  anywhere in the module (tpu/sharded.py builds these inside cached
  factory functions), where `f` is a module or nested function. A
  shard_mapped function is traced exactly like a jitted one — and it is
  the per-shard device code of the queued mesh dispatch path
  (tpu/dispatch.py), where a stray host sync would serialize the whole
  async pipeline — so every parameter is audited as a tracer (shard_map
  has no static_argnames channel).

`static_argnames` are honored: branching on a static argument is
concretized at trace time and is fine.

Rules (waiver tag `jax-ok`):

- jax-tracer-branch — Python `if`/`while` whose test directly references
  a non-static parameter of the staged function. Tracers have no stable
  truth value: at best this crashes with a ConcretizationTypeError, at
  worst (via shape-dependent rebinding) it silently bakes one branch into
  the compiled program. Use `jnp.where` / `lax.cond` / `lax.while_loop`.
  `x is None` / `is not None` and `isinstance` tests are exempt (they
  probe the Python-level binding, not the traced value).
- jax-host-sync — `.item()`, `float()`/`int()` on a parameter,
  `np.asarray` / `np.array`, and `jax.device_get` inside a staged
  function: each forces a device round-trip mid-kernel (or a trace
  error), serializing the pipeline the engine exists to keep on-device.
- jax-float-order — ordering comparisons (< <= > >=) on an operand that
  was just cast to a float dtype (`.astype(jnp.float32)` etc. or a
  `jnp.float32(...)` call). Consensus ordering must be exact; f32 is only
  safe below 2^24 and such casts belong on matmul inputs, not comparison
  operands (the established idiom casts back to int32 first — see
  tpu/frontier.py build_inv).

The analysis is per-function and non-transitive: helpers called FROM a
staged function are not audited (their `if`s are usually static shape
logic, e.g. kernels.suffix_min's log-step loop). The jit boundary is
where the contract lives; keep tracer-hostile code out of it.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from .core import Finding, SourceFile, dotted_name

WAIVER = "jax-ok"

FLOAT_DTYPES = {
    "float16", "float32", "float64", "bfloat16", "float_", "double",
}
HOST_SYNC_CALLS = {"jax.device_get", "np.asarray", "np.array",
                   "numpy.asarray", "numpy.array", "onp.asarray"}

# spellings of shard_map at its call sites (tpu/sharded.py calls
# jax.shard_map; the aliases cover the lint's own scratch fixtures)
SHARD_MAP_CALLEES = {
    "shard_map", "_shard_map", "jax.shard_map",
    "jax.experimental.shard_map.shard_map", "_exp_shard_map",
}


def _is_jit_expr(node: ast.AST) -> Tuple[bool, Tuple[str, ...]]:
    """(is jax.jit or functools.partial(jax.jit, ...), static_argnames)."""
    name = dotted_name(node)
    if name in ("jax.jit", "jit"):
        return True, ()
    if isinstance(node, ast.Call):
        callee = dotted_name(node.func)
        if callee in ("functools.partial", "partial"):
            if node.args and dotted_name(node.args[0]) in ("jax.jit", "jit"):
                return True, _static_argnames(node)
        elif callee in ("jax.jit", "jit"):
            return True, _static_argnames(node)
    return False, ()


def _static_argnames(call: ast.Call) -> Tuple[str, ...]:
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                return (v.value,)
            if isinstance(v, (ast.Tuple, ast.List)):
                return tuple(
                    e.value for e in v.elts
                    if isinstance(e, ast.Constant) and isinstance(e.value, str)
                )
    return ()


def find_staged_functions(
    sf: SourceFile,
) -> Dict[str, Tuple[ast.FunctionDef, Tuple[str, ...]]]:
    """{function name: (def node, static_argnames)} for every module
    function staged by jit, whether decorated or wrapped at module level."""
    defs: Dict[str, ast.FunctionDef] = {}
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.FunctionDef):
            defs.setdefault(node.name, node)

    staged: Dict[str, Tuple[ast.FunctionDef, Tuple[str, ...]]] = {}
    for name, fn in defs.items():
        for dec in fn.decorator_list:
            is_jit, statics = _is_jit_expr(dec)
            if is_jit:
                staged[name] = (fn, statics)
    # wrapped forms: x = jax.jit(f, ...) | x = partial(jax.jit, ...)(f)
    for node in sf.tree.body:
        if not isinstance(node, ast.Assign) or not isinstance(node.value, ast.Call):
            continue
        call = node.value
        is_jit, statics = _is_jit_expr(call.func)
        if not is_jit:
            continue
        if dotted_name(call.func) in ("jax.jit", "jit"):
            # direct jax.jit(f, static_argnames=...): statics sit on this
            # call, not on an inner partial
            statics = _static_argnames(call)
        for arg in call.args:
            target = dotted_name(arg)
            if target in defs and target not in staged:
                staged[target] = (defs[target], statics)
    # shard_mapped forms: shard_map(f, mesh=..., ...) ANYWHERE in the
    # module (the sharded backend builds them inside lru_cached factory
    # functions, so module-level assignment scanning never sees them).
    # Only the first positional argument is the staged function; every
    # parameter is a tracer (no static_argnames channel).
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        if dotted_name(node.func) not in SHARD_MAP_CALLEES:
            continue
        target = dotted_name(node.args[0])
        if target in defs and target not in staged:
            staged[target] = (defs[target], ())
    return staged


def _test_is_binding_probe(test: ast.expr) -> bool:
    """True for `x is None` / `x is not None` / isinstance(...) tests —
    Python-level probes that are legitimate on traced call paths."""
    if isinstance(test, ast.Compare) and all(
        isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops
    ):
        return True
    if isinstance(test, ast.Call) and dotted_name(test.func) == "isinstance":
        return True
    if isinstance(test, ast.BoolOp):
        return all(_test_is_binding_probe(v) for v in test.values)
    if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
        return _test_is_binding_probe(test.operand)
    return False


def _names_in(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _has_float_cast(node: ast.AST) -> bool:
    """Expression contains `.astype(<float dtype>)` or `jnp.float32(...)`
    style construction."""
    for sub in ast.walk(node):
        if not isinstance(sub, ast.Call):
            continue
        callee = dotted_name(sub.func)
        if callee is not None and callee.rsplit(".", 1)[-1] in FLOAT_DTYPES:
            return True
        if (
            isinstance(sub.func, ast.Attribute)
            and sub.func.attr == "astype"
            and any(_names_float_dtype(a) for a in sub.args)
        ):
            return True
    return False


def _names_float_dtype(node: ast.AST) -> bool:
    name = dotted_name(node)
    if name is not None and name.rsplit(".", 1)[-1] in FLOAT_DTYPES:
        return True
    return isinstance(node, ast.Constant) and node.value is float


class _StagedVisitor(ast.NodeVisitor):
    def __init__(
        self,
        sf: SourceFile,
        fn: ast.FunctionDef,
        tracer_params: Set[str],
    ) -> None:
        self.sf = sf
        self.fn = fn
        self.tracer_params = tracer_params
        self.findings: List[Finding] = []

    def _emit(self, rule: str, node: ast.AST, message: str) -> None:
        if self.sf.has_waiver(node.lineno, WAIVER):
            return
        self.findings.append(
            Finding(rule=rule, path=self.sf.path, line=node.lineno,
                    message=message, symbol=self.fn.name)
        )

    # -- tracer branches ---------------------------------------------------

    def _check_branch(self, node, kind: str) -> None:
        test = node.test
        if _test_is_binding_probe(test):
            return
        hit = _names_in(test) & self.tracer_params
        if hit:
            self._emit(
                "jax-tracer-branch", node,
                f"Python `{kind}` on traced value(s) {sorted(hit)} inside a "
                "jit-staged function; use jnp.where / lax.cond / "
                "lax.while_loop (or declare the argument in "
                "static_argnames if it is genuinely static)",
            )

    def visit_If(self, node: ast.If) -> None:  # noqa: N802
        self._check_branch(node, "if")
        self.generic_visit(node)

    def visit_While(self, node: ast.While) -> None:  # noqa: N802
        self._check_branch(node, "while")
        self.generic_visit(node)

    def visit_IfExp(self, node: ast.IfExp) -> None:  # noqa: N802
        self._check_branch(node, "if-expression")
        self.generic_visit(node)

    # -- host syncs --------------------------------------------------------

    def visit_Call(self, node: ast.Call) -> None:  # noqa: N802
        callee = dotted_name(node.func)
        if isinstance(node.func, ast.Attribute) and node.func.attr == "item":
            self._emit(
                "jax-host-sync", node,
                ".item() inside a jit-staged function forces a host "
                "round-trip (ConcretizationTypeError under trace); keep "
                "the value on device",
            )
        elif callee in HOST_SYNC_CALLS:
            self._emit(
                "jax-host-sync", node,
                f"{callee}() materializes device data on host mid-kernel; "
                "stay in jnp (device_get/asarray belong outside the jit "
                "boundary)",
            )
        elif callee in ("float", "int", "bool") and node.args:
            if _names_in(node.args[0]) & self.tracer_params:
                self._emit(
                    "jax-host-sync", node,
                    f"{callee}() on a traced value concretizes it "
                    "(host sync / trace error); use jnp casts",
                )
        self.generic_visit(node)

    # -- float ordering ----------------------------------------------------

    def visit_Compare(self, node: ast.Compare) -> None:  # noqa: N802
        if any(isinstance(op, (ast.Lt, ast.LtE, ast.Gt, ast.GtE)) for op in node.ops):
            operands = [node.left, *node.comparators]
            if any(_has_float_cast(o) for o in operands):
                self._emit(
                    "jax-float-order", node,
                    "ordering comparison on a float-cast operand: f32 is "
                    "exact only below 2^24 and consensus ordering must be "
                    "exact — cast back to int32 before comparing (see "
                    "tpu/frontier.py build_inv for the idiom)",
                )
        self.generic_visit(node)


def check_staging(sf: SourceFile) -> Iterable[Finding]:
    findings: List[Finding] = []
    for name, (fn, statics) in find_staged_functions(sf).items():
        params = {
            a.arg
            for a in (
                *fn.args.posonlyargs, *fn.args.args, *fn.args.kwonlyargs
            )
        }
        tracer_params = params - set(statics)
        visitor = _StagedVisitor(sf, fn, tracer_params)
        for stmt in fn.body:
            visitor.visit(stmt)
        findings.extend(visitor.findings)
    return findings
