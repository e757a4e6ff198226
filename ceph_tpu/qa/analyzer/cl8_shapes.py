"""CL8 — kernel shape/dtype abstract interpreter for the TPU dirs
(ops/, gf/, crush/).

Shape and dtype mismatches in jitted/Pallas code only surface at trace
time — on the TPU, often behind the codec registry, long after the edit
that broke them — and the GF(2^8) paths additionally depend on EXACT
integer semantics (a silent int->float promotion produces bytes that are
almost right, the worst kind of wrong; arXiv:2108.02692 and the
polynomial-RS realizations in arXiv:1312.5155 both catalogue this class).
The interpreter propagates a small ``(shape, dtype)`` lattice through
every function CL3 identifies as traced (``@jax.jit``, same-module
``jax.jit(fn)``, ``pl.pallas_call`` kernels), seeded by literal
constructors (``jnp.zeros((8, 16), jnp.uint8)``), casts, and reshapes.
Unknown stays unknown — parameters have no static shape, so real
kernels mostly flow Top and the checker only speaks when BOTH sides of
a conflict are provably known:

- ``matmul:*``     contraction-dim mismatch in ``a @ b`` / ``jnp.dot``/
  ``jnp.matmul`` (and literal ``dimension_numbers`` of
  ``lax.dot_general``);
- ``broadcast:*``  an elementwise binop whose known dims can't
  broadcast (unequal, neither 1);
- ``reshape:*``    a reshape whose literal target element count differs
  from the known source count;
- ``promote:*``    arithmetic mixing a concrete int array with a
  concrete float array — the implicit promotion silently leaves the
  GF(2^8)/CRUSH integer domain (explicit ``astype`` is the idiom);
- ``int-div:*``    true division ``/`` on integer arrays — the result
  is float even when both sides are int (use ``//`` or cast first);
- ``host-trip:*``  ``jax.device_get``/``device_put``/
  ``block_until_ready`` inside a traced body — a host<->device round
  trip per trace (or a trace error), never what a kernel wants.

cephdma adds the op-path HOST-TRIP AUDIT on top (``hosttrip:*``
idents): every function — traced or not — in the ``cl8_dirs`` modules
plus ``cl8_hostcopy_files`` (osd/write_batcher.py, osd/ec_backend.py)
is scanned for explicit host<->device traffic: ``jax.device_get`` /
``jax.device_put`` / ``.block_until_ready()`` calls, and
``np.asarray``/``np.array`` wrapped directly around a device-producing
kernel entry point (``apply_matrix_jax``, ``apply_matrix_pallas``,
``_apply_bitmatrix`` — the materialize-at-the-callsite idiom).
The contract is drive-to-zero: a deliberate sync seam (an op's
commit-point fetch, a decoded range serialized into a client reply)
carries an explicit ``# noqa: CL8`` with its reason; everything else
is a finding.  Baseline growth is a regression.

Weak-typed Python scalars adopt the array side's dtype (JAX semantics)
and never report.  ``# noqa: CL8`` / baseline.toml suppress as usual.
"""
from __future__ import annotations

import ast
from dataclasses import dataclass

from .core import Config, Finding, ModuleInfo
from .symbols import SymbolTable, attr_chain, call_name

_INT_DTYPES = {"int8", "int16", "int32", "int64",
               "uint8", "uint16", "uint32", "uint64"}
_FLOAT_DTYPES = {"float16", "float32", "float64", "bfloat16"}
_DTYPE_NAMES = _INT_DTYPES | _FLOAT_DTYPES | {"bool", "bool_"}
_CTOR_DEFAULT_FLOAT = {"zeros", "ones", "empty", "full", "eye", "linspace"}
_LIKE_CTORS = {"zeros_like", "ones_like", "empty_like", "full_like"}
_HOST_TRIPS = {"device_get", "device_put", "block_until_ready"}
_MODULE_ALIASES = {"jnp", "np", "numpy", "onp", "jax", "lax", "pl"}
_ARITH = (ast.Add, ast.Sub, ast.Mult, ast.Div, ast.FloorDiv, ast.Mod,
          ast.Pow)


@dataclass(frozen=True)
class AV:
    """Abstract value: shape is a tuple of (int | None) dims or None for
    wholly unknown; dtype is a dtype name or None; weak marks Python
    scalars (they adopt the other operand's dtype, JAX-style)."""
    shape: tuple | None = None
    dtype: str | None = None
    weak: bool = False


TOP = AV()


def _is_int(dt: str | None) -> bool:
    return dt in _INT_DTYPES


def _is_float(dt: str | None) -> bool:
    return dt in _FLOAT_DTYPES


def _dtype_of_node(node: ast.expr | None) -> str | None:
    """jnp.uint8 / np.float32 / "uint8" -> dtype name."""
    if node is None:
        return None
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value if node.value in _DTYPE_NAMES else None
    ch = attr_chain(node)
    if ch:
        leaf = ch[1][-1] if ch[1] else ch[0]
        return leaf if leaf in _DTYPE_NAMES else None
    return None


def _const_int(node: ast.expr) -> int | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return node.value
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        v = _const_int(node.operand)
        return -v if v is not None else None
    if isinstance(node, ast.BinOp):
        l, r = _const_int(node.left), _const_int(node.right)
        if l is None or r is None:
            return None
        if isinstance(node.op, ast.Mult):
            return l * r
        if isinstance(node.op, ast.Add):
            return l + r
        if isinstance(node.op, ast.Sub):
            return l - r
    return None


def _const_shape(node: ast.expr) -> tuple | None:
    """Literal shape argument: (8, 16) -> (8, 16); 8 -> (8,); dims that
    aren't literal ints become None (unknown dim)."""
    if isinstance(node, (ast.Tuple, ast.List)):
        return tuple(_const_int(e) for e in node.elts)
    v = _const_int(node)
    if v is not None:
        return (v,)
    return None


def _broadcast(a: tuple | None, b: tuple | None):
    """(result_shape, conflict_dim_pair | None) under numpy rules."""
    if a is None or b is None:
        return None, None
    out = []
    for i in range(1, max(len(a), len(b)) + 1):
        da = a[-i] if i <= len(a) else 1
        db = b[-i] if i <= len(b) else 1
        if da is None or db is None:
            out.append(None)
        elif da == db or db == 1:
            out.append(da)
        elif da == 1:
            out.append(db)
        else:
            return None, (da, db)
    return tuple(reversed(out)), None


#: device-producing kernel entry points: np.asarray(<one of these>(...))
#: is a host materialization of a device result at the callsite
_DEVICE_PRODUCERS = {
    "apply_matrix_jax", "apply_matrix_pallas", "_apply_bitmatrix",
}
_MATERIALIZERS = {"asarray", "array"}


def check(mods: list[ModuleInfo], sym: SymbolTable, cfg: Config) -> list[Finding]:
    from .cl3_tracing import collect_traced

    findings: list[Finding] = []
    dirs = set(cfg.cl8_dirs)
    audit_files = set(getattr(cfg, "cl8_hostcopy_files", ()))
    for mod in mods:
        in_dirs = mod.topdir() in dirs
        in_audit = in_dirs or mod.rel in audit_files
        if not in_audit:
            continue
        traced_fns = set()
        if in_dirs:
            for fn, _static, why in collect_traced(mod):
                traced_fns.add(fn)
                interp = _Interp(mod, fn, why)
                interp.run()
                findings.extend(interp.findings)
        findings.extend(_audit_host_trips(mod, traced_fns))
    return findings


def _audit_host_trips(mod: ModuleInfo, traced_fns: set) -> list[Finding]:
    """The cephdma op-path audit (module docstring): explicit
    host<->device traffic outside traced bodies must be a noqa'd
    deliberate seam.  Traced functions are skipped — the interpreter
    above already polices those with the stricter in-trace rule."""
    findings: list[Finding] = []
    seen: set[str] = set()

    def report(node: ast.AST, owner: str, msg: str) -> None:
        ident = f"hosttrip:{owner}"
        n = 2
        while ident in seen:
            ident = f"hosttrip:{owner}:{n}"
            n += 1
        seen.add(ident)
        findings.append(Finding(
            "CL8", mod.rel, getattr(node, "lineno", 1), ident, msg))

    def own_nodes(scope):
        """`scope`'s statements WITHOUT descending into nested
        functions — those are walked (and attributed) on their own."""
        stack = list(ast.iter_child_nodes(scope))
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            yield node
            stack.extend(ast.iter_child_nodes(node))

    def audit_scope(scope, owner: str) -> None:
        for node in own_nodes(scope):
            if not isinstance(node, ast.Call):
                continue
            cn = call_name(node)
            if cn in ("device_get", "device_put", "block_until_ready"):
                report(node, f"{owner}:{cn}",
                       f"[{owner}] explicit host<->device traffic "
                       f"({cn}) on the op path — commit with "
                       f"jnp.asarray under gf.h2d and fetch at the "
                       f"commit point, or mark the deliberate sync "
                       f"with a reasoned noqa")
                continue
            if cn in _MATERIALIZERS and node.args \
                    and isinstance(node.args[0], ast.Call):
                inner = call_name(node.args[0])
                if inner in _DEVICE_PRODUCERS:
                    report(node, f"{owner}:{cn}({inner})",
                           f"[{owner}] {cn}() materializes {inner}'s "
                           f"device result at the callsite — a "
                           f"host-copy sync per call; keep it "
                           f"device-resident (apply_matrix_jax's "
                           f"result + a commit-point fetch) or noqa "
                           f"the deliberate sync")

    # module scope (import-time transfers count too) — own_nodes skips
    # every FunctionDef subtree, so functions are attributed below
    audit_scope(mod.tree, "<module>")
    for fn in mod.walk():
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if fn in traced_fns:
            continue
        audit_scope(fn, fn.name)
    return findings


class _Interp:
    def __init__(self, mod: ModuleInfo, fn: ast.FunctionDef, why: str):
        self.mod = mod
        self.fn = fn
        self.why = why
        self.env: dict[str, AV] = {}
        self.findings: list[Finding] = []
        self._seen: set[str] = set()

    def run(self) -> None:
        self._body(self.fn.body)

    # -- statements --------------------------------------------------------
    def _body(self, body: list[ast.stmt]) -> None:
        for stmt in body:
            self._stmt(stmt)

    def _stmt(self, stmt: ast.stmt) -> None:
        if isinstance(stmt, ast.Assign):
            val = self._ev(stmt.value)
            for t in stmt.targets:
                self._bind(t, val)
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            self._bind(stmt.target, self._ev(stmt.value))
        elif isinstance(stmt, ast.AugAssign):
            synth = ast.BinOp(left=stmt.target, op=stmt.op,
                              right=stmt.value)
            ast.copy_location(synth, stmt)
            ast.fix_missing_locations(synth)
            val = self._ev(synth)
            self._bind(stmt.target, val)
        elif isinstance(stmt, (ast.Expr, ast.Return)):
            if stmt.value is not None:
                self._ev(stmt.value)
        elif isinstance(stmt, (ast.If, ast.While)):
            self._ev(stmt.test)
            self._body(stmt.body)
            self._body(stmt.orelse)
        elif isinstance(stmt, ast.For):
            self._ev(stmt.iter)
            self._bind(stmt.target, TOP)
            self._body(stmt.body)
            self._body(stmt.orelse)
        elif isinstance(stmt, ast.With):
            for item in stmt.items:
                self._ev(item.context_expr)
            self._body(stmt.body)
        elif isinstance(stmt, ast.Try):
            self._body(stmt.body)
            for h in stmt.handlers:
                self._body(h.body)
            self._body(stmt.orelse)
            self._body(stmt.finalbody)
        elif isinstance(stmt, ast.FunctionDef):
            self._body(stmt.body)  # nested kernels see the outer env

    def _bind(self, target: ast.expr, val: AV) -> None:
        if isinstance(target, ast.Name):
            self.env[target.id] = val
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                self._bind(e, TOP)

    # -- expressions -------------------------------------------------------
    def _ev(self, expr: ast.expr) -> AV:
        if isinstance(expr, ast.Name):
            return self.env.get(expr.id, TOP)
        if isinstance(expr, ast.Constant):
            v = expr.value
            if isinstance(v, bool):
                return AV((), "bool", weak=True)
            if isinstance(v, int):
                return AV((), "int32", weak=True)
            if isinstance(v, float):
                return AV((), "float32", weak=True)
            return TOP
        if isinstance(expr, ast.BinOp):
            return self._binop(expr)
        if isinstance(expr, ast.UnaryOp):
            return self._ev(expr.operand)
        if isinstance(expr, ast.Call):
            return self._call(expr)
        if isinstance(expr, ast.Attribute):
            if expr.attr == "T":
                base = self._ev(expr.value)
                if base.shape is not None:
                    return AV(tuple(reversed(base.shape)), base.dtype)
                return AV(None, base.dtype)
            # .shape/.dtype/.at and friends leave the lattice
            self._ev(expr.value)
            return TOP
        if isinstance(expr, ast.Subscript):
            base = self._ev(expr.value)
            if not isinstance(expr.slice, ast.Slice):
                self._ev_slicefree(expr.slice)
            # indexing reshapes unpredictably; keep only the dtype
            return AV(None, base.dtype)
        if isinstance(expr, (ast.Tuple, ast.List)):
            for e in expr.elts:
                self._ev(e)
            return TOP
        if isinstance(expr, ast.Compare):
            self._ev(expr.left)
            for c in expr.comparators:
                self._ev(c)
            ls = self._ev(expr.left).shape
            return AV(ls, "bool")
        if isinstance(expr, ast.BoolOp):
            for v in expr.values:
                self._ev(v)
            return TOP
        if isinstance(expr, ast.IfExp):
            self._ev(expr.test)
            a, b = self._ev(expr.body), self._ev(expr.orelse)
            return a if a.shape is not None else b
        if isinstance(expr, ast.Starred):
            return self._ev(expr.value)
        return TOP

    def _ev_slicefree(self, node: ast.expr) -> None:
        if isinstance(node, ast.Tuple):
            for e in node.elts:
                if not isinstance(e, ast.Slice):
                    self._ev(e)
        elif not isinstance(node, ast.Slice):
            self._ev(node)

    # -- binops ------------------------------------------------------------
    def _binop(self, node: ast.BinOp) -> AV:
        l, r = self._ev(node.left), self._ev(node.right)
        if isinstance(node.op, ast.MatMult):
            return self._matmul(node, l, r)
        shape, conflict = _broadcast(l.shape, r.shape)
        if conflict is not None:
            self._report(node, "broadcast",
                         f"elementwise op broadcasts shapes {l.shape} and "
                         f"{r.shape}: dims {conflict[0]} vs {conflict[1]} "
                         f"are incompatible")
        dtype = self._promote(node, l, r)
        return AV(shape, dtype)

    def _promote(self, node: ast.BinOp, l: AV, r: AV) -> str | None:
        ld = None if l.weak else l.dtype
        rd = None if r.weak else r.dtype
        if isinstance(node.op, ast.Div):
            # only speak when the int domain is PROVEN: one side must be
            # a concrete int array, and the other int-kind too (a weak
            # Python int literal counts; an unknown side could be float,
            # where / is already correct)
            concrete_int = _is_int(ld) or _is_int(rd)
            both_intish = _is_int(l.dtype) and _is_int(r.dtype)
            if concrete_int and both_intish:
                self._report(
                    node, "int-div",
                    f"true division on integer arrays "
                    f"({ld or rd}) silently promotes to float — the "
                    f"GF(2^8)/CRUSH paths need // or an explicit astype")
                return "float32"
        if isinstance(node.op, _ARITH) and _is_int(ld) and _is_float(rd):
            self._report(node, "promote",
                         f"arithmetic mixes {ld} with {rd} — the int "
                         f"side is implicitly promoted to float and "
                         f"leaves the exact-integer domain; cast "
                         f"explicitly with astype")
            return rd
        if isinstance(node.op, _ARITH) and _is_float(ld) and _is_int(rd):
            self._report(node, "promote",
                         f"arithmetic mixes {ld} with {rd} — the int "
                         f"side is implicitly promoted to float and "
                         f"leaves the exact-integer domain; cast "
                         f"explicitly with astype")
            return ld
        if ld is None:
            return rd
        if rd is None:
            return ld
        if ld == rd:
            return ld
        return None

    def _matmul(self, node: ast.AST, l: AV, r: AV) -> AV:
        ls, rs = l.shape, r.shape
        if ls is not None and rs is not None and ls and rs:
            lk = ls[-1]
            rk = rs[-2] if len(rs) >= 2 else rs[0]
            if lk is not None and rk is not None and lk != rk:
                self._report(node, "matmul",
                             f"matmul contraction dims differ: "
                             f"{ls} @ {rs} contracts {lk} against {rk}")
            out = tuple(ls[:-1]) + (tuple(rs[:-2]) + (rs[-1],)
                                    if len(rs) >= 2 else ())
            dtype = l.dtype if l.dtype == r.dtype else None
            return AV(out, dtype)
        dtype = l.dtype if l.dtype == r.dtype else None
        return AV(None, dtype)

    # -- calls -------------------------------------------------------------
    def _call(self, node: ast.Call) -> AV:
        for a in node.args:
            self._ev(a)
        for kw in node.keywords:
            self._ev(kw.value)
        cn = call_name(node)
        f = node.func
        if cn in _HOST_TRIPS:
            self._report(node, "host-trip",
                         f"{cn} inside a traced body forces a "
                         f"host<->device round trip per call (or a trace "
                         f"error); keep kernels device-only")
            return TOP
        kwmap = {kw.arg: kw.value for kw in node.keywords if kw.arg}

        # dtype-constructor casts: jnp.uint8(x), np.int32(x)
        if cn in _DTYPE_NAMES and node.args:
            inner = self._ev(node.args[0])
            return AV(inner.shape, cn)

        # jnp.reshape / np.where / lax.dot_general are module FUNCTIONS,
        # not methods — route them past the method branch (whose receiver
        # eval would misparse the array argument as the shape)
        is_module_fn = isinstance(f, ast.Attribute) \
            and isinstance(f.value, ast.Name) \
            and f.value.id in _MODULE_ALIASES
        if isinstance(f, ast.Attribute) and not is_module_fn:
            recv = self._ev(f.value)
            if cn == "astype" and node.args:
                dt = _dtype_of_node(node.args[0])
                return AV(recv.shape, dt or None)
            if cn == "reshape":
                return self._reshape(node, recv, node.args, kwmap)
            if cn == "transpose":
                if recv.shape is not None and not node.args:
                    return AV(tuple(reversed(recv.shape)), recv.dtype)
                return AV(None, recv.dtype)
            if cn in ("sum", "min", "max", "prod"):
                dt = _dtype_of_node(kwmap.get("dtype")) or recv.dtype
                return AV(None, dt)
            if cn == "mean":
                return AV(None, "float32")

        # module-level jnp/np constructors and transforms
        if cn in _CTOR_DEFAULT_FLOAT and node.args:
            shape = _const_shape(node.args[0]) if cn != "eye" else None
            if cn == "eye":
                n = _const_int(node.args[0])
                shape = (n, n) if n is not None else None
            dt = _dtype_of_node(kwmap.get("dtype"))
            if dt is None and cn == "full" and len(node.args) >= 3:
                dt = _dtype_of_node(node.args[2])
            elif dt is None and cn not in ("full",) and len(node.args) >= 2:
                dt = _dtype_of_node(node.args[1])
            return AV(shape, dt or "float32")
        if cn in _LIKE_CTORS and node.args:
            src = self._ev(node.args[0])
            dt = _dtype_of_node(kwmap.get("dtype")) or src.dtype
            return AV(src.shape, dt)
        if cn == "arange":
            n = _const_int(node.args[0]) if node.args else None
            dt = _dtype_of_node(kwmap.get("dtype")) or "int32"
            return AV((n,) if n is not None and len(node.args) == 1 else None,
                      dt)
        if cn in ("asarray", "array") and node.args:
            src = self._ev(node.args[0])
            dt = _dtype_of_node(kwmap.get("dtype"))
            if dt is None and len(node.args) >= 2:
                dt = _dtype_of_node(node.args[1])
            return AV(src.shape, dt or src.dtype)
        if cn == "reshape" and node.args:
            src = self._ev(node.args[0])
            return self._reshape(node, src, node.args[1:], kwmap)
        if cn == "where" and len(node.args) == 3:
            a, b = self._ev(node.args[1]), self._ev(node.args[2])
            shape, conflict = _broadcast(a.shape, b.shape)
            if conflict is not None:
                self._report(node, "broadcast",
                             f"where() branches have incompatible shapes "
                             f"{a.shape} vs {b.shape}")
            return AV(shape, a.dtype if a.dtype == b.dtype else None)
        if cn in ("dot", "matmul") and len(node.args) >= 2:
            return self._matmul(node, self._ev(node.args[0]),
                                self._ev(node.args[1]))
        if cn == "dot_general" and len(node.args) >= 2:
            return self._dot_general(node, kwmap)
        if cn == "stack" and node.args \
                and isinstance(node.args[0], (ast.List, ast.Tuple)):
            elts = [self._ev(e) for e in node.args[0].elts]
            if elts and all(e.shape == elts[0].shape and e.shape is not None
                            for e in elts):
                return AV((len(elts),) + elts[0].shape, elts[0].dtype)
            return TOP
        return TOP

    def _reshape(self, node: ast.AST, src: AV, args: list[ast.expr],
                 kwmap: dict) -> AV:
        if len(args) == 1:
            shape = _const_shape(args[0])
        else:
            shape = tuple(_const_int(a) for a in args) if args else None
        if shape is None:
            return AV(None, src.dtype)
        if src.shape is not None and all(d is not None for d in src.shape):
            src_n = 1
            for d in src.shape:
                src_n *= d
            knowns = [d for d in shape if d is not None and d != -1]
            tgt_n = 1
            for d in knowns:
                tgt_n *= d
            if all(d is not None for d in shape) and -1 not in shape:
                if tgt_n != src_n:
                    self._report(
                        node, "reshape",
                        f"reshape {src.shape} -> {shape}: element count "
                        f"{src_n} != {tgt_n}")
            elif -1 in shape and tgt_n and src_n % tgt_n:
                self._report(
                    node, "reshape",
                    f"reshape {src.shape} -> {shape}: {src_n} elements "
                    f"don't divide by the known dims ({tgt_n})")
        return AV(shape, src.dtype)

    def _dot_general(self, node: ast.Call, kwmap: dict) -> AV:
        l, r = self._ev(node.args[0]), self._ev(node.args[1])
        dn = kwmap.get("dimension_numbers")
        if len(node.args) >= 3 and dn is None:
            dn = node.args[2]
        pairs = _literal_dim_numbers(dn)
        if pairs is not None and l.shape is not None and r.shape is not None:
            for lc, rc in pairs:
                if lc < len(l.shape) and rc < len(r.shape):
                    dl, dr = l.shape[lc], r.shape[rc]
                    if dl is not None and dr is not None and dl != dr:
                        self._report(
                            node, "matmul",
                            f"dot_general contracts dim {lc} of "
                            f"{l.shape} ({dl}) against dim {rc} of "
                            f"{r.shape} ({dr})")
        dt = _dtype_of_node(kwmap.get("preferred_element_type"))
        return AV(None, dt)

    def _report(self, node: ast.AST, kind: str, msg: str) -> None:
        ident = f"{self.fn.name}:{kind}"
        n = 2
        while ident in self._seen:
            ident = f"{self.fn.name}:{kind}:{n}"
            n += 1
        self._seen.add(ident)
        self.findings.append(Finding(
            "CL8", self.mod.rel, getattr(node, "lineno", self.fn.lineno),
            ident, f"[{self.why}:{self.fn.name}] {msg}"))


def _literal_dim_numbers(node: ast.expr | None):
    """(((lc,), (rc,)), ((), ())) literal -> [(lc, rc), ...]; None when
    not a literal."""
    if not isinstance(node, ast.Tuple) or not node.elts:
        return None
    contract = node.elts[0]
    if not isinstance(contract, ast.Tuple) or len(contract.elts) != 2:
        return None
    lcs, rcs = contract.elts
    if not isinstance(lcs, ast.Tuple) or not isinstance(rcs, ast.Tuple):
        return None
    out = []
    for le, re_ in zip(lcs.elts, rcs.elts):
        lv, rv = _const_int(le), _const_int(re_)
        if lv is None or rv is None:
            return None
        out.append((lv, rv))
    return out
