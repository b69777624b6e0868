"""Compile nGQL predicate subtrees to vectorized jnp mask functions.

The reference evaluates pushed-down edge filters row-at-a-time inside
storaged's scan loop (StorageExpressionContext; reference:
src/storage/exec [UNVERIFIED — empty mount, SURVEY §0]).  Here the same
predicate becomes ONE jnp expression over whole property columns — the
north-star "vectorized property-predicate mask" — with the host
interpreter's exact semantics:

  * three-valued logic: every compiled term is a (value, is_null) pair;
    Kleene AND/OR, null-propagating arithmetic & comparisons;
  * division / modulo by zero → null (NullKind collapses to "drop row"
    under a WHERE, which is all a mask needs);
  * strings are dict codes (int64): ==, !=, IN compile; ordering /
    CONTAINS / regex on strings do NOT (structural `compilable()` check
    refuses fusion, the row stays on the host path);
  * NULL sentinels: INT64_MIN in int/string columns, NaN in floats.

`compilable(expr, etypes)` is the static gate the optimizer rule uses;
`compile_predicate(expr, block, pool)` produces the mask fn used inside
the hop kernel.  Columns arrive as a dict: reserved keys `_rank`, `_src`, `_dst` (endpoint DENSE ids for id($^)/id($$)) plus one
key per edge property name.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Sequence, Set, Tuple

import jax.numpy as jnp
import numpy as np

from ..core import expr as E
from ..core.value import NullValue
from ..graphstore.csr import INT_NULL, StringPool
from ..graphstore.schema import PropType
from .device import join_halves, nan_halves


class CannotCompile(Exception):
    pass


_NUMERIC = ("int", "float")
_CMP_OPS = ("==", "!=", "<", "<=", ">", ">=")
_ARITH_OPS = ("+", "-", "*", "/", "%")
_BIT_OPS = ("&", "|", "^")          # int-only; others refuse
_LOGIC_OPS = ("AND", "OR", "XOR")


def _is_string_type(pt: PropType) -> bool:
    return pt in (PropType.STRING, PropType.FIXED_STRING)


def _kind_of(pt: PropType) -> str:
    if pt in (PropType.FLOAT, PropType.DOUBLE):
        return "float"
    if _is_string_type(pt):
        return "str"
    if pt == PropType.BOOL:
        return "bool"
    # Temporal kinds stay distinct: the host engine returns BAD_TYPE for
    # e.g. DateTime < int, so the device must not compare their raw int
    # encodings against numeric literals (same-kind compares are fine —
    # the encodings are order-isomorphic).
    if pt == PropType.DATE:
        return "date"
    if pt == PropType.TIME:
        return "time"
    if pt == PropType.DATETIME:
        return "datetime"
    if pt == PropType.DURATION:
        return "duration"
    if pt == PropType.GEOGRAPHY:
        return "geo"    # distinct kind: no device op compares geographies
    return "int"        # ints + TIMESTAMP (host value is a plain int)


# ---------------------------------------------------------------------------
# Static compilability gate (no pool / schema values needed)
# ---------------------------------------------------------------------------


def compilable(e: E.Expr, etypes: Sequence[str]) -> bool:
    """True iff `compile_predicate` will succeed for this expr against a
    single-block hop over one of `etypes`.  Conservative."""
    try:
        _check(e, set(etypes))
        return True
    except CannotCompile:
        return False


def _edge_prop_ref(e: E.Expr):
    """Normalize the three spellings of an edge-property reference:
    EdgeProp (validator-canonical), AttributeExpr(LabelExpr) (raw parse of
    `knows.w`), rank(edge).  Returns (edge_name_or_None, prop) or None."""
    if isinstance(e, E.EdgeProp):
        return (e.edge, e.name)
    if isinstance(e, E.AttributeExpr) and isinstance(e.obj, E.LabelExpr):
        return (e.obj.name, e.attr)
    if (isinstance(e, E.FunctionCall) and e.name.lower() == "rank"
            and len(e.args) == 1 and isinstance(e.args[0], E.EdgeExpr)):
        return (None, "_rank")
    return None


def _vid_ref(e: E.Expr):
    """id($$) / id($^) → the capture column holding that endpoint's
    DENSE id ("_dst" / "_src").  Compilable only in direct comparisons
    against literal vids (the literal translates to a dense id at
    compile time; arbitrary arithmetic over vids cannot)."""
    if (isinstance(e, E.FunctionCall) and e.name.lower() == "id"
            and len(e.args) == 1 and getattr(e.args[0], "kind", "")
            == "vertex"):
        which = getattr(e.args[0], "which", "")
        if which == "$$":
            return "_dst"
        if which == "$^":
            return "_src"
    return None


def _nonnull_lit(x: E.Expr) -> bool:
    """Literal usable in a dense-id compare.  NULL is out (comparison
    answers NULL on the host — see _id_pred_shape_ok) and so is bool:
    hash(True)==hash(1) would resolve a dense id for `id(v) == true`
    while host v_eq answers False for int-vs-bool."""
    return (isinstance(x, E.Literal) and x.value is not None
            and not isinstance(x.value, (NullValue, bool)))


def _id_pred_shape_ok(e: "E.Binary", l_ref: bool, r_ref: bool) -> bool:
    """Shared id-vs-literal shape gate for the edge plane (id($$)/id($^))
    and the vertex plane (id(alias)).  NULL literals are rejected: the
    host's comparison-with-NULL answers NULL (row dropped), which a
    dense-id compare cannot express for the negated ops ('!=' /
    'NOT IN' would mask every row back IN)."""
    if e.op in ("==", "!=") and ((l_ref and _nonnull_lit(e.rhs))
                                 or (r_ref and _nonnull_lit(e.lhs))):
        return True
    if e.op in ("IN", "NOT IN") and l_ref \
            and isinstance(e.rhs, (E.ListExpr, E.SetExpr)) \
            and all(_nonnull_lit(i) for i in e.rhs.items):
        return True
    return False


def _check(e: E.Expr, etypes: Set[str]):
    if isinstance(e, E.Literal):
        v = e.value
        if v is None or isinstance(v, (bool, int, float, str, NullValue)):
            return
        raise CannotCompile(f"literal {type(v)}")
    ref = _edge_prop_ref(e)
    if ref is not None:
        edge, name = ref
        if name in ("_src", "_dst", "_type"):
            raise CannotCompile("edge reserved prop beyond _rank")
        if name != "_rank" and len(etypes) != 1:
            raise CannotCompile("prop predicate over multiple edge types")
        # "__edge__" is the planner's alias for the edge being traversed
        # (MATCH inline props, _edge_pred) — always the single etype here
        if name != "_rank" and edge != "__edge__" and edge not in etypes:
            raise CannotCompile(f"predicate on non-traversed edge {edge}")
        return
    if isinstance(e, E.Unary):
        if e.op in ("NOT", "-", "+", "IS_NULL", "IS_NOT_NULL"):
            _check(e.operand, etypes)
            return
        raise CannotCompile(f"unary {e.op}")
    if isinstance(e, E.Binary):
        # endpoint-id predicate: id($$)/id($^) vs literal vid(s) only
        lv, rv = _vid_ref(e.lhs), _vid_ref(e.rhs)
        if lv or rv:
            if _id_pred_shape_ok(e, bool(lv), bool(rv)):
                return
            raise CannotCompile(
                "id($$)/id($^) only compiles vs non-null literal vids")
        if e.op in _LOGIC_OPS + _CMP_OPS + _ARITH_OPS + _BIT_OPS:
            _check(e.lhs, etypes)
            _check(e.rhs, etypes)
            return
        if e.op in ("IN", "NOT IN"):
            _check(e.lhs, etypes)
            if not isinstance(e.rhs, (E.ListExpr, E.SetExpr)):
                raise CannotCompile("IN rhs must be a literal list")
            for item in e.rhs.items:
                if not isinstance(item, E.Literal):
                    raise CannotCompile("IN item not literal")
            return
        raise CannotCompile(f"binary {e.op}")
    raise CannotCompile(f"expr kind {e.kind}")


# ---------------------------------------------------------------------------
# Compilation — terms are (value_array, null_mask, kind)
# ---------------------------------------------------------------------------

Term = Tuple[Any, Any, str]             # (val, isnull, kind)
MaskFn = Callable[[Dict[str, Any]], Any]


def compile_predicate(e: E.Expr, prop_types: Dict[str, PropType],
                      pool: StringPool,
                      vid_to_dense=None) -> Tuple[MaskFn, List[str]]:
    """Returns (mask_fn, needed_columns).  mask_fn(cols) -> bool array:
    True where the predicate evaluates to (non-null) true.

    vid_to_dense: vid → dense id (-1 unknown), required to compile
    id($$)/id($^) comparisons — the literal vid translates to the dense
    currency the kernel's src/dst columns carry."""
    needed: Set[str] = set()

    def dense_of(v):
        if vid_to_dense is None:
            raise CannotCompile("no vid→dense mapping for id() predicate")
        d = vid_to_dense(v)
        return int(d) if d is not None else -1

    def vid_cmp(col, op, values):
        """id(endpoint) ==/!=/IN literal vid(s) → dense comparison;
        unknown vids map to -1, which no real dense id equals."""
        needed.add(col)
        dv = [dense_of(v.value) for v in values]

        def g(c):
            ep = c[col]
            m = jnp.zeros(jnp.shape(ep), bool)
            for d in dv:
                m = m | (ep == d)
            if op in ("!=", "NOT IN"):
                m = jnp.logical_not(m)
            return (m, jnp.zeros(jnp.shape(ep), bool), "bool")
        return g

    def build(x: E.Expr) -> Callable[[Dict[str, Any]], Term]:
        if isinstance(x, E.Binary):
            lv, rv = _vid_ref(x.lhs), _vid_ref(x.rhs)
            if lv or rv:
                if x.op in ("==", "!="):
                    col = lv or rv
                    lit = x.rhs if lv else x.lhs
                    if not isinstance(lit, E.Literal):
                        raise CannotCompile("id() vs non-literal")
                    return vid_cmp(col, x.op, [lit])
                if x.op in ("IN", "NOT IN") and lv:
                    return vid_cmp(lv, x.op, list(x.rhs.items))
                raise CannotCompile("id() predicate shape")
        if isinstance(x, E.Literal):
            return _lit(x.value, pool)
        ref = _edge_prop_ref(x)
        if ref is not None:
            _, pname = ref
            if pname == "_rank":
                needed.add("_rank")
                return lambda c: (c["_rank"],
                                  jnp.zeros(c["_rank"].shape, bool), "int")
            pt = prop_types.get(pname)
            if pt is None:
                raise CannotCompile(f"unknown edge prop {pname}")
            kind = _kind_of(pt)
            name = pname
            needed.add(name)
            # the kernels hand a property column's gathered slots over
            # as their 32-bit halves (device.py `split_halves`)
            dtype = jnp.float64 if kind == "float" else jnp.int64

            def g(c):
                v = join_halves(c[name], dtype)
                if kind == "float":
                    return (v, nan_halves(c[name]), "float")
                if kind == "bool":
                    return (v != 0, v == INT_NULL, "bool")
                return (v, v == INT_NULL, kind)
            return g
        if isinstance(x, E.Unary):
            return _unary(x.op, build(x.operand))
        if isinstance(x, E.Binary):
            if x.op in ("IN", "NOT IN"):
                return _in_list(build(x.lhs),
                                [it.value for it in x.rhs.items],
                                pool, negate=x.op == "NOT IN")
            return _binary(x.op, build(x.lhs), build(x.rhs))
        raise CannotCompile(f"expr kind {x.kind}")

    term = build(e)

    def mask_fn(cols: Dict[str, Any]):
        val, isnull, kind = term(cols)
        if kind != "bool":
            # non-bool WHERE result: host to_bool3 yields null → drop row
            return jnp.zeros(val.shape, bool)
        return jnp.logical_and(val, jnp.logical_not(isnull))

    return mask_fn, sorted(needed)


def _term_alg(xp):
    """Build the (value, is_null, kind) term algebra over one array
    namespace.  The SAME code compiles the in-kernel jnp mask functions
    (hop predicate pushdown) and the host-side numpy vertex-predicate
    masks (fused MATCH tail, match_agg.py) — jnp and np agree on every
    op used here, so the two planes cannot drift semantically."""

    def _lit(v: Any, pool: StringPool) -> Callable[[Dict[str, Any]], Term]:
        if v is None or isinstance(v, NullValue):
            return lambda c: (xp.zeros((), xp.int64), xp.ones((), bool),
                              "int")
        if isinstance(v, bool):
            return lambda c: (xp.asarray(v), xp.zeros((), bool), "bool")
        if isinstance(v, int):
            if not (-(1 << 63) <= v < (1 << 63)):
                # host compares arbitrary-precision ints; fall back
                raise CannotCompile("int literal outside int64")
            return lambda c: (xp.asarray(v, xp.int64), xp.zeros((), bool),
                              "int")
        if isinstance(v, float):
            return lambda c: (xp.asarray(v, xp.float64),
                              xp.zeros((), bool), "float")
        if isinstance(v, str):
            code = pool.lookup(v)   # -2 when absent: equals nothing non-null
            return lambda c: (xp.asarray(code, xp.int64),
                              xp.zeros((), bool), "str")
        raise CannotCompile(f"literal {type(v)}")

    def _unary(op: str, f) -> Callable[[Dict[str, Any]], Term]:
        def g(c):
            v, n, k = f(c)
            if op == "IS_NULL":
                return (n, xp.zeros(xp.shape(n), bool), "bool")
            if op == "IS_NOT_NULL":
                return (xp.logical_not(n), xp.zeros(xp.shape(n), bool),
                        "bool")
            if op == "NOT":
                if k != "bool":
                    raise CannotCompile("NOT on non-bool")
                return (xp.logical_not(v), n, "bool")
            if op == "-":
                if k not in _NUMERIC:
                    raise CannotCompile("negate non-numeric")
                return (-v, n, k)
            if op == "+":
                if k not in _NUMERIC:
                    raise CannotCompile("+x non-numeric")
                return (v, n, k)
            raise CannotCompile(f"unary {op}")
        return g

    def _coerce_pair(av, ak, bv, bk):
        """Numeric promotion for mixed int/float operands."""
        if ak == bk:
            return av, bv, ak
        if set((ak, bk)) == {"int", "float"}:
            return (av.astype(xp.float64) if ak == "int" else av,
                    bv.astype(xp.float64) if bk == "int" else bv, "float")
        raise CannotCompile(f"type mix {ak}/{bk}")

    def _binary(op: str, fa, fb) -> Callable[[Dict[str, Any]], Term]:
        def g(c):
            av, an, ak = fa(c)
            bv, bn, bk = fb(c)
            if op in _LOGIC_OPS:
                if ak != "bool" or bk != "bool":
                    raise CannotCompile("logic on non-bool")
                if op == "AND":
                    is_false = (~an & ~av) | (~bn & ~bv)
                    val = ~is_false
                    null = ~is_false & (an | bn)
                    return (val & ~null, null, "bool")
                if op == "OR":
                    is_true = (~an & av) | (~bn & bv)
                    null = ~is_true & (an | bn)
                    return (is_true, null, "bool")
                # XOR
                return (xp.logical_xor(av, bv), an | bn, "bool")
            if op in _CMP_OPS:
                null = an | bn
                if "str" in (ak, bk) or "bool" in (ak, bk) or "geo" in (ak, bk):
                    if ak != bk:
                        raise CannotCompile(f"compare {ak} vs {bk}")
                    if op not in ("==", "!="):
                        # dict codes are insertion-ordered, not value-ordered
                        raise CannotCompile(f"ordering on {ak}")
                    val = (av == bv) if op == "==" else (av != bv)
                    return (val, null, "bool")
                a2, b2, _ = _coerce_pair(av, ak, bv, bk)
                val = {"==": a2 == b2, "!=": a2 != b2, "<": a2 < b2,
                       "<=": a2 <= b2, ">": a2 > b2, ">=": a2 >= b2}[op]
                return (val, null, "bool")
            if op in _ARITH_OPS:
                if ak not in _NUMERIC or bk not in _NUMERIC:
                    raise CannotCompile(f"arith on {ak}/{bk}")
                a2, b2, k = _coerce_pair(av, ak, bv, bk)
                null = an | bn
                if op == "+":
                    return (a2 + b2, null, k)
                if op == "-":
                    return (a2 - b2, null, k)
                if op == "*":
                    return (a2 * b2, null, k)
                if op == "/":
                    null = null | (b2 == 0)
                    safe = xp.where(b2 == 0, xp.ones((), b2.dtype), b2)
                    if k == "int":
                        # host semantics: truncation toward zero
                        q = xp.abs(a2) // xp.abs(safe)
                        sign = xp.where((a2 >= 0) == (safe >= 0), 1, -1)
                        return (q * sign, null, "int")
                    return (a2 / safe, null, "float")
                # %
                null = null | (b2 == 0)
                safe = xp.where(b2 == 0, xp.ones((), b2.dtype), b2)
                if k == "int":
                    # host v_mod: sign follows the dividend (C fmod style)
                    r = xp.abs(a2) % xp.abs(safe)
                    return (xp.where(a2 >= 0, r, -r), null, "int")
                return (xp.where(xp.signbit(a2),
                                 -(xp.abs(a2) % xp.abs(safe)),
                                 xp.abs(a2) % xp.abs(safe)), null, "float")
            if op in _BIT_OPS:
                # host gives BAD_TYPE (row-dropping) for non-int
                # operands incl. bools/floats — only the int/int shape
                # compiles; everything else falls back
                if ak != "int" or bk != "int":
                    raise CannotCompile(f"bitwise on {ak}/{bk}")
                null = an | bn
                val = {"&": av & bv, "|": av | bv, "^": av ^ bv}[op]
                return (val, null, "int")
            raise CannotCompile(f"binary {op}")
        return g

    def _in_list(fa, items: List[Any], pool: StringPool,
                 negate: bool) -> Callable[[Dict[str, Any]], Term]:
        def g(c):
            av, an, ak = fa(c)
            any_true = xp.zeros(xp.shape(av), bool)
            any_null = xp.zeros(xp.shape(av), bool)
            for it in items:
                if it is None or isinstance(it, NullValue):
                    any_null = xp.ones(xp.shape(av), bool)
                    continue
                # type-mismatched items yield NULL from v_eq on the host
                # (not False), so anything not exactly comparable must
                # fall back
                if isinstance(it, bool):
                    if ak != "bool":
                        raise CannotCompile("IN bool item vs non-bool")
                    any_true = any_true | (av == it)
                elif isinstance(it, int):
                    if ak not in _NUMERIC \
                            or not (-(1 << 63) <= it < (1 << 63)):
                        raise CannotCompile("IN int item vs non-numeric")
                    if ak == "int":
                        any_true = any_true | (av == it)
                    else:
                        any_true = any_true | (av == float(it))
                elif isinstance(it, float):
                    if ak not in _NUMERIC:
                        raise CannotCompile("IN float item vs non-numeric")
                    any_true = any_true | (av.astype(xp.float64) == it)
                elif isinstance(it, str):
                    if ak != "str":
                        raise CannotCompile("IN str item vs non-string")
                    any_true = any_true | (av == pool.lookup(it))
                else:
                    raise CannotCompile(f"IN item {type(it)}")
            val = any_true
            null = an | (~any_true & any_null)
            if negate:
                return (~val & ~null, null, "bool")
            return (val & ~null, null, "bool")
        return g

    return _lit, _unary, _coerce_pair, _binary, _in_list


_lit, _unary, _coerce_pair, _binary, _in_list = _term_alg(jnp)
_np_lit, _np_unary, _np_coerce_pair, _np_binary, _np_in_list = _term_alg(np)


# ---------------------------------------------------------------------------
# Vertex-predicate compiler (numpy, host plane)
# ---------------------------------------------------------------------------
#
# The fused MATCH pipeline (tpu/match_agg.py) evaluates AppendVertices
# filters — `_hastag(v, "Tag")`, `v.Tag.prop > x`, compositions — as ONE
# numpy mask over the snapshot's TagTable columns instead of per-row
# Python `Expr.eval` over built Vertex objects.  Same Term algebra as
# the in-kernel predicate compiler (shared `_term_alg`), numpy-bound so
# a host-side mask never dispatches to the device.


def _vertex_ref(x: "E.Expr", alias: str):
    """Classify a vertex-alias reference.  Returns ("prop", tag, prop) |
    ("attr", prop) | ("hastag", tag) | None; raises CannotCompile on a
    reference to a DIFFERENT alias (the caller's filter must be
    single-alias)."""
    if isinstance(x, E.LabelTagProp):
        if x.var != alias:
            raise CannotCompile(f"prop of other alias {x.var}")
        return ("prop", x.tag, x.prop)
    if isinstance(x, E.AttributeExpr) and isinstance(x.obj, E.LabelExpr):
        # tag-less `v.prop`: get_attribute over the MERGED tag props
        # (later tag in sorted order wins on a name collision)
        if x.obj.name != alias:
            raise CannotCompile(f"attr of other alias {x.obj.name}")
        return ("attr", x.attr)
    if (isinstance(x, E.FunctionCall) and x.name == "_hastag"
            and len(x.args) == 2 and isinstance(x.args[0], E.LabelExpr)
            and isinstance(x.args[1], E.Literal)
            and isinstance(x.args[1].value, str)):
        if x.args[0].name != alias:
            raise CannotCompile(f"_hastag of other alias {x.args[0].name}")
        return ("hastag", x.args[1].value)
    return None


def _vertex_id_ref(x: "E.Expr", alias: str) -> bool:
    """True iff x is id(<alias>)."""
    return (isinstance(x, E.FunctionCall) and x.name == "id"
            and len(x.args) == 1 and isinstance(x.args[0], E.LabelExpr)
            and x.args[0].name == alias)


def vertex_compilable(e: "E.Expr", alias: str) -> bool:
    """Static gate: will compile_vertex_predicate_np succeed (given the
    snapshot has the referenced tags)?  Conservative, schema-free."""
    try:
        _vertex_check(e, alias)
        return True
    except CannotCompile:
        return False


def _vertex_check(e: "E.Expr", alias: str):
    if isinstance(e, E.Literal):
        v = e.value
        if v is None or isinstance(v, (bool, int, float, str, NullValue)):
            return
        raise CannotCompile(f"literal {type(v)}")
    if _vertex_ref(e, alias) is not None:
        return
    if isinstance(e, E.Unary):
        if e.op in ("NOT", "-", "+", "IS_NULL", "IS_NOT_NULL"):
            _vertex_check(e.operand, alias)
            return
        raise CannotCompile(f"unary {e.op}")
    if isinstance(e, E.Binary):
        li, ri = _vertex_id_ref(e.lhs, alias), _vertex_id_ref(e.rhs, alias)
        if li or ri:
            if _id_pred_shape_ok(e, li, ri):
                return
            raise CannotCompile("id(v) only compiles vs non-null "
                                "literal vids")
        if e.op in _LOGIC_OPS + _CMP_OPS + _ARITH_OPS + _BIT_OPS:
            _vertex_check(e.lhs, alias)
            _vertex_check(e.rhs, alias)
            return
        if e.op in ("IN", "NOT IN"):
            _vertex_check(e.lhs, alias)
            if not isinstance(e.rhs, (E.ListExpr, E.SetExpr)):
                raise CannotCompile("IN rhs must be a literal list")
            for item in e.rhs.items:
                if not isinstance(item, E.Literal):
                    raise CannotCompile("IN item not literal")
            return
        raise CannotCompile(f"binary {e.op}")
    raise CannotCompile(f"expr kind {e.kind}")


def compile_vertex_predicate_np(e: "E.Expr", alias: str, snap,
                                sd) -> Callable[["np.ndarray"], "np.ndarray"]:
    """Compile a single-alias vertex predicate against CsrSnapshot tag
    tables.  Returns mask_fn(dense_ids) -> bool array: True where the
    predicate is (non-null) true for the vertex with that dense id.

    Tag-table null currency matches the edge plane: INT_NULL sentinel in
    int-coded columns, NaN in floats — absent-tag rows carry the fill,
    so `v.Tag.prop` on a vertex without Tag is NULL exactly like the
    host's per-row lookup (core/expr.py LabelTagProp)."""
    P = snap.num_parts
    pool = snap.pool

    def dense_of(v):
        d = sd.dense_id(v)
        return int(d) if d is not None else -1

    def vid_cmp(op, values):
        dv = [dense_of(x.value) for x in values]

        def g(c):
            ep = c["_dense"]
            m = np.zeros(np.shape(ep), bool)
            for d in dv:
                m = m | (ep == d)
            if op in ("!=", "NOT IN"):
                m = np.logical_not(m)
            return (m, np.zeros(np.shape(ep), bool), "bool")
        return g

    def build(x: "E.Expr"):
        if isinstance(x, E.Binary):
            li, ri = _vertex_id_ref(x.lhs, alias), _vertex_id_ref(x.rhs, alias)
            if li or ri:
                if x.op in ("==", "!="):
                    lit = x.rhs if li else x.lhs
                    if not isinstance(lit, E.Literal):
                        raise CannotCompile("id(v) vs non-literal")
                    return vid_cmp(x.op, [lit])
                if x.op in ("IN", "NOT IN") and li:
                    return vid_cmp(x.op, list(x.rhs.items))
                raise CannotCompile("id(v) predicate shape")
        if isinstance(x, E.Literal):
            return _np_lit(x.value, pool)
        ref = _vertex_ref(x, alias)
        if ref is not None:
            if ref[0] == "attr":
                return _attr_term(snap, P, ref[1])
            if ref[0] == "hastag":
                tt = snap.tags.get(ref[1])
                if tt is None:
                    return lambda c: (np.zeros(np.shape(c["_dense"]), bool),
                                      np.zeros(np.shape(c["_dense"]), bool),
                                      "bool")
                pres = tt.present

                def g(c, pres=pres):
                    d = c["_dense"]
                    m = pres[d % P, d // P]
                    return (m, np.zeros(m.shape, bool), "bool")
                return g
            _, tag, pname = ref
            tt = snap.tags.get(tag)
            if tt is None or pname not in tt.props:
                # unknown tag/prop → NULL (host LabelTagProp: absent)
                return lambda c: (np.zeros(np.shape(c["_dense"]), np.int64),
                                  np.ones(np.shape(c["_dense"]), bool),
                                  "int")
            kind = _kind_of(tt.prop_types[pname])
            col = tt.props[pname]

            def g(c, col=col, kind=kind):
                d = c["_dense"]
                raw = col[d % P, d // P]
                if kind == "float":
                    return (raw, np.isnan(raw), "float")
                if kind == "bool":
                    return (raw != 0, raw == INT_NULL, "bool")
                return (raw, raw == INT_NULL, kind)
            return g
        if isinstance(x, E.Unary):
            return _np_unary(x.op, build(x.operand))
        if isinstance(x, E.Binary):
            if x.op in ("IN", "NOT IN"):
                return _np_in_list(build(x.lhs),
                                   [it.value for it in x.rhs.items],
                                   pool, negate=x.op == "NOT IN")
            return _np_binary(x.op, build(x.lhs), build(x.rhs))
        raise CannotCompile(f"expr kind {x.kind}")

    term = build(e)

    def mask_fn(dense):
        val, isnull, kind = term({"_dense": dense})
        if kind != "bool":
            return np.zeros(np.shape(dense), bool)
        val = np.broadcast_to(val, np.shape(dense))
        isnull = np.broadcast_to(isnull, np.shape(dense))
        return np.logical_and(val, np.logical_not(isnull))

    return mask_fn


def merged_attr_columns(snap, prop: str):
    """(present, raw, kind) per tag whose schema carries `prop`, in the
    snapshot's sorted-tag order — the columnar mirror of
    Vertex.properties()'s dict merge (later tag wins).  Raises when the
    participating columns disagree on the value kind (a per-row merge
    of mixed encodings has no single columnar type)."""
    parts = []
    for tt in snap.tags.values():          # insertion = sorted tag order
        if prop in tt.props:
            parts.append((tt.present, tt.props[prop],
                          _kind_of(tt.prop_types[prop]),
                          tt.prop_types[prop]))
    kinds = {k for _, _, k, _ in parts}
    if len(kinds) > 1:
        raise CannotCompile(f"attr {prop} mixes value kinds across tags")
    return parts


def merged_attr_raw(snap, parts, dense: "np.ndarray"):
    """Merged raw column for `parts` at `dense` (sentinel nulls)."""
    P = snap.num_parts
    kind = parts[0][2]
    if kind == "float":
        val = np.full(np.shape(dense), np.nan)
    else:
        val = np.full(np.shape(dense), INT_NULL, np.int64)
    p_, li = dense % P, dense // P
    for pres, col, _, _ in parts:
        pm = pres[p_, li]
        val = np.where(pm, col[p_, li], val)
    return val


def _attr_term(snap, P, prop: str):
    parts = merged_attr_columns(snap, prop)
    if not parts:
        return lambda c: (np.zeros(np.shape(c["_dense"]), np.int64),
                          np.ones(np.shape(c["_dense"]), bool), "int")
    kind = parts[0][2]

    def g(c):
        raw = merged_attr_raw(snap, parts, c["_dense"])
        if kind == "float":
            return (raw, np.isnan(raw), "float")
        if kind == "bool":
            return (raw != 0, raw == INT_NULL, "bool")
        return (raw, raw == INT_NULL, kind)
    return g


# ---------------------------------------------------------------------------
# Columnar YIELD compiler — the fused-Project output path
# ---------------------------------------------------------------------------
#
# The fusion rule absorbs a GO plan's final Project(go_row) into
# TpuTraverse when every yield column is computable straight from the
# materialized edge columns (sv, dv, rr, props) with NO per-row Python
# evaluation.  Semantics mirror RowContext/get_edge_prop and the
# src/dst/rank/type/typeid builtins exactly (core/functions.py) —
# including the etype-sign swap for reverse-direction blocks.

_YIELD_FNS = frozenset({"src", "dst", "rank", "type", "typeid"})


def yieldable(e: "E.Expr") -> bool:
    """Can this YIELD column be evaluated columnar-side?"""
    if e.kind == "literal":
        return True
    if e.kind == "edge_prop":
        return True
    if e.kind == "function" and e.name in _YIELD_FNS and len(e.args) == 1 \
            and e.args[0].kind == "edge":
        return True
    return False


def eval_yield_column_np(e: "E.Expr", b: Dict[str, Any]) -> "np.ndarray":
    """Evaluate one absorbed YIELD column over a materialized block,
    columnar: returns numpy arrays (object dtype for vids/strings,
    native dtype for numeric prop columns) with no per-element tolist —
    the ColumnarDataSet fast path.

    b: {"et", "etype" (signed), "n", "sv", "dv", "rr", "props"} from
    tpu/assemble.py `_block_columns`, `b["props"]` numpy arrays
    (decode_prop_column_np).  For reverse ("in") blocks etype < 0 and
    sv is the frontier vertex — the PHYSICAL edge is dv→sv, matching
    Edge(sv, dv, etype=-id) built by the row materializer."""
    import numpy as np

    from ..core.value import NULL_UNKNOWN_PROP
    n = b["n"]
    fwd = b["etype"] >= 0

    def _const(v, dtype=object):
        a = np.empty(n, dtype=dtype)
        a.fill(v)
        return a

    if e.kind == "literal":
        return _const(e.value)
    if e.kind == "function":
        name = e.name
        if name == "src":
            return b["sv"] if fwd else b["dv"]
        if name == "dst":
            return b["dv"] if fwd else b["sv"]
        if name == "rank":
            return np.asarray(b["rr"], dtype=np.int64)
        if name == "type":
            return _const(b["et"])
        if name == "typeid":
            return _const(int(b["etype"]), dtype=np.int64)
    if e.kind == "edge_prop":
        pname = e.name
        if pname == "_src":
            return b["sv"] if fwd else b["dv"]
        if pname == "_dst":
            return b["dv"] if fwd else b["sv"]
        if pname == "_rank":
            return np.asarray(b["rr"], dtype=np.int64)
        if pname == "_type":
            return _const(b["et"])
        col = b["props"].get(pname)
        if col is None:
            return _const(NULL_UNKNOWN_PROP)
        return col
    raise CannotCompile(f"yield not columnar: {e.kind}")
