"""Expression layer of the columnar query plane: parse the table DSL's
string expressions, discover referenced columns (the pruning substrate),
and compile the supported subset into VECTORIZED array programs that
evaluate over whole column batches before any row tuple materializes
(numpy, on the driver) or over one traced record inside a stage program
(jax.numpy, for a table resident on the device): one admission, one
program, two emitters (`evaluate`).

Admission is exact, not optimistic — an expression only vectorizes when
the array program provably computes what the host's per-row Python eval
computes for every value the batch can contain:

  * integer arithmetic is admitted through interval analysis over the
    batch's actual per-column [min, max] ranges (the same idea as
    fuse._IntInterval's ranged-int top-k probe): every intermediate
    must fit int64, because the host computes exact Python ints while
    the array path wraps;
  * division requires a provably nonzero divisor (constant, or a
    column whose range excludes 0) — the host raises ZeroDivisionError
    where numpy would emit inf;
  * ``min``/``max`` calls compile to ``np.where`` forms that reproduce
    Python's comparison semantics exactly (``np.minimum`` propagates
    NaN where Python ``min`` returns its first argument);
  * ``and``/``or``/``not`` are admitted only in BOOLEAN (predicate)
    context, where truthiness is all that survives — in value context
    Python's and/or return an operand, which has no array twin here.

Everything else declines with a recorded reason; the planner keeps the
declining operator on the host row path and the `table-host-fallback`
lint rule reports the same reason pre-flight.
"""

import ast
import operator
import sys

import numpy as np

_I64_MAX = 2 ** 63 - 1


class ExprDecline(Exception):
    """Why an expression cannot vectorize (carried as the reason)."""


class ColumnExpr:
    """One parsed DSL expression: its AST, referenced columns, and the
    original text.  Vectorization is a separate, per-batch admission
    (dtypes + value ranges in hand) via `vectorize`."""

    __slots__ = ("expr", "tree", "columns", "parse_error")

    def __init__(self, expr, fields):
        self.expr = expr
        self.tree = None
        self.parse_error = None
        self.columns = set()
        try:
            self.tree = ast.parse(expr, mode="eval")
        except SyntaxError as e:
            self.parse_error = "unparseable expression: %s" % e
            return
        fields = set(fields)
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Name) and node.id in fields:
                self.columns.add(node.id)

    def __repr__(self):
        return "<ColumnExpr %r cols=%s>" % (self.expr,
                                            sorted(self.columns))


def compile_expr(expr, fields):
    return ColumnExpr(expr, fields)


# ---------------------------------------------------------------------------
# vectorization
# ---------------------------------------------------------------------------
# ONE admission, two emitters.  `_Vectorizer` walks the AST once and
# builds a PROGRAM: a nested tuple of (op, operands...) that holds no
# array, no range and no function, so it hashes by content and two plans
# of the same text are the same program.  `evaluate` runs a program over
# an environment of columns with one of two emitters:
#
#   numpy      {column: array} of a driver-side batch (the scan of part
#              files and driver-resident slices)
#   jax.numpy  {column: traced scalar} of ONE record inside a stage
#              program (a table resident on the device: fuse.py vmaps
#              the record function like any user lambda); a byte-string
#              column is a layout.ByteStr
#
# What differs between the two is spelled here and nowhere else: how a
# value becomes bool / int64 / float, and which function compares.

def _as_bool(arr):
    a = np.asarray(arr)
    if a.dtype == np.bool_:
        return a
    return a.astype(bool)


class _NumpyEmit:
    where = staticmethod(np.where)
    abs = staticmethod(np.abs)
    as_bool = staticmethod(_as_bool)
    cmp = {"Lt": np.less, "LtE": np.less_equal, "Gt": np.greater,
           "GtE": np.greater_equal, "Eq": np.equal, "NotEq": np.not_equal}

    @staticmethod
    def as_int(x):
        return np.asarray(x).astype(np.int64)

    @staticmethod
    def as_float(x):
        return np.asarray(x, np.float64)


class _TraceEmit:
    """Over one traced record.  Comparisons go through the operators,
    so that a ByteStr answers `==` / `!=` itself; a float is what a
    float column is on the device (layout.record_spec: float32)."""

    cmp = {"Lt": operator.lt, "LtE": operator.le, "Gt": operator.gt,
           "GtE": operator.ge, "Eq": operator.eq, "NotEq": operator.ne}

    def __init__(self):
        import jax.numpy as jnp
        self.jnp = jnp
        self.where = jnp.where
        self.abs = jnp.abs

    def as_bool(self, x):
        return self.jnp.asarray(x).astype(bool)

    def as_int(self, x):
        return self.jnp.asarray(x).astype(self.jnp.int64)

    def as_float(self, x):
        return self.jnp.asarray(x).astype(self.jnp.float32)


_NUMPY = _NumpyEmit()
_TRACE = []                     # the one _TraceEmit, made on first use


def _emitter(env):
    """The traced emitter when any column of `env` is traced (jax is
    imported by then), else numpy's: a stage that fell back to Python
    rows hands the same function plain scalars."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NUMPY
    for v in env.values():
        if isinstance(v, jax.core.Tracer) or hasattr(v, "words"):
            if not _TRACE:
                _TRACE.append(_TraceEmit())
            return _TRACE[0]
    return _NUMPY


_BINOPS = {"add": operator.add, "sub": operator.sub, "mul": operator.mul,
           "div": operator.truediv, "floordiv": operator.floordiv,
           "mod": operator.mod}


def _eval(prog, env, xp):
    op = prog[0]
    if op == "col":
        return env[prog[1]]
    if op == "const":
        return prog[1]
    if op in _BINOPS:
        return _BINOPS[op](_eval(prog[1], env, xp), _eval(prog[2], env, xp))
    if op == "cmp":
        out = None
        for name, left, right in prog[1]:
            part = xp.as_bool(xp.cmp[name](_eval(left, env, xp),
                                           _eval(right, env, xp)))
            out = part if out is None else out & part
        return out
    if op in ("and", "or"):
        out = xp.as_bool(_eval(prog[1][0], env, xp))
        for p in prog[1][1:]:
            nxt = xp.as_bool(_eval(p, env, xp))
            out = out & nxt if op == "and" else out | nxt
        return out
    if op == "not":
        return ~xp.as_bool(_eval(prog[1], env, xp))
    if op == "bool":
        return xp.as_bool(_eval(prog[1], env, xp))
    if op == "neg":
        return -_eval(prog[1], env, xp)
    if op == "int":
        return xp.as_int(_eval(prog[1], env, xp))
    if op == "float":
        return xp.as_float(_eval(prog[1], env, xp))
    if op == "abs":
        return xp.abs(_eval(prog[1], env, xp))
    if op in ("min", "max"):
        # Python's min(a, b) exactly: b if b < a else a (a NaN b never
        # compares less, so `a` wins; np.minimum would propagate it)
        out = _eval(prog[1][0], env, xp)
        for p in prog[1][1:]:
            b = _eval(p, env, xp)
            out = xp.where(b < out if op == "min" else b > out, b, out)
        return out
    raise ValueError("unknown op %r in an admitted program" % (op,))


def evaluate(prog, env):
    """Run an admitted program over {column: array} (numpy batches) or
    {column: traced scalar} (one record of a stage program)."""
    return _eval(prog, env, _emitter(env))


class _V:
    """One admitted sub-expression: its program + static type facts.

    kind: "i" int, "f" float, "b" bool (comparison output), "o" object
    (string column / str or bytes literal).  bounds: exact (lo, hi)
    Python ints for int-kind nodes (None once unknown — which declines
    any further int arithmetic, keeping the no-wrap proof honest)."""

    __slots__ = ("prog", "kind", "bounds", "const")

    def __init__(self, prog, kind, bounds=None, const=None):
        self.prog = prog
        self.kind = kind
        self.bounds = bounds
        self.const = const


def _chk(lo, hi, what):
    if abs(lo) > _I64_MAX or abs(hi) > _I64_MAX:
        raise ExprDecline(
            "int expression may leave int64 (%s bounds [%d, %d]): the "
            "host computes exact Python ints — host path" % (what, lo, hi))
    return (lo, hi)


def _const_v(value):
    prog = ("const", value)
    if isinstance(value, bool):
        return _V(prog, "b", (int(value), int(value)), const=value)
    if isinstance(value, int):
        _chk(value, value, "literal")
        return _V(prog, "i", (value, value), const=value)
    if isinstance(value, float):
        return _V(prog, "f", const=value)
    if isinstance(value, (str, bytes)):
        return _V(prog, "o", const=value)
    raise ExprDecline("unsupported literal %r" % (value,))


class _Vectorizer:
    """AST -> admitted program, with per-node admission.

    dtypes: {column: numpy dtype} of the scanned batch (object dtype
    for string columns, S<w> for fixed-width byte strings); ranges:
    {column: (lo, hi) exact ints} for int columns (None entries decline
    int arithmetic over them).  `device`: the program will run over a
    table resident on the device, where a float is float32: an ordering
    or equality of floats there is not provably the host's (float64),
    so it declines; float ARGUMENTS of aggregates are admitted as on
    the driver path, whose columns round to float32 at ingest."""

    def __init__(self, dtypes, ranges, device=False):
        self.dtypes = dtypes
        self.ranges = ranges or {}
        self.device = device

    def build(self, node, boolean):
        meth = getattr(self, "_v_%s" % type(node).__name__, None)
        if meth is None:
            raise ExprDecline("unsupported syntax %s in a vectorized "
                              "expression" % type(node).__name__)
        return meth(node, boolean)

    # -- leaves ---------------------------------------------------------
    def _v_Expression(self, node, boolean):
        return self.build(node.body, boolean)

    def _v_Constant(self, node, boolean):
        return _const_v(node.value)

    def _v_Name(self, node, boolean):
        name = node.id
        if name == "True":
            return _const_v(True)
        if name == "False":
            return _const_v(False)
        if name not in self.dtypes:
            raise ExprDecline("unknown name %r" % name)
        dt = self.dtypes[name]
        prog = ("col", name)
        if dt == np.dtype(object) or dt.kind in "US":
            return _V(prog, "o")
        if dt.kind == "b":
            raise ExprDecline("bool column %r stays on the host path"
                              % name)
        if dt.kind == "i":
            rng = self.ranges.get(name)
            if rng is None:
                raise ExprDecline(
                    "int column %r has no value range (needed for the "
                    "no-overflow proof)" % name)
            return _V(prog, "i", (int(rng[0]), int(rng[1])))
        if dt.kind == "f":
            return _V(prog, "f")
        raise ExprDecline("unsupported column dtype %s for %r"
                          % (dt, name))

    # -- arithmetic -----------------------------------------------------
    def _numeric(self, v, what):
        if v.kind == "o":
            raise ExprDecline("string operand in %s" % what)
        if v.kind == "b":
            # Python arithmetic treats bools as ints (True + True = 2);
            # bool arrays would logical-or under "+" — cast so the
            # array program keeps the host's semantics
            return _V(("int", v.prog), "i", v.bounds or (0, 1),
                      const=v.const)
        return v

    def _v_UnaryOp(self, node, boolean):
        if isinstance(node.op, ast.Not):
            v = self.build(node.operand, True)
            return _V(("not", v.prog), "b", (0, 1))
        v = self._numeric(self.build(node.operand, False), "unary op")
        if isinstance(node.op, ast.USub):
            bounds = None
            if v.kind in "ib":
                bounds = _chk(-v.bounds[1], -v.bounds[0], "negation")
            return _V(("neg", v.prog), "f" if v.kind == "f" else "i",
                      bounds)
        if isinstance(node.op, ast.UAdd):
            return v
        raise ExprDecline("unsupported unary op")

    def _v_BinOp(self, node, boolean):
        a = self._numeric(self.build(node.left, False), "arithmetic")
        b = self._numeric(self.build(node.right, False), "arithmetic")
        op = node.op
        int_sides = a.kind in "ib" and b.kind in "ib"
        kind = "i" if int_sides else "f"
        if isinstance(op, ast.Add):
            bounds = _chk(a.bounds[0] + b.bounds[0],
                          a.bounds[1] + b.bounds[1], "+") \
                if int_sides else None
            return _V(("add", a.prog, b.prog), kind, bounds)
        if isinstance(op, ast.Sub):
            bounds = _chk(a.bounds[0] - b.bounds[1],
                          a.bounds[1] - b.bounds[0], "-") \
                if int_sides else None
            return _V(("sub", a.prog, b.prog), kind, bounds)
        if isinstance(op, ast.Mult):
            bounds = None
            if int_sides:
                corners = [x * y for x in a.bounds for y in b.bounds]
                bounds = _chk(min(corners), max(corners), "*")
            return _V(("mul", a.prog, b.prog), kind, bounds)
        if isinstance(op, ast.Div):
            self._nonzero(b, "/")
            return _V(("div", a.prog, b.prog), "f")
        if isinstance(op, (ast.FloorDiv, ast.Mod)):
            self._nonzero(b, "// or %")
            if not int_sides:
                # float // and % match numpy's floor conventions, but
                # the host's exact-float corner cases (signed zeros)
                # are not worth proving here
                raise ExprDecline("float // and % stay on the host")
            if b.bounds[0] <= 0 <= b.bounds[1]:
                raise ExprDecline("divisor range crosses zero")
            if isinstance(op, ast.FloorDiv):
                corners = [x // y for x in a.bounds for y in b.bounds]
                bounds = _chk(min(corners), max(corners), "//")
                return _V(("floordiv", a.prog, b.prog), "i", bounds)
            if b.bounds[0] > 0:
                bounds = (0, b.bounds[1] - 1)
            else:
                bounds = (b.bounds[0] + 1, 0)
            return _V(("mod", a.prog, b.prog), "i", bounds)
        raise ExprDecline("unsupported operator %s"
                          % type(op).__name__)

    def _nonzero(self, v, what):
        if v.kind == "f":
            if v.const is not None and v.const != 0:
                return
            raise ExprDecline(
                "divisor of %s not provably nonzero (the host raises "
                "ZeroDivisionError where arrays emit inf)" % what)
        if v.bounds[0] <= 0 <= v.bounds[1]:
            raise ExprDecline("divisor of %s not provably nonzero"
                              % what)

    # -- comparisons / boolean ------------------------------------------
    def _v_Compare(self, node, boolean):
        parts = []
        left = self.build(node.left, False)
        for op, right_node in zip(node.ops, node.comparators):
            right = self.build(right_node, False)
            if (left.kind == "o") != (right.kind == "o"):
                if not isinstance(op, (ast.Eq, ast.NotEq)):
                    raise ExprDecline(
                        "ordering comparison between string and "
                        "numeric operands")
            name = type(op).__name__
            if name not in _NumpyEmit.cmp:
                raise ExprDecline("unsupported comparison %s" % name)
            if self.device:
                self._device_compare(left, right, name)
            parts.append((name, left.prog, right.prog))
            left = right
        return _V(("cmp", tuple(parts)), "b", (0, 1))

    def _device_compare(self, left, right, name):
        """What a comparison over a resident table cannot prove equal
        to the host's: floats (float32 there, float64 here) and the
        ORDER of byte strings (a ByteStr answers == and != only)."""
        if "f" in (left.kind, right.kind):
            raise ExprDecline(
                "float comparison over a table resident on the device "
                "(float32 there, float64 on the host)")
        if "o" in (left.kind, right.kind) and name not in ("Eq", "NotEq"):
            raise ExprDecline(
                "ordering of byte strings over a table resident on the "
                "device")

    def _v_BoolOp(self, node, boolean):
        if not boolean:
            raise ExprDecline(
                "and/or outside a predicate (Python's and/or return "
                "an OPERAND, which has no array twin)")
        progs = tuple(self.build(v, True).prog for v in node.values)
        return _V(("and" if isinstance(node.op, ast.And) else "or",
                   progs), "b", (0, 1))

    # -- calls ----------------------------------------------------------
    def _v_Call(self, node, boolean):
        if not isinstance(node.func, ast.Name) or node.keywords:
            raise ExprDecline("unsupported call form")
        name = node.func.id
        args = [self.build(a, False) for a in node.args]
        if name == "abs" and len(args) == 1:
            (v,) = args
            v = self._numeric(v, "abs")
            bounds = None
            if v.kind in "ib":
                lo, hi = v.bounds
                bounds = _chk(0 if lo <= 0 <= hi else min(abs(lo),
                                                          abs(hi)),
                              max(abs(lo), abs(hi)), "abs")
            return _V(("abs", v.prog), "f" if v.kind == "f" else "i",
                      bounds)
        if name in ("min", "max") and len(args) >= 2:
            args = [self._numeric(a, name) for a in args]
            kind = "f" if "f" in {a.kind for a in args} else "i"
            if kind == "f" and self.device:
                self._device_compare(args[0], args[1], "Lt")
            bounds = None
            if kind == "i":
                agg = min if name == "min" else max
                bounds = (agg(a.bounds[0] for a in args),
                          agg(a.bounds[1] for a in args))
            return _V((name, tuple(a.prog for a in args)), kind, bounds)
        if name == "float" and len(args) == 1:
            v = self._numeric(args[0], "float()")
            return _V(("float", v.prog), "f")
        raise ExprDecline("unsupported function %r in a vectorized "
                          "expression" % name)


class VecExpr:
    """An admitted array program: `prog`, and fn({column: array}) ->
    value array (bool array for predicates) that runs it; kind in
    "ifb"; bounds the exact (lo, hi) int interval for int-kind outputs
    (drives the no-overflow proof of any DOWNSTREAM expression over
    this derived column)."""

    __slots__ = ("prog", "kind", "bounds")

    def __init__(self, prog, kind, bounds=None):
        self.prog = prog
        self.kind = kind
        self.bounds = bounds

    def fn(self, env):
        return evaluate(self.prog, env)


def vectorize(colexpr, dtypes, ranges=None, boolean=False, device=False):
    """Admit a ColumnExpr as an array program, or explain why not.

    Returns (VecExpr, None) on admission or (None, reason) on decline.
    `ranges` supplies exact (lo, hi) per int column for the
    no-overflow interval proof; `device` says the program will run
    inside a stage program over a table resident on the device (see
    _Vectorizer)."""
    if colexpr.parse_error:
        return None, colexpr.parse_error
    try:
        dts = {k: np.dtype(v) for k, v in dtypes.items()}
        v = _Vectorizer(dts, ranges, device).build(colexpr.tree, boolean)
        if boolean:
            return VecExpr(("bool", v.prog), "b"), None
        if v.kind == "o":
            return None, ("string-valued expressions have no "
                          "device column form")
        if v.kind == "b":
            return None, ("bool-valued projection stays on the "
                          "host (predicate context only)")
        return VecExpr(v.prog, v.kind, v.bounds), None
    except ExprDecline as e:
        return None, str(e)
    except Exception as e:          # never let admission kill a query
        return None, "vectorize failed: %s" % e


def int_ranges(cols):
    """Exact (lo, hi) per int column of a batch dict — the interval
    proof's inputs.  Empty columns map to (0, 0)."""
    out = {}
    for name, arr in cols.items():
        a = np.asarray(arr)
        if a.dtype.kind == "i":
            out[name] = ((int(a.min()), int(a.max())) if a.size
                         else (0, 0))
        elif a.dtype.kind == "b":
            out[name] = (0, 1)
    return out
