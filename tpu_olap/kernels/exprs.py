"""Row-expression evaluation over column arrays (numpy or jax.numpy).

Backs virtual columns and expression filters (tpu_olap.ir.expr). The same
evaluator serves the device path (jnp) and the CPU fallback (np) so both
paths share semantics by construction.
"""

from __future__ import annotations

from tpu_olap.ir.expr import BinOp, Col, Expr, FuncCall, Lit

_ARITH = {
    "+": lambda a, b: a + b,
    "-": lambda a, b: a - b,
    "*": lambda a, b: a * b,
    "/": lambda a, b: a / b,
    "%": lambda a, b: a % b,
    "==": lambda a, b: a == b,
    "!=": lambda a, b: a != b,
    "<": lambda a, b: a < b,
    "<=": lambda a, b: a <= b,
    ">": lambda a, b: a > b,
    ">=": lambda a, b: a >= b,
    "&&": lambda a, b: a & b,
    "||": lambda a, b: a | b,
}


def eval_expr(expr: Expr, env: dict, xp, narrow_ints: bool = False):
    """Evaluate an expression AST.

    env maps column name -> array (numeric values; dict codes are NOT
    valid inputs — the planner resolves string columns before lowering).
    xp is the array module (numpy or jax.numpy). narrow_ints=True is the
    Pallas-kernel mode: every node was proven to fit int32 at eligibility
    time, so int literals may be coerced to int32 (required — Mosaic
    cannot lower the weak-i64 scalars x64 would otherwise produce).
    """
    if isinstance(expr, Lit):
        return expr.value
    if isinstance(expr, Col):
        if expr.name not in env:
            raise KeyError(f"unknown column {expr.name!r} in expression")
        return env[expr.name]
    if isinstance(expr, BinOp):
        left = eval_expr(expr.left, env, xp, narrow_ints)
        right = eval_expr(expr.right, env, xp, narrow_ints)
        if expr.op == "/":
            # SQL-style: integer operands still divide as floats
            left = _as_float(left, xp)
        return _ARITH[expr.op](left, right)
    if isinstance(expr, FuncCall):
        args = [eval_expr(a, env, xp, narrow_ints) for a in expr.args]
        return _call(expr.name, args, xp, narrow_ints)
    raise TypeError(f"not an expression: {expr!r}")


def virtual_null_mask(expr: Expr, nulls: dict, xp):
    """SQL null propagation for virtual columns: the result is null where
    ANY referenced input is null. Returns a bool mask or None when no
    referenced column carries nulls."""
    mask = None
    for col in sorted(expr.columns()):
        m = nulls.get(col)
        if m is not None:
            mask = m if mask is None else (mask | m)
    return mask


def widen_int_env(expr: Expr, cols: dict, xp) -> dict:
    """Copy of `cols` with the expression's narrow-int inputs upcast to
    int64: device columns may be stored int32 (executor.dataset narrow
    storage), and products/sums must not wrap. XLA fuses the widening
    into the consumer, so the HBM read stays narrow. No-op without x64
    (int64 lanes unavailable — matches pre-narrowing behavior). Columns
    are taken in sorted order: a set's order changes with the process's
    hash seed, the traced program with it, and so would its key in the
    persistent compile cache."""
    from tpu_olap.kernels.hashing import has_x64
    if not has_x64(xp):
        return cols
    out = None
    for c in sorted(expr.columns()):
        v = cols.get(c)
        if v is not None and getattr(v, "dtype", None) is not None and \
                v.dtype.kind in "iu" and v.dtype.itemsize < 8:
            if out is None:
                out = dict(cols)
            out[c] = v.astype(xp.int64)
    return out if out is not None else cols


def materialize_virtuals(vexprs: dict, cols: dict, nulls: dict, xp,
                         wide_ints: bool = True) -> None:
    """Evaluate every virtual column into `cols` AND attach its null mask
    to `nulls` (SQL null propagation). The single shared site for all
    kernels — forgetting the mask half reintroduces a null-leak bug.
    wide_ints=False keeps narrow arithmetic (the Pallas kernel bounds
    every intermediate to int32 at eligibility time)."""
    for name, ex in vexprs.items():
        env = widen_int_env(ex, cols, xp) if wide_ints else cols
        cols[name] = eval_expr(ex, env, xp, narrow_ints=not wide_ints)
        nm = virtual_null_mask(ex, nulls, xp)
        if nm is not None:
            nulls[name] = nm


def _as_float(v, xp):
    from tpu_olap.kernels.hashing import has_x64
    if hasattr(v, "dtype") and v.dtype.kind in "iu":
        return v.astype(xp.float64 if has_x64(xp) else xp.float32)
    return v


def _call(name, args, xp, narrow_ints: bool = False):
    if name == "abs":
        return xp.abs(args[0])
    if name == "floor":
        return xp.floor(args[0])
    if name == "ceil":
        return xp.ceil(args[0])
    if name == "sqrt":
        return xp.sqrt(args[0])
    if name == "log":
        return xp.log(args[0])
    if name == "exp":
        return xp.exp(args[0])
    if name == "pow":
        return xp.power(args[0], args[1])
    if name == "if":
        a1, a2 = args[1], args[2]
        if narrow_ints:
            # Pallas-kernel mode only: Python-int branches would enter
            # xp.where as weak i64 scalars under x64, and Mosaic cannot
            # lower scalar i64->i32 (infinite recursion). Eligibility
            # bounded every node to int32, so the coercion is exact. The
            # wide (XLA/numpy) path keeps i64 literals — downstream
            # arithmetic may legitimately exceed int32 there.
            import numpy as _np
            if type(a1) is int and -2**31 <= a1 < 2**31:
                a1 = _np.int32(a1)
            if type(a2) is int and -2**31 <= a2 < 2**31:
                a2 = _np.int32(a2)
        return xp.where(args[0], a1, a2)
    if name in ("min", "least"):
        return xp.minimum(args[0], args[1])
    if name in ("max", "greatest"):
        return xp.maximum(args[0], args[1])
    if name == "cast_double":
        return _as_float(args[0], xp)
    if name == "cast_long":
        x = args[0]
        if hasattr(x, "dtype") and x.dtype.kind in "iu":
            return x  # already integral
        from tpu_olap.kernels.hashing import has_x64
        it = xp.int64 if has_x64(xp) else xp.int32
        return xp.trunc(x).astype(it)  # SQL casts truncate toward zero
    raise ValueError(f"unknown function {name!r} in expression")
