"""Trace Python/JAX functions into STRELA DFGs (the compiler frontend).

A kernel is an ordinary Python function over int32 streams::

    def relu(x):
        return jnp.where(x > 0, x, 0)

``trace(relu, length=N)`` runs ``jax.make_jaxpr`` over ``(N,)``-shaped int32
abstract values and lowers the resulting jaxpr equation-by-equation onto the
``core.dfg`` IR:

  * ``add/sub/mul/shift/and/or/xor``            -> ALU nodes
  * ``gt/lt/ge/le/eq/ne``                       -> CMP nodes (+ XOR-1 inverts)
  * ``select_n`` / ``jnp.where`` / ``max/min``  -> CMP + if/else MUX
  * scalar Python constants                     -> folded PE constants
  * ``reduce_sum`` / ``jnp.dot``                -> accumulator ALUs
    (see patterns.py)
  * two-way ``lax.cond``                        -> BRANCH/MERGE pairs
    (see patterns.py)
  * ``lax.while_loop`` / ``lax.fori_loop``      -> gated Branch/Merge loops
    with recirculation back edges (see patterns.py)
  * ``lax.scan`` over the stream               -> loop-carried back-edge
    recurrences (see patterns.py)

Anything else raises :class:`UnsupportedPrimitiveError` naming the offending
equation. Constant placement honours the hardware: a PE holds one constant
on operand *b*; constants on the left of non-commutative ops are rewritten
(``c - x`` becomes ``x * -1 + c``).

Two tracing modes share all of the lowering code:

  * **stream mode** (default): avals are ``(length,)`` int32 — elementwise
    ops and whole-stream reductions appear naturally;
  * **element mode**: avals are scalar ``()`` int32 — required for
    ``lax.cond`` (its predicate must be a scalar), at the cost of reductions
    (which need the stream extent). ``mode="auto"`` retries in element mode
    when stream-mode tracing dies inside ``lax.cond``.
"""
from __future__ import annotations

import dataclasses
import inspect
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from repro.core import dfg as D
from repro.core.executor import wrap32
from repro.core.isa import AluOp, CmpOp


class FrontendError(Exception):
    """A traced function cannot be lowered onto the fabric."""


class UnsupportedPrimitiveError(FrontendError):
    """A jaxpr equation uses a primitive the fabric has no lowering for."""


@dataclasses.dataclass(frozen=True)
class Wire:
    """A value carried on a DFG signal: (producer node, output port)."""

    node: str
    port: str = "out"


@dataclasses.dataclass(frozen=True)
class FinalWire(Wire):
    """A scan's final carry: the producer emits every element but only the
    *last* token is the value (OMN last-value mode). Valid only as a kernel
    output; joining it with a stream elementwise is rejected at trace time."""


@dataclasses.dataclass(frozen=True)
class ConstVal:
    """A compile-time scalar constant (folds into a PE constant)."""

    value: int


Value = Union[Wire, ConstVal]

_COMMUTATIVE = {AluOp.ADD, AluOp.MUL, AluOp.AND, AluOp.OR, AluOp.XOR}

_SUPPORTED_NOTE = (
    "the STRELA fabric lowers int32 add/sub/mul/shift/bitwise ALU ops, "
    "eqz/gtz comparisons, select/where/max/min muxes, full-stream "
    "sum/prod/bitwise reductions, 1-D dot products, two-way lax.cond, "
    "data-dependent lax.while_loop / lax.fori_loop, and whole-stream "
    "lax.scan recurrences")


def _fold(x) -> int:
    return int(np.asarray(wrap32(x)).reshape(()))


class Lowerer:
    """Lowers one jaxpr (plus nested sub-jaxprs) into a DFGBuilder."""

    def __init__(self, name: str, length: int):
        self.name = name
        self.length = length
        self.b = D.DFG.build(name)
        self._counters: Dict[str, int] = {}
        # token rate per node: 1 = one token per stream element (full rate),
        # 0 = reduced (an accumulator emission). Joining the two starves the
        # elastic join in hardware, so it is rejected at trace time.
        self._rate: Dict[str, int] = {}

    def _join_rate(self, wires: Sequence[Optional[Wire]]) -> int:
        for w in wires:
            if isinstance(w, FinalWire):
                raise FrontendError(
                    f"{self.name}: a scan's final carry is a single "
                    f"end-of-stream value; it can only be returned as a "
                    f"kernel output, not consumed elementwise (re-using it "
                    f"needs a multi-shot plan with a re-armed PE constant)")
        rates = {self._rate.get(w.node, 1) for w in wires if w is not None}
        if len(rates) > 1:
            raise FrontendError(
                f"{self.name}: cannot join a reduction output (a single "
                f"emitted token) with a full-rate stream elementwise; "
                f"re-broadcasting a computed scalar needs a multi-shot plan "
                f"with a re-armed PE constant")
        return rates.pop() if rates else 1

    # -- naming / env helpers ----------------------------------------------
    def fresh(self, stem: str) -> str:
        k = self._counters.get(stem, 0)
        self._counters[stem] = k + 1
        return f"{stem}{k}"

    def unsupported(self, eqn, why: Optional[str] = None) -> "FrontendError":
        prim = eqn.primitive.name
        detail = f" ({why})" if why else ""
        return UnsupportedPrimitiveError(
            f"{self.name}: cannot lower primitive '{prim}'{detail} in "
            f"equation `{_eqn_str(eqn)}`; {_SUPPORTED_NOTE}")

    def value_of(self, atom, env: Dict[Any, Value]) -> Value:
        from jax._src.core import Literal
        if isinstance(atom, Literal):
            return ConstVal(_fold(atom.val))
        return env[atom]

    # -- node emission ------------------------------------------------------
    def emit_alu(self, op: AluOp, a: Wire, b: Optional[Wire] = None,
                 const_b: Optional[int] = None, *, stem: Optional[str] = None,
                 acc_init: Optional[int] = None, emit_every: int = 1) -> Wire:
        name = self.fresh(stem or op.name.lower())
        rate = self._join_rate([a, b])
        self.b.alu(name, op, a.node, b.node if b is not None else None,
                   const_b=const_b, acc_init=acc_init, emit_every=emit_every,
                   a_port=a.port, b_port=b.port if b is not None else "out")
        self._rate[name] = 0 if (acc_init is not None
                                 and emit_every != 1) else rate
        return Wire(name)

    def emit_cmp(self, op: CmpOp, a: Wire, b: Optional[Wire] = None,
                 const_b: Optional[int] = None) -> Wire:
        name = self.fresh("cmp")
        self._rate[name] = self._join_rate([a, b])
        self.b.cmp(name, op, a.node, b.node if b is not None else None,
                   const_b=const_b, a_port=a.port,
                   b_port=b.port if b is not None else "out")
        return Wire(name)

    def emit_mux(self, a: Wire, b: Optional[Wire], ctrl: Wire,
                 const_b: Optional[int] = None) -> Wire:
        name = self.fresh("mux")
        self._rate[name] = self._join_rate([a, b, ctrl])
        self.b.mux(name, a.node, b.node if b is not None else None, ctrl.node,
                   a_port=a.port, b_port=b.port if b is not None else "out",
                   ctrl_port=ctrl.port)
        if b is None:
            self.b.nodes[name].value = const_b
        return Wire(name)

    # -- arithmetic with constant discipline --------------------------------
    def alu(self, op: AluOp, a: Value, b: Value) -> Value:
        """Lower ``op(a, b)`` folding/commuting constants onto operand b."""
        from repro.core.executor import alu_eval
        if isinstance(a, ConstVal) and isinstance(b, ConstVal):
            return ConstVal(_fold(alu_eval(op, a.value, b.value)))
        if isinstance(b, ConstVal):
            return self.emit_alu(op, a, const_b=b.value)
        if isinstance(a, ConstVal):
            if op in _COMMUTATIVE:
                return self.emit_alu(op, b, const_b=a.value)
            if op == AluOp.SUB:
                # c - x  ->  x * -1 (+ c unless c == 0): the PE constant
                # lives on operand b, so the left-constant form is rewritten.
                neg = self.emit_alu(AluOp.MUL, b, const_b=_fold(-1))
                if a.value == 0:
                    return neg
                return self.emit_alu(AluOp.ADD, neg, const_b=a.value)
            raise FrontendError(
                f"{self.name}: constant on the left of non-commutative "
                f"{op.name} is not expressible as a PE constant")
        return self.emit_alu(op, a, b)

    def lnot(self, v: Value) -> Value:
        """Logical not of a 0/1 value (comparator output)."""
        if isinstance(v, ConstVal):
            return ConstVal(0 if v.value else 1)
        return self.emit_alu(AluOp.XOR, v, const_b=1, stem="not")

    def gtz(self, a: Value, b: Value) -> Value:
        """a > b as a CMP node (GTZ over a - b)."""
        if isinstance(a, ConstVal) and isinstance(b, ConstVal):
            return ConstVal(int(a.value > b.value))
        if isinstance(a, Wire) and isinstance(b, ConstVal):
            if b.value == 0:
                return self.emit_cmp(CmpOp.GTZ, a)
            return self.emit_cmp(CmpOp.GTZ, a, const_b=b.value)
        if isinstance(a, Wire) and isinstance(b, Wire):
            return self.emit_cmp(CmpOp.GTZ, a, b)
        # const > wire: compare the rewritten difference directly
        diff = self.alu(AluOp.SUB, a, b)
        return self.emit_cmp(CmpOp.GTZ, diff)

    def eqz(self, a: Value, b: Value) -> Value:
        if isinstance(a, ConstVal) and isinstance(b, ConstVal):
            return ConstVal(int(a.value == b.value))
        if isinstance(a, ConstVal):
            a, b = b, a
        if isinstance(b, ConstVal):
            if b.value == 0:
                return self.emit_cmp(CmpOp.EQZ, a)
            return self.emit_cmp(CmpOp.EQZ, a, const_b=b.value)
        return self.emit_cmp(CmpOp.EQZ, a, b)

    def select(self, pred: Value, on_false: Value, on_true: Value) -> Value:
        """if/else mux: ``pred ? on_true : on_false`` (select_n case order)."""
        if isinstance(pred, ConstVal):
            return on_true if pred.value else on_false
        if isinstance(on_true, Wire):
            if isinstance(on_false, Wire):
                return self.emit_mux(on_true, on_false, pred)
            return self.emit_mux(on_true, None, pred, const_b=on_false.value)
        if isinstance(on_false, Wire):
            # true case is the constant: invert the predicate so the wire
            # rides the mux's a input and the constant folds onto b.
            inv = self.lnot(pred)
            return self.emit_mux(on_false, None, inv, const_b=on_true.value)
        # both cases constant:  f + pred * (t - f)
        span = _fold(on_true.value - on_false.value)
        scaled = self.alu(AluOp.MUL, pred, ConstVal(span))
        return self.alu(AluOp.ADD, scaled, ConstVal(on_false.value))

    def maximum(self, a: Value, b: Value) -> Value:
        if isinstance(a, ConstVal) and isinstance(b, ConstVal):
            return ConstVal(max(a.value, b.value))
        if isinstance(a, ConstVal):
            a, b = b, a
        c = self.gtz(a, b)
        return self.select(c, b, a)

    def minimum(self, a: Value, b: Value) -> Value:
        if isinstance(a, ConstVal) and isinstance(b, ConstVal):
            return ConstVal(min(a.value, b.value))
        if isinstance(a, ConstVal):
            a, b = b, a
        c = self.gtz(a, b)
        return self.select(c, a, b)

    def paced_const(self, pace: Wire, value: int) -> Wire:
        """A constant token stream paced by ``pace`` (one token out per token
        in): x*0 + c. Needed when a lax.cond branch returns a constant."""
        zero = self.emit_alu(AluOp.MUL, pace, const_b=0, stem="pace")
        if value == 0:
            return zero
        return self.emit_alu(AluOp.ADD, zero, const_b=_fold(value))

    # -- jaxpr walking ------------------------------------------------------
    def lower_jaxpr(self, jaxpr, consts: Sequence[Any],
                    args: Sequence[Value]) -> List[Value]:
        env: Dict[Any, Value] = {}
        if len(jaxpr.constvars) != len(consts):
            raise FrontendError(f"{self.name}: constvar/const mismatch")
        for var, c in zip(jaxpr.constvars, consts):
            arr = np.asarray(c)
            if arr.ndim != 0:
                raise FrontendError(
                    f"{self.name}: captured non-scalar constant of shape "
                    f"{arr.shape}; only scalar closure constants fold into "
                    f"PE constants")
            env[var] = ConstVal(_fold(arr))
        for var, val in zip(jaxpr.invars, args):
            env[var] = val
        for eqn in jaxpr.eqns:
            self.lower_eqn(eqn, env)
        return [self.value_of(v, env) for v in jaxpr.outvars]

    def lower_eqn(self, eqn, env: Dict[Any, Value]) -> None:
        prim = eqn.primitive.name
        handler = _HANDLERS.get(prim)
        if handler is None:
            from repro.frontend import patterns
            handler = patterns.PATTERN_HANDLERS.get(prim)
        if handler is None:
            raise self.unsupported(eqn)
        outs = handler(self, eqn, [self.value_of(v, env) for v in eqn.invars])
        if len(outs) != len(eqn.outvars):
            raise AssertionError(f"handler for {prim} returned {len(outs)} "
                                 f"values for {len(eqn.outvars)} outvars")
        for var, val in zip(eqn.outvars, outs):
            env[var] = val

    # -- graph finishing ----------------------------------------------------
    def finish(self, out_vals: Sequence[Value],
               input_names: Sequence[str]) -> D.DFG:
        for i, val in enumerate(out_vals):
            if isinstance(val, ConstVal):
                raise FrontendError(
                    f"{self.name}: output {i} is the compile-time constant "
                    f"{val.value}; a kernel output must depend on a stream")
            self.b.out(f"out{i}", val.node, src_port=val.port)
            if isinstance(val, FinalWire):
                # scan final carry: OMN stores the last value (stride-0)
                self.b.nodes[f"out{i}"].emit_every = 0
        self._prune(input_names)
        return self.b.done()

    def _prune(self, input_names: Sequence[str]) -> None:
        """Drop nodes with no path to an OUTPUT (dead jaxpr code)."""
        b = self.b
        live = set(b.outputs)
        stack = list(b.outputs)
        rev: Dict[str, List[str]] = {}
        for e in b.edges:
            rev.setdefault(e.dst, []).append(e.src)
        while stack:
            n = stack.pop()
            for p in rev.get(n, ()):
                if p not in live:
                    live.add(p)
                    stack.append(p)
        dead_inputs = [n for n in input_names if n not in live]
        if dead_inputs:
            raise FrontendError(
                f"{self.name}: stream input(s) {dead_inputs} are never used "
                f"by the function; every IMN stream must reach an output")
        b.nodes = {n: nd for n, nd in b.nodes.items() if n in live}
        b.edges = [e for e in b.edges if e.src in live and e.dst in live]


# ---------------------------------------------------------------------------
# elementwise primitive handlers
# ---------------------------------------------------------------------------

def _simple_alu(op: AluOp):
    def h(lw: Lowerer, eqn, ins: List[Value]) -> List[Value]:
        return [lw.alu(op, ins[0], ins[1])]
    return h


def _h_shift(op: AluOp):
    def h(lw: Lowerer, eqn, ins: List[Value]) -> List[Value]:
        a, b = ins
        if isinstance(a, ConstVal) and isinstance(b, Wire):
            raise lw.unsupported(eqn, "constant shifted by a stream")
        return [lw.alu(op, a, b)]
    return h


def _h_neg(lw: Lowerer, eqn, ins: List[Value]) -> List[Value]:
    return [lw.alu(AluOp.MUL, ins[0], ConstVal(_fold(-1)))]


def _h_integer_pow(lw: Lowerer, eqn, ins: List[Value]) -> List[Value]:
    y = int(eqn.params["y"])
    (x,) = ins
    if y < 1 or y > 8:
        raise lw.unsupported(eqn, f"exponent {y} out of the unrolled range")
    acc = x
    for _ in range(y - 1):
        acc = lw.alu(AluOp.MUL, acc, x)
    return [acc]


def _h_square(lw: Lowerer, eqn, ins: List[Value]) -> List[Value]:
    return [lw.alu(AluOp.MUL, ins[0], ins[0])]


def _h_gt(lw, eqn, ins):
    return [lw.gtz(ins[0], ins[1])]


def _h_lt(lw, eqn, ins):
    return [lw.gtz(ins[1], ins[0])]


def _h_ge(lw, eqn, ins):
    return [lw.lnot(lw.gtz(ins[1], ins[0]))]


def _h_le(lw, eqn, ins):
    return [lw.lnot(lw.gtz(ins[0], ins[1]))]


def _h_eq(lw, eqn, ins):
    return [lw.eqz(ins[0], ins[1])]


def _h_ne(lw, eqn, ins):
    return [lw.lnot(lw.eqz(ins[0], ins[1]))]


def _h_select_n(lw: Lowerer, eqn, ins: List[Value]) -> List[Value]:
    if len(ins) != 3:
        raise lw.unsupported(eqn, f"{len(ins) - 1}-way select (fabric muxes "
                             f"are two-way)")
    pred, case_f, case_t = ins
    return [lw.select(pred, case_f, case_t)]


def _h_max(lw, eqn, ins):
    return [lw.maximum(ins[0], ins[1])]


def _h_min(lw, eqn, ins):
    return [lw.minimum(ins[0], ins[1])]


def _h_clamp(lw: Lowerer, eqn, ins: List[Value]) -> List[Value]:
    lo, x, hi = ins
    return [lw.minimum(lw.maximum(x, lo), hi)]


def _h_alias(lw: Lowerer, eqn, ins: List[Value]) -> List[Value]:
    return [ins[0]]


def _h_broadcast(lw: Lowerer, eqn, ins: List[Value]) -> List[Value]:
    (v,) = ins
    if isinstance(v, ConstVal):
        return [v]
    src_shape = eqn.invars[0].aval.shape
    dst_shape = eqn.outvars[0].aval.shape
    if src_shape == dst_shape:
        return [v]
    raise lw.unsupported(
        eqn, f"broadcast of a runtime value from {src_shape} to {dst_shape} "
             f"(re-broadcasting a computed scalar needs a multi-shot plan "
             f"with a re-armed PE constant)")


def _h_inline_call(param_key: str):
    """Inline jit/closed_call/custom_jvp_call sub-jaxprs (each carries a
    ClosedJaxpr under ``param_key``)."""
    def h(lw: Lowerer, eqn, ins: List[Value]) -> List[Value]:
        closed = eqn.params[param_key]
        n_ins = len(ins)
        if eqn.primitive.name == "custom_jvp_call":
            # trailing invars may be jvp residuals; sub-jaxpr decides
            n_ins = len(closed.jaxpr.invars)
        return lw.lower_jaxpr(closed.jaxpr, closed.consts, ins[:n_ins])
    return h


_HANDLERS: Dict[str, Callable] = {
    "add": _simple_alu(AluOp.ADD),
    "sub": _simple_alu(AluOp.SUB),
    "mul": _simple_alu(AluOp.MUL),
    "and": _simple_alu(AluOp.AND),
    "or": _simple_alu(AluOp.OR),
    "xor": _simple_alu(AluOp.XOR),
    "shift_left": _h_shift(AluOp.SHL),
    "shift_right_arithmetic": _h_shift(AluOp.SHR),
    "neg": _h_neg,
    "integer_pow": _h_integer_pow,
    "square": _h_square,
    "gt": _h_gt,
    "lt": _h_lt,
    "ge": _h_ge,
    "le": _h_le,
    "eq": _h_eq,
    "ne": _h_ne,
    "select_n": _h_select_n,
    "max": _h_max,
    "min": _h_min,
    "clamp": _h_clamp,
    "convert_element_type": _h_alias,
    "stop_gradient": _h_alias,
    "copy": _h_alias,
    "broadcast_in_dim": _h_broadcast,
    "reshape": _h_alias,
    "jit": _h_inline_call("jaxpr"),
    "closed_call": _h_inline_call("call_jaxpr"),
    "custom_jvp_call": _h_inline_call("call_jaxpr"),
}


def _eqn_str(eqn) -> str:
    try:
        s = str(eqn)
    except Exception:   # pragma: no cover - jaxpr printing is best-effort
        s = f"{eqn.primitive.name}(...)"
    s = " ".join(s.split())
    return s if len(s) <= 200 else s[:197] + "..."


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def trace(fn: Callable, length: int, *, name: Optional[str] = None,
          mode: str = "auto") -> D.DFG:
    """Trace ``fn`` over int32 streams of ``length`` into a validated DFG.

    ``mode``: "stream" traces over ``(length,)`` avals (reductions work),
    "element" over scalar avals (``lax.cond`` works), "auto" tries stream
    then falls back to element when tracing fails on a scalar-only
    primitive. Raises :class:`UnsupportedPrimitiveError` (with the offending
    equation) or :class:`FrontendError` for structural problems.
    """
    import jax
    import jax.numpy as jnp

    kname = name or getattr(fn, "__name__", "traced")
    try:
        params = [p for p in inspect.signature(fn).parameters.values()
                  if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)]
        arg_names = [p.name for p in params]
    except (TypeError, ValueError):
        raise FrontendError(f"{kname}: cannot inspect function signature")
    if not arg_names:
        raise FrontendError(f"{kname}: kernel takes no stream arguments")

    def _make_jaxpr(shape):
        avals = [jax.ShapeDtypeStruct(shape, jnp.int32) for _ in arg_names]
        return jax.make_jaxpr(fn)(*avals)

    if mode not in ("auto", "stream", "element"):
        raise ValueError(f"bad trace mode {mode!r}")
    closed = None
    if mode in ("auto", "stream"):
        try:
            closed = _make_jaxpr((length,))
        except TypeError:
            # lax.cond (and friends) demand scalar operands; in auto mode
            # retry the per-element trace, which lowers cond to Branch/Merge
            if mode == "stream":
                raise
    if closed is None:
        closed = _make_jaxpr(())

    lw = Lowerer(kname, length)
    args: List[Value] = []
    for aname in arg_names:
        lw.b.inp(aname)
        args.append(Wire(aname))
    outs = lw.lower_jaxpr(closed.jaxpr, closed.consts, args)
    return lw.finish(outs, arg_names)
