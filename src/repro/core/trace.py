"""Trace acquisition: the paper's Device abstraction + a jaxpr-level tracer.

Two ways to obtain the event stream the planner needs:

1. ``RecordingDevice`` — the paper's §V ``Device`` class, verbatim semantics:
   ``Malloc``/``Free``/``Exec(fn, read_blocks, write_blocks)`` record events
   into a list which undergoes the repeatability test (core/iteration.py).
   This is the runtime path: model-transparent, no graph needed.  Used by the
   event-level simulator and for systems whose execution is imperative.

2. ``trace_jaxpr`` — the TPU/JAX adaptation.  Under XLA the "iterative nature"
   is compiled-in: one ``jax.make_jaxpr(step_fn)`` IS the canonical iteration.
   We walk the jaxpr as a virtual interpreter (inlining scan/while/cond/jit/
   shard_map bodies the number of times they execute) and emit the same
   event stream a runtime recorder would have seen: MALLOC+WRITE at
   producer, READ at each consumer, FREE after last use (refcount
   semantics).  This gives the
   offline-DSA instance for *any* jitted step function — every architecture
   in configs/ goes through this path.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable, Sequence

import jax
import numpy as np
from jax import core as _jcore_internal
from jax.extend import core as _jex_core


class _JCore:
    """Compat shim: jaxpr datatypes moved to jax.extend.core in newer JAX."""

    Literal = _jex_core.Literal
    ClosedJaxpr = _jex_core.ClosedJaxpr
    Jaxpr = _jex_core.Jaxpr
    DropVar = _jcore_internal.DropVar


jcore = _JCore

from .events import Event, EventKind, IterationTrace, build_trace
from .iteration import IterationDetector


# --------------------------------------------------------------------------
# 1. The paper's Device abstraction (runtime recording path)
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class Block:
    """Handle for a device memory block (the paper's ``Block*``)."""

    var: int
    size: int


class RecordingDevice:
    """Paper §V ``Device``: records Malloc/Free/Exec and detects the iteration.

    In the paper this object fronts cudaMalloc/cudaFree until the pool is
    built.  Here it fronts nothing (we are planning, not allocating) but the
    recorded stream and the repeatability test are identical.
    """

    def __init__(self, min_period: int = 4):
        self._next_var = 0
        self._index = 0
        self._detector = IterationDetector(min_period=min_period)
        self.events: list[Event] = []

    # -- paper API ----------------------------------------------------------
    def malloc(self, size: int) -> Block:
        blk = Block(self._next_var, int(size))
        self._next_var += 1
        self._emit(EventKind.MALLOC, blk)
        return blk

    def free(self, blk: Block) -> None:
        self._emit(EventKind.FREE, blk)

    def exec(
        self,
        fn: Callable[..., Any] | None,
        read_blocks: Sequence[Block],
        write_blocks: Sequence[Block],
        *args: Any,
    ) -> Any:
        """Run an operation, recording its read/write sets (paper's ``Exec``)."""
        for blk in read_blocks:
            self._emit(EventKind.READ, blk)
        for blk in write_blocks:
            self._emit(EventKind.WRITE, blk)
        return fn(*args) if fn is not None else None

    # -- stream plumbing -----------------------------------------------------
    def _emit(self, kind: EventKind, blk: Block) -> None:
        ev = Event(kind, blk.var, blk.size, self._index)
        self._index += 1
        self.events.append(ev)
        self._detector.feed(ev)

    @property
    def iteration_detected(self) -> bool:
        return self._detector.period is not None

    def iteration_trace(self) -> IterationTrace:
        """The canonical one-iteration trace (PoolOpt's input)."""
        self._detector.finalize()
        return build_trace(self._detector.iteration_events())


# --------------------------------------------------------------------------
# 2. jaxpr-level lifetime extraction (the XLA-world adaptation)
# --------------------------------------------------------------------------


def _aval_bytes(aval) -> int:
    try:
        shape = aval.shape
        itemsize = np.dtype(aval.dtype).itemsize
    except Exception:  # tokens, abstract refs
        return 0
    return int(math.prod(shape)) * int(itemsize)


# Inline-expansion caps: scan bodies are unrolled at most this many times so a
# 500k-step decode loop doesn't produce a 500k-long event stream.  Lifetime
# *structure* (what overlaps what) is preserved by unrolling a few periods.
_MAX_SCAN_UNROLL = 64


def _eqn_cost(eqn) -> tuple[float, float]:
    """Rough (flops, bytes_touched) estimate for one jaxpr equation.

    Used only by the swap-schedule timing model; roofline numbers for the real
    system come from ``compiled.cost_analysis()``, never from this.
    """
    out_elems = 0.0
    bytes_touched = 0.0
    for ov in eqn.outvars:
        try:
            out_elems += float(math.prod(ov.aval.shape))
            bytes_touched += _aval_bytes(ov.aval)
        except Exception:
            pass
    for iv in eqn.invars:
        if not isinstance(iv, jcore.Literal):
            try:
                bytes_touched += _aval_bytes(iv.aval)
            except Exception:
                pass
    name = eqn.primitive.name
    flops = out_elems  # elementwise default
    if name == "dot_general":
        dims = eqn.params["dimension_numbers"][0]
        lhs = eqn.invars[0].aval.shape
        k = 1.0
        for d in dims[0]:
            k *= lhs[d]
        flops = 2.0 * out_elems * k
    elif name in ("conv_general_dilated",):
        rhs = eqn.invars[1].aval.shape  # kernel
        k = float(math.prod(rhs[:-1]))  # spatial*in_ch per out channel (approx)
        flops = 2.0 * out_elems * k
    elif name in ("reduce_sum", "reduce_max", "reduce_min", "argmax", "argmin"):
        try:
            flops = float(math.prod(eqn.invars[0].aval.shape))
        except Exception:
            pass
    return (flops, bytes_touched)


# Primitives whose body jaxpr runs once per call: inlined, so the temporaries
# of nested jits (jax.nn.silu, softmax), remat regions, custom derivatives and
# shard_map bodies reach the planner as variables of their own.
_CALL_PRIMS = frozenset(
    {"jit", "shard_map", "closed_call", "remat2", "custom_jvp_call", "custom_vjp_call"}
)


class _JaxprEventEmitter:
    """Virtual interpreter over a ClosedJaxpr that emits the event stream."""

    def __init__(self, max_scan_unroll: int = _MAX_SCAN_UNROLL):
        self.events: list[Event] = []
        self.names: dict[int, str] = {}
        self.sizes: dict[int, int] = {}
        self.op_costs: dict[int, tuple[float, float]] = {}  # index -> (flops, bytes)
        self._index = 0
        self._next_var = 0
        self._max_unroll = max_scan_unroll

    # -- var-id management: jaxpr Vars -> fresh integer ids per dynamic scope
    def _fresh(self, size: int, name: str = "") -> int:
        vid = self._next_var
        self._next_var += 1
        self.sizes[vid] = size
        if name:
            self.names[vid] = name
        return vid

    def _emit(self, kind: EventKind, vid: int) -> None:
        self.events.append(Event(kind, vid, self.sizes[vid], self._index))
        self._index += 1

    # -- interpretation -------------------------------------------------------
    def run(self, closed: jcore.ClosedJaxpr, arg_names: Sequence[str] | None = None):
        jaxpr = closed.jaxpr
        env: dict[Any, int] = {}
        # Function inputs (params, batch) pre-exist: lifetime starts at 0.
        for i, invar in enumerate(jaxpr.invars):
            name = arg_names[i] if arg_names and i < len(arg_names) else f"arg{i}"
            vid = self._fresh(_aval_bytes(invar.aval), name)
            env[invar] = vid
            self._emit(EventKind.MALLOC, vid)
        for cv, const in zip(jaxpr.constvars, closed.consts):
            size = int(np.asarray(const).nbytes) if hasattr(const, "nbytes") else 0
            vid = self._fresh(size, "const")
            env[cv] = vid
            self._emit(EventKind.MALLOC, vid)
        self._run_jaxpr(jaxpr, env)
        # Outputs are read once more at the end (returned to caller).
        for outvar in jaxpr.outvars:
            if not isinstance(outvar, jcore.Literal) and outvar in env:
                self._emit(EventKind.READ, env[outvar])

    def _read(self, env, atom) -> int | None:
        if isinstance(atom, jcore.Literal):
            return None
        return env.get(atom)

    def _run_jaxpr(self, jaxpr: jcore.Jaxpr, env: dict) -> None:
        for eqn in jaxpr.eqns:
            self._run_eqn(eqn, env)

    def _bind_outputs(self, eqn, env, suffix: str = "") -> None:
        for ov in eqn.outvars:
            if isinstance(ov, jcore.DropVar):
                continue
            name = f"{eqn.primitive.name}{suffix}"
            if eqn.primitive.name == "name":  # checkpoint_name label
                name = str(eqn.params.get("name", "name"))
            vid = self._fresh(_aval_bytes(ov.aval), name)
            env[ov] = vid
            self._emit(EventKind.MALLOC, vid)
            self._emit(EventKind.WRITE, vid)

    def _read_inputs(self, eqn, env) -> None:
        for iv in eqn.invars:
            vid = self._read(env, iv)
            if vid is not None:
                self._emit(EventKind.READ, vid)

    def _run_eqn(self, eqn, env: dict) -> None:
        prim = eqn.primitive.name
        if prim == "scan":
            self._run_scan(eqn, env)
            return
        if prim == "while":
            self._run_subjaxpr(eqn, env, eqn.params["body_jaxpr"], times=1)
            return
        if prim == "cond":
            self._read_inputs(eqn, env)
            branch = eqn.params["branches"][0]
            inner_env = {}
            # cond invars: [pred, *operands]
            for bv, iv in zip(branch.jaxpr.invars, eqn.invars[1:]):
                vid = self._read(env, iv)
                if vid is not None:
                    inner_env[bv] = vid
            self._run_jaxpr(branch.jaxpr, inner_env)
            self._bind_outputs(eqn, env)
            return
        if prim in _CALL_PRIMS:
            self._run_call(eqn, env, eqn.params.get("jaxpr") or eqn.params["call_jaxpr"])
            return
        # Default: a primitive compute op.
        self._read_inputs(eqn, env)
        cost_index = self._index  # the eqn's cost is charged to its first output
        self._bind_outputs(eqn, env)
        self.op_costs[cost_index] = _eqn_cost(eqn)

    def _run_call(self, eqn, env, sub) -> None:
        closed = sub if isinstance(sub, jcore.ClosedJaxpr) else jcore.ClosedJaxpr(sub, ())
        inner_env: dict = {}
        for bv, iv in zip(closed.jaxpr.invars, eqn.invars):
            vid = self._read(env, iv)
            if vid is not None:
                inner_env[bv] = vid
        for cv in closed.jaxpr.constvars:
            inner_env[cv] = self._fresh(0, "const")
            self._emit(EventKind.MALLOC, inner_env[cv])
        self._run_jaxpr(closed.jaxpr, inner_env)
        # Map results back out.
        for ov, inner_ov in zip(eqn.outvars, closed.jaxpr.outvars):
            if isinstance(ov, jcore.DropVar):
                continue
            if isinstance(inner_ov, jcore.Literal) or inner_ov not in inner_env:
                vid = self._fresh(_aval_bytes(ov.aval), eqn.primitive.name)
                self._emit(EventKind.MALLOC, vid)
                self._emit(EventKind.WRITE, vid)
            else:
                vid = inner_env[inner_ov]
            env[ov] = vid

    def _run_scan(self, eqn, env: dict) -> None:
        """Unroll a scan: per trip, xs slices are fresh small buffers, carries
        are fresh buffers replacing the previous trip's (refcount-freed), and
        per-trip ys slices accumulate into the stacked outputs."""
        p = eqn.params
        body: jcore.ClosedJaxpr = p["jaxpr"]
        length = int(p["length"])
        n_carry, n_consts = int(p["num_carry"]), int(p["num_consts"])
        trips = min(length, self._max_unroll)

        self._read_inputs(eqn, env)
        const_ids = [self._read(env, iv) for iv in eqn.invars[:n_consts]]
        carry_ids = [self._read(env, iv) for iv in eqn.invars[n_consts:n_consts + n_carry]]
        xs_atoms = eqn.invars[n_consts + n_carry:]

        body_invars = body.jaxpr.invars
        for t in range(trips):
            inner_env: dict = {}
            for bv, cid in zip(body_invars[:n_consts], const_ids):
                if cid is not None:
                    inner_env[bv] = cid
            for bv, cid in zip(body_invars[n_consts:n_consts + n_carry], carry_ids):
                if cid is not None:
                    inner_env[bv] = cid
            # xs slices: one layer's worth of each stacked input.
            for bv, xa in zip(body_invars[n_consts + n_carry:], xs_atoms):
                vid = self._fresh(_aval_bytes(bv.aval), f"scan_x[{t}]")
                inner_env[bv] = vid
                self._emit(EventKind.MALLOC, vid)
                self._emit(EventKind.WRITE, vid)
            for cv in body.jaxpr.constvars:
                inner_env[cv] = self._fresh(0, "const")
                self._emit(EventKind.MALLOC, inner_env[cv])
            self._run_jaxpr(body.jaxpr, inner_env)
            # New carries come from body outputs.
            new_carry = []
            for ov in body.jaxpr.outvars[:n_carry]:
                if isinstance(ov, jcore.Literal) or ov not in inner_env:
                    vid = self._fresh(_aval_bytes(ov.aval), "carry")
                    self._emit(EventKind.MALLOC, vid)
                    self._emit(EventKind.WRITE, vid)
                else:
                    vid = inner_env[ov]
                new_carry.append(vid)
            # ys slices are read (copied into the stacked output).
            for ov in body.jaxpr.outvars[n_carry:]:
                if not isinstance(ov, jcore.Literal) and ov in inner_env:
                    self._emit(EventKind.READ, inner_env[ov])
            carry_ids = new_carry
        self._bind_outputs(eqn, env, suffix=f"[{trips}x]")


def trace_step_fn(
    fn: Callable,
    *example_args,
    arg_names: Sequence[str] | None = None,
    max_scan_unroll: int = _MAX_SCAN_UNROLL,
    add_frees: bool = True,
) -> IterationTrace:
    """Trace ``fn`` at the given (ShapeDtypeStruct or array) args and return
    the one-iteration offline-DSA instance.

    FREE events are synthesized at last-use (refcount semantics), matching
    what the paper's runtime recorder observes from the framework's GC.
    """
    closed = jax.make_jaxpr(fn)(*example_args)
    return trace_jaxpr(closed, arg_names=arg_names, max_scan_unroll=max_scan_unroll)


def trace_jaxpr(
    closed: jcore.ClosedJaxpr,
    arg_names: Sequence[str] | None = None,
    max_scan_unroll: int = _MAX_SCAN_UNROLL,
) -> IterationTrace:
    em = _JaxprEventEmitter(max_scan_unroll=max_scan_unroll)
    em.run(closed, arg_names=arg_names)
    events, index_map = _with_frees(em.events)
    trace = build_trace(events)
    trace.op_costs = {
        index_map[i]: cost for i, cost in em.op_costs.items() if i in index_map
    }
    info_by_id = trace.by_id()
    for vid, name in em.names.items():
        if vid in info_by_id:
            info_by_id[vid].name = name
    return trace


def _with_frees(events: list[Event]) -> tuple[list[Event], dict[int, int]]:
    """Insert FREE events at each variable's last use (refcounting).

    Returns the re-indexed stream plus a map old_index -> new_index so that
    per-op metadata (cost estimates) can follow the re-indexing.
    """
    last_use: dict[int, int] = {}
    size: dict[int, int] = {}
    for ev in events:
        last_use[ev.var] = ev.index
        size[ev.var] = ev.size
    # Re-index: frees occupy fresh op indices interleaved after last uses.
    by_index: dict[int, list[int]] = {}
    for var, idx in last_use.items():
        by_index.setdefault(idx, []).append(var)
    out: list[Event] = []
    index_map: dict[int, int] = {}
    cursor = 0
    for ev in events:
        index_map[ev.index] = cursor
        out.append(Event(ev.kind, ev.var, ev.size, cursor))
        cursor += 1
        for var in by_index.get(ev.index, ()):
            out.append(Event(EventKind.FREE, var, size[var], cursor))
            cursor += 1
    return out, index_map
