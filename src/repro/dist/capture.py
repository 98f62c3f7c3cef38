"""Sharded trace capture: per-device event streams from a partitioned step.

The paper derives lifetimes and read/write order from the iterative loop of a
*single* device.  Under a ``shard_map``/``jit``-sharded step each device owns
a *fraction* of every partitioned tensor and crosses the interconnect at
every collective — both of which the single-device tracer cannot see.  This
module walks the same jaxpr the single-device tracer walks, but

  * divides every variable's size by its *shard divisor* — derived from the
    step's input ``PartitionSpec``s (the launch/steps.py spec builders) and
    propagated through equations (an output inherits the largest input
    divisor that divides its byte size; anything else is replicated), and
  * tags collective equations (``psum``/``all_gather``/``reduce_scatter``/…)
    with cost-model durations on the device interconnect, so the planner's
    timeline contains the windows a swap may (or may not) overlap.

On a 1x1 mesh every divisor is 1 and no collective fires, so the emitted
event stream — and therefore the solved plan — is byte-identical to the
single-device ``trace_step_fn`` path (pinned by tests/test_dist.py).

SPMD means every device executes the same program over same-shaped shards,
so one capture describes a whole *device group*; ``ShardedCapture`` keeps
the group->devices map explicit so heterogeneous groups (e.g. per-host
parameter servers) slot in without changing consumers.
"""

from __future__ import annotations

import hashlib
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Sequence

from ..core.events import IterationTrace, build_trace
from ..core.simulator import HardwareSpec
from ..core.trace import _MAX_SCAN_UNROLL, _JaxprEventEmitter, _with_frees

# jaxpr primitives that cross the device interconnect.  ``pmean`` lowers to
# psum; reduce_scatter appears as psum_scatter in recent jax.
COLLECTIVE_PRIMS = {
    "psum": "all_reduce",
    "pmax": "all_reduce",
    "pmin": "all_reduce",
    "all_gather": "all_gather",
    "psum_scatter": "reduce_scatter",
    "reduce_scatter": "reduce_scatter",
    "all_to_all": "all_to_all",
    "ppermute": "permute",
    "pbroadcast": "broadcast",
}


# ------------------------------------------------------------------- meshes
@dataclass(frozen=True)
class MeshSpec:
    """Device-mesh shape as data: ordered (axis name, size) pairs.

    A plain-data twin of ``jax.sharding.Mesh`` so planning and benchmarks
    never need real (or force-hosted) devices — the capture walks an
    abstract jaxpr and only the *sizes* matter.
    """

    axes: tuple[tuple[str, int], ...]

    @classmethod
    def make(cls, **axes: int) -> "MeshSpec":
        return cls(tuple((k, int(v)) for k, v in axes.items()))

    @classmethod
    def parse(cls, text: str) -> "MeshSpec":
        """Parse ``"data=4"`` / ``"data=4,model=2"`` (CLI mesh syntax)."""
        pairs = []
        for item in text.split(","):
            item = item.strip()
            if not item:
                continue
            name, _, size = item.partition("=")
            try:
                pairs.append((name.strip(), int(size)))
            except ValueError:
                raise ValueError(f"bad mesh axis {item!r} (want name=size)")
        if not pairs:
            raise ValueError(f"empty mesh spec {text!r}")
        return cls(tuple(pairs))

    @classmethod
    def from_mesh(cls, mesh) -> "MeshSpec":
        """From a live ``jax.sharding.Mesh`` (launch/mesh.py builders)."""
        return cls(tuple((str(n), int(mesh.shape[n])) for n in mesh.axis_names))

    @property
    def num_devices(self) -> int:
        n = 1
        for _, s in self.axes:
            n *= s
        return n

    def axis_size(self, names) -> int:
        """Product of the named axes' sizes (missing axes count as 1)."""
        if names is None:
            return 1
        if isinstance(names, str):
            names = (names,)
        sizes = dict(self.axes)
        n = 1
        for name in names:
            n *= sizes.get(name, 1)
        return n

    def signature(self) -> str:
        """Filesystem/key-safe mesh shape, empty for a single device so 1x1
        captures key identically to the legacy single-device path."""
        if self.num_devices <= 1:
            return ""
        return "x".join(f"{n}{s}" for n, s in self.axes)


def shard_divisor(shape: Sequence[int], spec, mesh: MeshSpec) -> int:
    """How many ways a tensor of ``shape`` is split under ``spec``.

    ``spec`` is a ``jax.sharding.PartitionSpec``-like sequence: one entry per
    dim, each None, an axis name, or a tuple of axis names.  A dim that the
    mesh axes do not divide evenly degrades to replicated for that dim —
    matching the launch/steps.py divisibility guard.
    """
    div = 1
    for dim, part in zip(shape, spec):
        if part is None:
            continue
        k = mesh.axis_size(part)
        if k > 1 and dim % k == 0:
            div *= k
    return div


def divisors_from_specs(shapes, specs, mesh: MeshSpec) -> list[int]:
    """Per-leaf shard divisors for a pytree of (ShapeDtypeStruct, spec) pairs,
    flattened in jaxpr-invars order (the order ``jax.make_jaxpr`` flattens
    arguments)."""
    import jax
    from jax.sharding import PartitionSpec

    shape_leaves = jax.tree_util.tree_leaves(shapes)
    spec_leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: x is None or isinstance(x, PartitionSpec)
    )
    if len(shape_leaves) != len(spec_leaves):
        raise ValueError(
            f"{len(shape_leaves)} shape leaves vs {len(spec_leaves)} spec leaves"
        )
    out = []
    for leaf, spec in zip(shape_leaves, spec_leaves):
        if spec is None:
            out.append(1)
        else:
            out.append(shard_divisor(leaf.shape, spec, mesh))
    return out


# -------------------------------------------------------------- collectives
@dataclass(frozen=True)
class Collective:
    """One tagged interconnect operation within the iteration."""

    index: int          # op index in the per-device event stream
    kind: str           # canonical name (all_reduce / all_gather / ...)
    nbytes: int         # per-device payload bytes
    seconds: float      # modeled interconnect occupancy


def collective_seconds(kind: str, nbytes: int, ndev: int, hw: HardwareSpec) -> float:
    """Ring cost model: all-reduce moves 2(D-1)/D of the payload per device,
    gather/scatter (D-1)/D, permutes one hop."""
    if ndev <= 1 or nbytes <= 0:
        return 0.0
    bw = hw.ici_bw or hw.link_bw
    if kind == "all_reduce":
        factor = 2.0 * (ndev - 1) / ndev
    elif kind in ("all_gather", "reduce_scatter", "all_to_all"):
        factor = (ndev - 1) / ndev
    else:  # permute / broadcast: one hop
        factor = 1.0
    return factor * nbytes / bw + hw.collective_latency_s


# ------------------------------------------------------------------ capture
@dataclass
class ShardedTrace:
    """Per-device-group iteration trace plus its tagged collectives."""

    trace: IterationTrace
    collectives: list[Collective] = field(default_factory=list)

    def collective_map(self) -> dict[int, float]:
        """Op index -> seconds, the shape ``runtime.Tenant.collectives`` takes."""
        out: dict[int, float] = {}
        for c in self.collectives:
            out[c.index] = out.get(c.index, 0.0) + c.seconds
        return out


@dataclass
class ShardedCapture:
    """One sharded capture: the mesh, the per-group streams, and which
    devices run which group (SPMD: one group spanning every device)."""

    mesh: MeshSpec
    groups: dict[str, ShardedTrace]
    device_group: dict[int, str]
    spec_signature: str = ""

    def plan_topology(self) -> str:
        """The ``PlanKey.topology`` value: mesh shape + PartitionSpec
        signature.  Empty on a 1x1 mesh, so single-device plans keep their
        legacy keys (and a sharded plan can never alias one)."""
        mesh_sig = self.mesh.signature()
        if not mesh_sig:
            return ""
        return f"{mesh_sig}-{self.spec_signature}" if self.spec_signature else mesh_sig


class _ShardedEventEmitter(_JaxprEventEmitter):
    """The single-device jaxpr interpreter, re-sized per shard.

    Every variable gets a *divisor*: inputs from their PartitionSpecs,
    intermediates by propagation (largest input divisor that divides the
    output's byte size; otherwise replicated).  The divisor context is a
    stack-restored instance attribute because the parent class allocates ids
    deep inside scan/call handling — every ``_fresh`` sees the divisor of
    the innermost equation being interpreted.
    """

    def __init__(self, mesh: MeshSpec, hw: HardwareSpec,
                 max_scan_unroll: int = _MAX_SCAN_UNROLL):
        super().__init__(max_scan_unroll=max_scan_unroll)
        self.mesh = mesh
        self.hw = hw
        self.divisors: dict[int, int] = {}
        self.collectives: list[Collective] = []
        self._ctx_div = 1
        # Per-input divisors, drained positionally by the first
        # len(jaxpr.invars) _fresh calls — exactly the input mallocs the
        # parent run() emits before anything else.
        self._arg_divs: "deque[int]" = deque()

    # -- sizing ---------------------------------------------------------
    def _fresh(self, size: int, name: str = "") -> int:
        div = self._arg_divs.popleft() if self._arg_divs else self._ctx_div
        if div <= 1 or size <= 0 or size % div != 0:
            div = 1
        vid = super()._fresh(size // div, name)
        self.divisors[vid] = div
        return vid

    def _propagated_div(self, eqn, env) -> int:
        div = 1
        for iv in eqn.invars:
            vid = self._read(env, iv)
            if vid is not None:
                div = max(div, self.divisors.get(vid, 1))
        return div

    # -- interpretation -------------------------------------------------
    def _run_eqn(self, eqn, env: dict) -> None:
        prim = eqn.primitive.name
        kind = COLLECTIVE_PRIMS.get(prim)
        prev = self._ctx_div
        self._ctx_div = self._propagated_div(eqn, env)
        try:
            if kind is not None and self.mesh.num_devices > 1:
                self._run_collective(eqn, env, kind)
            else:
                super()._run_eqn(eqn, env)
        finally:
            self._ctx_div = prev

    def _run_scan(self, eqn, env: dict) -> None:
        """Parent scan unrolling with *per-atom* divisor context.

        The generic eqn hook applies the max input divisor to every output,
        which is wrong inside a scan: a replicated stacked-weights xs input
        must not inherit the batch-sharded carry's divisor (its per-trip
        slices would be undersized by the shard factor, and per-device peak
        would undercount replicated memory).  Mirrors
        ``core.trace._JaxprEventEmitter._run_scan`` event-for-event — the
        1x1 byte-identity tests pin any divergence — inserting only
        ``_ctx_div`` assignments from each atom's own recorded divisor.
        """
        from ..core.events import EventKind
        from ..core.trace import _aval_bytes, jcore

        scan_div = self._ctx_div  # the generic propagated div, for outputs
        p = eqn.params
        body = p["jaxpr"]
        length = int(p["length"])
        n_carry, n_consts = int(p["num_carry"]), int(p["num_consts"])
        trips = min(length, self._max_unroll)

        self._read_inputs(eqn, env)
        const_ids = [self._read(env, iv) for iv in eqn.invars[:n_consts]]
        carry_ids = [self._read(env, iv) for iv in eqn.invars[n_consts:n_consts + n_carry]]
        xs_atoms = eqn.invars[n_consts + n_carry:]
        xs_divs = [
            self.divisors.get(self._read(env, xa), 1) if self._read(env, xa) is not None else 1
            for xa in xs_atoms
        ]
        carry_divs = [
            self.divisors.get(cid, 1) if cid is not None else 1 for cid in carry_ids
        ]

        body_invars = body.jaxpr.invars
        for t in range(trips):
            inner_env: dict = {}
            for bv, cid in zip(body_invars[:n_consts], const_ids):
                if cid is not None:
                    inner_env[bv] = cid
            for bv, cid in zip(body_invars[n_consts:n_consts + n_carry], carry_ids):
                if cid is not None:
                    inner_env[bv] = cid
            # xs slices: one layer's worth of each stacked input, sharded
            # exactly as the stacked input itself is.
            for (bv, xa), xdiv in zip(
                zip(body_invars[n_consts + n_carry:], xs_atoms), xs_divs
            ):
                self._ctx_div = xdiv
                vid = self._fresh(_aval_bytes(bv.aval), f"scan_x[{t}]")
                inner_env[bv] = vid
                self._emit(EventKind.MALLOC, vid)
                self._emit(EventKind.WRITE, vid)
            self._ctx_div = 1
            for cv in body.jaxpr.constvars:
                inner_env[cv] = self._fresh(0, "const")
                self._emit(EventKind.MALLOC, inner_env[cv])
            self._run_jaxpr(body.jaxpr, inner_env)
            # New carries come from body outputs; a literal/missing output
            # keeps the incoming carry's sharding.
            new_carry = []
            for ov, cdiv in zip(body.jaxpr.outvars[:n_carry], carry_divs):
                if isinstance(ov, jcore.Literal) or ov not in inner_env:
                    self._ctx_div = cdiv
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
        self._ctx_div = scan_div
        self._bind_outputs(eqn, env, suffix=f"[{trips}x]")

    def _run_collective(self, eqn, env: dict, kind: str) -> None:
        """A collective reads its (per-shard) inputs, occupies the
        interconnect, and writes its outputs; the payload is the per-shard
        input bytes already divided by the sharding."""
        nbytes = 0
        for iv in eqn.invars:
            vid = self._read(env, iv)
            if vid is not None:
                nbytes += self.sizes.get(vid, 0)
        self._read_inputs(eqn, env)
        cost_index = self._index  # charged to the first output, like compute
        self._bind_outputs(eqn, env)
        ndev = _collective_device_count(eqn, self.mesh)
        seconds = collective_seconds(kind, nbytes, ndev, self.hw)
        if seconds > 0.0:
            self.collectives.append(Collective(cost_index, kind, nbytes, seconds))

    def run_with_divisors(
        self,
        closed,
        arg_names: Sequence[str] | None = None,
        arg_divisors: Sequence[int] | None = None,
    ) -> None:
        """Parent ``run`` with per-input divisors from the PartitionSpecs.

        Delegates to ``_JaxprEventEmitter.run`` (byte-identical event order
        by construction): the divisor queue is drained positionally by the
        input mallocs — the parent's first ``len(invars)`` ``_fresh`` calls.
        """
        n_inputs = len(closed.jaxpr.invars)
        self._arg_divs = deque((arg_divisors or [])[:n_inputs])
        try:
            self.run(closed, arg_names=arg_names)
        finally:
            self._arg_divs = deque()


def _synthesized(
    extra: Sequence[tuple], trace: IterationTrace, mesh: MeshSpec, hw: HardwareSpec
) -> list[Collective]:
    """Cost-model collectives a jitted (GSPMD) jaxpr cannot show: XLA inserts
    them at compile time, so callers name the known ones.  Entries are
    ``(kind, nbytes[, op_index[, ndev]])``: op_index defaults to the
    iteration boundary (the data-parallel gradient sync position; None also
    means that), a float in [0, 1) is a fraction of the iteration (op counts
    aren't known pre-capture), and ``ndev`` scopes the collective to its
    participating axis (e.g. 4 for a data-axis all-reduce on a
    data=4,model=2 mesh) instead of the whole mesh."""
    out: list[Collective] = []
    tail = max(0, trace.num_indices - 1)
    for entry in extra:
        kind, nbytes = entry[0], int(entry[1])
        index = tail
        if len(entry) > 2 and entry[2] is not None:
            pos = entry[2]
            index = int(pos * tail) if isinstance(pos, float) and 0 <= pos < 1 else int(pos)
        index = max(0, min(index, tail))
        ndev = int(entry[3]) if len(entry) > 3 and entry[3] else mesh.num_devices
        seconds = collective_seconds(kind, nbytes, ndev, hw)
        if seconds > 0.0:
            out.append(Collective(index, kind, nbytes, seconds))
    return out


def sharded_param_bytes(shapes, specs, mesh: MeshSpec) -> int:
    """Per-device bytes of a (shapes, PartitionSpecs) pytree pair — what one
    device holds of the parameters, i.e. its gradient-sync payload."""
    import jax
    import numpy as np

    divs = divisors_from_specs(shapes, specs, mesh)
    leaves = jax.tree_util.tree_leaves(shapes)
    return sum(
        int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize // d
        for leaf, d in zip(leaves, divs)
    )


def gradient_sync_collective(
    pshapes, pspecs, mesh: MeshSpec, axes=("pod", "data")
) -> "tuple | None":
    """The data-parallel gradient all-reduce as an ``extra_collectives``
    entry (iteration boundary, scoped to the data axes), or None when the
    mesh has no data parallelism.  One definition shared by the shardplan
    CLI and the benchmarks so both price the same cost model."""
    ndev = mesh.axis_size(tuple(axes))
    if ndev <= 1:
        return None
    return ("all_reduce", sharded_param_bytes(pshapes, pspecs, mesh), None, ndev)


def _collective_device_count(eqn, mesh: MeshSpec) -> int:
    """Devices participating in a collective: the product of its axis-name
    params' sizes, falling back to the whole mesh."""
    names = eqn.params.get("axes") or eqn.params.get("axis_name")
    if names is None:
        return mesh.num_devices
    if isinstance(names, (str, int)):
        names = (names,)
    n = 1
    for a in names:
        n *= mesh.axis_size(a) if isinstance(a, str) else 1
    return n if n > 1 else mesh.num_devices


def _spec_signature_from_divisors(divisors: Sequence[int]) -> str:
    """Stable short hash of the per-input shard pattern: two captures of the
    same step under different PartitionSpecs must key differently."""
    raw = ",".join(str(d) for d in divisors)
    return hashlib.sha256(raw.encode()).hexdigest()[:8]


# Must match plan.passes.TraceCapture's default: on a 1x1 mesh the capture
# shares the single-device PlanKey (empty topology), so any tracer setting
# that changes the event stream has to agree or the two paths would write
# different plans under one cache name.
_CAPTURE_MAX_SCAN_UNROLL = 16


def capture_sharded_trace(
    fn: Callable,
    *example_args,
    mesh: MeshSpec,
    hw: HardwareSpec,
    in_specs=None,
    arg_names: Sequence[str] | None = None,
    max_scan_unroll: int = _CAPTURE_MAX_SCAN_UNROLL,
    extra_collectives: Sequence[tuple[str, int]] = (),
) -> ShardedCapture:
    """Capture the per-device event stream of one sharded step.

    ``in_specs`` is a pytree of PartitionSpecs matching ``example_args``
    (the launch/steps.py builders produce exactly this), or None for fully
    replicated inputs.  ``extra_collectives`` appends cost-model collectives
    the jaxpr does not contain explicitly — a GSPMD-jitted train step holds
    no collective eqns (XLA inserts them at compile time), so callers name
    the known ones, e.g. ``[("all_reduce", grad_bytes)]`` for the data-
    parallel gradient sync at the iteration boundary.

    Works entirely on abstract values: no real (or force-hosted) multi-device
    runtime is required, which is what lets benchmarks and CI capture 4-way
    meshes on a single-CPU sandbox.
    """
    import jax

    # The mesh's axes are bound while tracing, so a step written with named
    # collectives (lax.psum over "data") traces as it would under shard_map.
    closed = jax.make_jaxpr(fn, axis_env=list(mesh.axes))(*example_args)
    arg_divisors = None
    if in_specs is not None:
        arg_divisors = divisors_from_specs(example_args, in_specs, mesh)
    em = _ShardedEventEmitter(mesh, hw, max_scan_unroll=max_scan_unroll)
    em.run_with_divisors(closed, arg_names=arg_names, arg_divisors=arg_divisors)
    events, index_map = _with_frees(em.events)
    trace = build_trace(events)
    trace.op_costs = {
        index_map[i]: cost for i, cost in em.op_costs.items() if i in index_map
    }
    info_by_id = trace.by_id()
    for vid, name in em.names.items():
        if vid in info_by_id:
            info_by_id[vid].name = name
    collectives = [
        Collective(index_map[c.index], c.kind, c.nbytes, c.seconds)
        for c in em.collectives
        if c.index in index_map
    ]
    collectives.extend(_synthesized(extra_collectives, trace, mesh, hw))
    if mesh.num_devices > 1 and collectives:
        trace.op_extra_s = {}
        for c in collectives:
            trace.op_extra_s[c.index] = trace.op_extra_s.get(c.index, 0.0) + c.seconds
    sharded = ShardedTrace(trace=trace, collectives=sorted(collectives, key=lambda c: c.index))
    spec_sig = (
        _spec_signature_from_divisors(arg_divisors)
        if arg_divisors and mesh.num_devices > 1
        else ""
    )
    return ShardedCapture(
        mesh=mesh,
        groups={"spmd": sharded},
        device_group={d: "spmd" for d in range(mesh.num_devices)},
        spec_signature=spec_sig,
    )


def shard_existing_trace(
    trace: IterationTrace,
    mesh: MeshSpec,
    hw: HardwareSpec,
    divisor_fn: Callable[[str, int], int],
    extra_collectives: Sequence[tuple[str, int]] = (),
) -> ShardedCapture:
    """Re-size an already-captured single-device trace by a per-variable
    divisor rule ``divisor_fn(name, size) -> int`` (e.g. batch-sharded
    activations / replicated weights for the CNN benchmark traces).

    The cheap route into ``repro.dist`` for workloads whose trace exists but
    whose step function is not at hand; the jaxpr route above is the
    faithful one.
    """
    variables = []
    applied: list[int] = []
    for v in trace.variables:
        div = max(1, int(divisor_fn(v.name, v.size)))
        if v.size % div != 0:
            div = 1
        applied.append(div)
        variables.append(
            type(v)(
                var=v.var,
                size=v.size // div,
                alloc_index=v.alloc_index,
                free_index=v.free_index,
                accesses=list(v.accesses),
                access_is_write=list(v.access_is_write),
                name=v.name,
            )
        )
    sharded = IterationTrace(variables, trace.num_indices)
    if trace.op_costs is not None:
        # Per-device compute touches per-device bytes; flops scale the same
        # way for batch-parallel work.
        ndev = mesh.num_devices
        sharded.op_costs = {
            i: (f / ndev, b / ndev) for i, (f, b) in trace.op_costs.items()
        }
    collectives = _synthesized(extra_collectives, sharded, mesh, hw)
    if collectives:
        sharded.op_extra_s = {}
        for c in collectives:
            sharded.op_extra_s[c.index] = sharded.op_extra_s.get(c.index, 0.0) + c.seconds
    return ShardedCapture(
        mesh=mesh,
        groups={"spmd": ShardedTrace(sharded, collectives)},
        device_group={d: "spmd" for d in range(mesh.num_devices)},
        # Signed by the divisors actually applied, so two different rules
        # (or an edited rule) over the same trace never share a PlanKey.
        spec_signature=f"rule{_spec_signature_from_divisors(applied)}",
    )
