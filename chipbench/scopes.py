"""Device time of the program's named scopes in a traced window.

The model code names its layers with ``jax.named_scope`` (``embed``,
``layers``, ``attn``, ``qkv``, ``kv_write``, ``sdpa``, ``out_proj``, ``ffn``,
``head``, ...). JAX writes the scopes into the ``op_name`` metadata of every
HLO instruction; the TPU profile names each op event by its instruction's
text (``%fusion.12 = bf16[...] fusion(...)``) and carries no op name. So an
op's scope comes from the compiled program's HLO text: ``hlo_scopes`` maps
each instruction to the innermost program scope of its ``op_name``. An
instruction without an ``op_name`` (a copy XLA inserted, say) takes the scope
of the instruction whose computation holds it: a copy in the body of the
layers' ``while`` counts under ``layers``. Where that gives none, as in the
entry computation, it takes the scope of its first operand that has one: the
copy of the ``while``'s cache result into the step's output counts under
``layers`` too. An op with no scope even then (a weight cast that XLA hoisted
out of the loop, whose operand is a parameter) is ``unattributed``.

``scope_times`` sums the self time of each op (trace.self_times: a loop's own
event holds its body's ops) by scope over the traced window, for the runs of
one compiled program, and divides by the number of runs: device milliseconds
a run by scope, which add up to the program's mean device time.

The persistent compilation cache keys a program with its metadata stripped,
so a program that differs from a cached one in its scopes alone loads the
cached executable and the cached metadata. The HLO text read here has to come
from a compile whose key holds the metadata
(``jax_compilation_cache_include_metadata_in_key``) or from no cache at all.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict

from chipbench import trace

# The scopes of models/transformer.py and models/attention.py, any depth.
SCOPES = frozenset({
    "embed", "layers", "head",
    "attn", "mla", "mamba", "hybrid", "cross_attn", "ffn", "moe",
    "qkv", "kv_write", "sdpa", "out_proj",
})
UNATTRIBUTED = "unattributed"

# The decode step's layers, each the scopes whose time it sums: attention
# over the cache; the cache's writes, and the layer loop's own slices,
# updates and copies of the stacked cache; the weight-reading projections,
# FFN and head with their norms, residual adds and masks.
DECODE_LAYERS = {
    "decode_sdpa_ms": ("sdpa",),
    "decode_kv_cache_ms": ("kv_write", "layers"),
    "decode_weights_ms": ("embed", "qkv", "out_proj", "ffn", "head", "attn"),
}

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(
    r"\b(?:calls|body|condition|to_apply|true_computation|false_computation)=%?([\w.\-]+)")
_CALL_LISTS = re.compile(r"\b(?:branch_computations|called_computations|calls)=\{([^}]*)\}")
_OPERAND = re.compile(r"%([\w.\-]+)")


def instruction_of(event_name: str) -> str:
    """The instruction an op event ran: ``%copy.95 = bf16[...] copy(...)`` -> ``copy.95``."""
    m = re.match(r"%?([\w.\-]+) = ", event_name)
    return m.group(1) if m else event_name


def scope_of_op_name(op_name: str) -> str | None:
    """The innermost program scope in an ``op_name`` path, or None."""
    inner = None
    for part in op_name.split("/"):
        if part in SCOPES:
            inner = part
    return inner


def hlo_scopes(hlo_text: str) -> dict[str, str | None]:
    """Instruction name -> its innermost program scope (None: no scope), from
    an HLO module's text, with the inheritance rules of the module docstring."""
    own: dict[str, str | None] = {}    # instruction -> scope of its op_name
    named: dict[str, bool] = {}        # instruction -> has an op_name
    home: dict[str, str] = {}          # instruction -> its computation
    operands: dict[str, list[str]] = {}
    caller: dict[str, str] = {}        # computation -> an instruction calling it
    computation = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            computation = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m or computation is None:
            continue
        name = m.group(1)
        home[name] = computation
        op = _OP_NAME.search(line)
        named[name] = bool(op and op.group(1))
        own[name] = scope_of_op_name(op.group(1)) if named[name] else None
        operands[name] = _OPERAND.findall(line[m.end():])
        called = _CALLS.findall(line)
        for group in _CALL_LISTS.findall(line):
            called += [c.strip().lstrip("%") for c in group.split(",") if c.strip()]
        for c in called:
            caller.setdefault(c, name)

    out: dict[str, str | None] = {}

    def resolve(name: str) -> str | None:
        # Data flow and calls in HLO hold no cycle.
        if name not in out:
            if named[name]:
                scope = own[name]
            else:
                up = caller.get(home[name])
                scope = resolve(up) if up else None
                for o in operands[name]:
                    if scope is None and home.get(o) == home[name]:
                        scope = resolve(o)
            out[name] = scope
        return out[name]

    for name in home:
        resolve(name)
    return out


def scope_times(raw: dict, scopes: dict[str, str | None], module: str) -> dict | None:
    """Device ms a run by scope for the runs of the compiled program whose
    name holds ``module``, inside the traced window, on the first device.

    ``raw`` is what ``trace.extract`` returns. Beside the ms by scope: the
    three largest unattributed ops, and the ops the HLO text does not hold
    (none, where the text is that of the executable that ran). Returns None
    where no run of the program falls in the window, or where no op of it
    has a scope (a program without named scopes)."""
    w0, w1 = trace.window_of(raw)
    devices = sorted(raw["ops"])
    if not devices:
        return None
    dev = devices[0]
    runs = sorted((s, s + d) for n, s, d in raw["modules"].get(dev, [])
                  if module in n and s >= w0 and s + d <= w1)
    if not runs:
        return None
    starts = [r0 for r0, _ in runs]
    ivs = []
    for n, s, d in raw["ops"][dev]:
        # An op belongs to the run that holds its start.
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < runs[i][1]:
            ivs.append((s, s + d, instruction_of(n)))
    by_op = trace.self_times(ivs)
    total = defaultdict(float)
    lost = defaultdict(float)
    for op, t in by_op.items():
        scope = scopes.get(op)
        total[scope or UNATTRIBUTED] += t
        if scope is None:
            lost[op] += t
    if set(total) <= {UNATTRIBUTED}:
        return None
    n = len(runs)
    return {
        "runs": n,
        "module_ms": sum(r1 - r0 for r0, r1 in runs) / n * 1e-6,
        "scope_ms": {k: v / n * 1e-6 for k, v in sorted(total.items())},
        "unattributed_top": [[op, t / n * 1e-6] for op, t in
                             sorted(lost.items(), key=lambda kv: -kv[1])[:3]],
        "missing_ops": sorted(op for op in by_op if op not in scopes),
    }
