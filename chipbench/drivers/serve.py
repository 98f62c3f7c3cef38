"""Serving cells: prefill, then greedy decode through the cache, composed as
``launch/serve.py`` composes them.

Set-up makes the weights from the seed, prefills a batch of seeded prompts
into a cache of ``max_seq`` positions (the traffic's long context is set-up
it needs), and warms the decode step until two successive steps compile
nothing. The window then serves turns: each turn restarts at the prompt's
end with a new seeded token per sequence and decodes ``turn_len`` tokens
over the built cache, the argmax taken on the device and fed to the next
step there. The host streams every token as a streaming server does, and
keeps the chip fed while it reads: ``AHEAD_S`` seconds of steps stay
dispatched ahead of the one whose token it waits for, each token's copy to
host memory started as its step is dispatched, so a host stall shorter than
that leaves the chip busy. Each read closes one inter-token gap of every
sequence of the batch. When the window's time is up nothing more is sent:
every step sent is read, and the window ends with the last read, so all that
work counts over all that time. After the window a seeded sample of the
finished requests is replayed through the plain reference.
"""

from __future__ import annotations

import collections
import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import bench, checks, weights
from chipbench.reference.common import REFERENCE
from chipbench.traffic import SyntheticTokens

# Seconds of decode steps kept dispatched ahead of the token the host reads.
AHEAD_S = 5.0


class Traffic:
    """Seeded prompts [batch, prompt_len] and each turn's first tokens [batch, 1]."""

    def __init__(self, cfg: dict, wl: dict, seed: int):
        V, B = cfg["vocab_size"], wl["batch"]
        self.prompt = SyntheticTokens(V, wl["prompt_len"], B, seed).ids_at(0)[:, : wl["prompt_len"]]
        self._turns = SyntheticTokens(V, 0, B, seed)

    def first(self, turn: int) -> np.ndarray:
        return self._turns.ids_at(turn + 1)[:, :1]


def served_sequence(traffic: Traffic, turn: int, row: int, tokens: np.ndarray) -> np.ndarray:
    """The ids the cache held for one request: prompt, first token, and every
    served token but the last."""
    return np.concatenate([traffic.prompt[row], traffic.first(turn)[row], tokens[:-1]])


def reference_gaps(cfg: dict, wl: dict, seed: int, requests, rank_by=None):
    """Per request, the reference's gap of each served token (or, with
    ``rank_by``, of the token that ``rank_by``'s logits put first)."""
    ref = bench.kind(cfg["kind"]).reference
    P = wl["prompt_len"]
    params = weights.make(cfg, seed)
    logits = jax.jit(lambda p, ids: ref.logits(p, ids, P, cfg, REFERENCE))
    other = None if rank_by is None else jax.jit(lambda p, ids: ref.logits(p, ids, P, cfg, rank_by))
    gaps = []
    for ids, served in requests:
        want = logits(params, jnp.asarray(ids))
        tokens = jnp.asarray(served) if other is None else jnp.argmax(
            other(params, jnp.asarray(ids)), axis=-1)
        gaps.append(np.asarray(checks.served_gaps(want, tokens)))
    return gaps


def readings(cfg: dict, wl: dict, seed: int, requests) -> dict:
    """What the limit is set from, beside the program's own gap: the control
    (the reference in float8) on the same prompts and tokens, and the gap a
    served token altered at one seeded position per request would read."""
    from chipbench.reference.common import CONTROL

    with jax.default_matmul_precision("highest"):
        control = reference_gaps(cfg, wl, seed, requests, rank_by=CONTROL)
        rng = np.random.Generator(np.random.Philox(key=seed, counter=[0, 0, 0, 2]))
        altered = []
        for ids, served in requests:
            at = int(rng.integers(len(served)))
            served = served.copy()
            served[at] = (served[at] + 1) % cfg["vocab_size"]
            altered.append((ids, served))
        altered = reference_gaps(cfg, wl, seed, altered)
    return {"control_gap": max(float(np.max(g)) for g in control),
            "altered_token_gap": max(float(np.max(g)) for g in altered)}


def run(wl: dict, cfg: dict, ctx) -> dict:
    from repro.models import build_model

    seed, dev = ctx.seed, ctx.devices[0]
    B, P, T, max_seq = wl["batch"], wl["prompt_len"], wl["turn_len"], wl["max_seq"]
    if P + T > max_seq:
        raise SystemExit(f"chipbench: prompt {P} + turn {T} exceed max_seq {max_seq}")
    mc = ctx.program_config(cfg)
    model = build_model(mc)
    kind = bench.kind(cfg["kind"])
    traffic = Traffic(cfg, wl, seed)

    params = weights.make(cfg, seed)
    bad = weights.layout_mismatch(jax.eval_shape(lambda: params), model.init_shapes())
    if bad:
        raise SystemExit(f"chipbench: the weights do not fit the program's layout: {bad}")

    prefill = jax.jit(lambda p, b: model.prefill(p, b, max_seq=max_seq))
    _, cache = prefill(params, {"tokens": jnp.asarray(traffic.prompt)})
    jax.block_until_ready(cache)

    def serve_decode_step(params, cache, tok, pos):
        logits, cache = model.decode_step(params, cache, tok, pos)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None], cache

    decode = jax.jit(ctx.break_step(serve_decode_step), donate_argnums=(1,))

    # Warm-up over the first positions after the prompt (each turn rewrites them).
    tok, quiet, n = jnp.asarray(traffic.first(0)), 0, 0
    while quiet < 2:
        before = ctx.compiles.count
        tok, cache = decode(params, cache, tok, np.int32(P + n))
        tok.block_until_ready()
        quiet = quiet + 1 if ctx.compiles.count == before else 0
        n += 1
    # The queue's depth, from the time of a few warm steps.
    t = time.perf_counter()
    for i in range(3):
        tok, cache = decode(params, cache, tok, np.int32(P + n + i))
    tok.block_until_ready()
    step_s = (time.perf_counter() - t) / 3
    ahead = max(1, math.ceil(AHEAD_S / step_s))
    bench.log(f"queue: {ahead} steps dispatched ahead ({step_s * 1e3:.2f} ms a warm step)")
    mem = decode.lower(params, cache, tok, np.int32(P)).compile().memory_analysis()

    spans = ctx.spans
    served = {}             # turn -> its tokens read so far, each [batch, 1]
    token_gaps, contexts = [], []
    turn, pos, n = 0, P, 0  # the next step's turn, position and index in its turn
    tok = jnp.asarray(traffic.first(0))
    queue = collections.deque()  # (turn, token) of each step sent and not yet read
    sent = 0
    # Everything made so far stays out of the collector's passes in the window.
    gc.collect()
    gc.freeze()
    profiler = ctx.start_trace()
    compile_s = ctx.compiles.seconds + ctx.compiles.lowering_s
    t0, c0 = time.perf_counter(), ctx.compiles.count
    last = t0
    with spans("window"):
        while True:
            closed = sent >= wl["trace_steps"] if ctx.trace else (
                time.perf_counter() - t0 >= ctx.seconds)
            if not closed:
                with spans("dispatch"):
                    out, cache = decode(params, cache, tok, np.int32(pos))
                out.copy_to_host_async()
                queue.append((turn, out))
                sent += 1
                contexts.append(pos + 1)
                pos, n, tok = pos + 1, n + 1, out
                if n == T:
                    turn, pos, n = turn + 1, P, 0
                    with spans("next_turn"):
                        tok = jnp.asarray(traffic.first(turn))
                if len(queue) <= ahead:
                    continue
            elif not queue:
                break
            read_turn, token = queue.popleft()
            with spans("sync"):
                host = np.asarray(token)
            now = time.perf_counter()
            token_gaps.append(now - last)
            last = now
            served.setdefault(read_turn, []).append(host)
    t1 = last
    trace = ctx.stop_trace(profiler)
    window_compiles = ctx.compiles.count - c0
    gc.unfreeze()
    peak = dev.memory_stats()["peak_bytes_in_use"] if dev.platform == "tpu" else 0
    setup_s = t0 - ctx.start
    steps = len(token_gaps)

    # Where no turn finished in the window, the rest of one is read untimed,
    # so a short window still has a request to check.
    while not any(len(v) == T for v in served.values()):
        tok, cache = decode(params, cache, tok, np.int32(pos))
        served.setdefault(turn, []).append(np.asarray(tok))
        pos, n = pos + 1, n + 1
    finished = [(t, np.concatenate(toks, axis=1)) for t, toks in served.items()
                if len(toks) == T]

    del params, cache, tok, out, token
    gc.collect()

    # A seeded sample of the finished requests (turn, row); all are turn_len long.
    pairs = [(t, r) for t, _ in finished for r in range(B)]
    rng = np.random.Generator(np.random.Philox(key=seed, counter=[0, 0, 0, 1]))
    pick = rng.choice(len(pairs), size=min(wl["check_requests"], len(pairs)), replace=False)
    by_turn = dict(finished)
    requests = [(served_sequence(traffic, t, r, by_turn[t][r]), by_turn[t][r])
                for t, r in (pairs[i] for i in sorted(pick))]
    t = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        ref_gaps = reference_gaps(cfg, wl, seed, requests)
    widest = [float(np.max(g)) for g in ref_gaps]
    bench.log(f"reference: {time.perf_counter() - t:.1f} s over {len(requests)} requests, "
              f"widest gaps {widest}")

    numbers = {"served_gap": max(widest)}
    if ctx.extra.get("readings"):
        numbers.update(readings(cfg, wl, seed, requests))

    mean_ctx = float(np.mean(contexts))
    mem_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    return {
        "attempted": len(finished) * B,
        "failed": None,
        "numbers": numbers,
        "per_request": widest,
        "window_s": t1 - t0,
        "window_compiles": window_compiles,
        "memory_peak_bytes": peak,
        "trace": trace,
        "e2e": {
            "decode_tokens_per_s": steps * B / (t1 - t0),
            "decode_step_p95_ms": float(np.quantile(token_gaps, 0.95)) * 1e3,
            "setup_s": setup_s,
            "peak_hbm_gib": mem_bytes / 2**30,
        },
        "obs": {
            "kind": "serve",
            "compile_s": compile_s,
            "tokens_per_s": steps * B / (t1 - t0),
            "steps": steps,
            "flops_per_token": kind.decode_flops_per_token(cfg, mean_ctx),
            "step_bytes": kind.decode_step_bytes(cfg, B, mean_ctx),
            "step_name": "serve_decode_step",
            "peaks": ctx.peaks,
            "trace": trace,
        },
        "memory": {
            "peak_bytes_in_use": peak,
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "host_temp_bytes": mem.host_temp_size_in_bytes,
            "footprint_bytes": mem_bytes,
        },
    }
