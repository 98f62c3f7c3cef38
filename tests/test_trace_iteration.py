"""RecordingDevice (paper §V), iteration detection, jaxpr lifetime tracer."""

import jax
import jax.numpy as jnp
import pytest

from repro.core.events import EventKind, build_trace
from repro.core.iteration import IterationDetector, detect_repeating_suffix
from repro.core.trace import RecordingDevice, trace_step_fn


def run_fake_iterations(dev, n_iters=3, n_blocks=5, size=1 << 20):
    for _ in range(n_iters):
        blocks = [dev.malloc(size * (i + 1)) for i in range(n_blocks)]
        for b in blocks:
            dev.exec(None, [b], [b])
        for b in blocks:
            dev.free(b)


def test_device_detects_iteration():
    dev = RecordingDevice()
    run_fake_iterations(dev)
    dev._detector.finalize()
    assert dev.iteration_detected
    # one iteration = n_blocks * (malloc + read + write + free)
    assert dev._detector.period == 5 * 4


def test_iteration_requires_malloc_and_free():
    # A pure read/write loop must NOT be detected as an iteration.
    sigs = [(int(EventKind.READ), 64), (int(EventKind.WRITE), 64)] * 20
    assert detect_repeating_suffix(sigs) is None


def test_detected_trace_has_lifetimes():
    dev = RecordingDevice()
    run_fake_iterations(dev, n_iters=4)
    tr = dev.iteration_trace()
    assert len(tr.variables) >= 5
    assert tr.peak_load() > 0


def test_jaxpr_tracer_mlp():
    def step(w1, w2, x):
        h = jnp.tanh(x @ w1)
        y = h @ w2
        return jnp.sum(y * y)

    w1 = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    w2 = jax.ShapeDtypeStruct((128, 32), jnp.float32)
    x = jax.ShapeDtypeStruct((16, 64), jnp.float32)
    tr = trace_step_fn(step, w1, w2, x)
    assert tr.peak_load() > 0
    # args are the first mallocs of the stream
    args = [v for v in tr.variables if v.alloc_index < 3]
    assert len(args) >= 3
    # every var's free is after its last access
    for v in tr.variables:
        if v.accesses:
            assert v.free_index >= max(v.accesses)


def test_jaxpr_tracer_scan_unroll():
    def step(carry, xs):
        def body(c, x):
            return c * x + 1.0, c
        return jax.lax.scan(body, carry, xs)

    c = jax.ShapeDtypeStruct((8,), jnp.float32)
    xs = jax.ShapeDtypeStruct((12, 8), jnp.float32)
    tr = trace_step_fn(step, c, xs, max_scan_unroll=12)
    # each trip mallocs fresh buffers: at least one var per trip
    assert len(tr.variables) >= 12


def test_jaxpr_tracer_grad_has_backward_phase():
    def loss(w, x):
        return jnp.sum(jnp.tanh(x @ w) ** 2)

    def step(w, x):
        return jax.grad(loss)(w, x)

    w = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 32), jnp.float32)
    tr = trace_step_fn(step, w, x)
    # load profile should rise then fall (residuals held for backward)
    curve = tr.load_curve()
    peak_at = curve.index(max(curve))
    assert 0 < peak_at < len(curve) - 1


def test_checkpoint_name_labels_survive():
    from jax.ad_checkpoint import checkpoint_name

    def step(w, x):
        def f(w):
            h = checkpoint_name(jnp.tanh(x @ w), "block_in")
            return jnp.sum(h * h)
        return jax.grad(jax.checkpoint(f, policy=None))(w)

    w = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 32), jnp.float32)
    tr = trace_step_fn(step, w, x)
    names = {v.name for v in tr.variables}
    assert "block_in" in names


@pytest.mark.parametrize("wrap", ["jit", "shard_map", "custom_jvp"])
def test_call_bodies_are_inlined(wrap):
    """Nested jit (jax.nn.silu), shard_map and custom-derivative bodies are
    walked: their temporaries become variables of their own, and no opaque
    call primitive is left standing in for them."""
    from functools import partial

    from jax.sharding import Mesh, PartitionSpec as P

    def inner(x):
        return jnp.tanh(x) * x + 1.0

    if wrap == "jit":
        f = jax.jit(inner)
    elif wrap == "shard_map":
        mesh = Mesh(jax.devices()[:1], ("data",))
        f = partial(jax.shard_map, mesh=mesh, in_specs=P("data"),
                    out_specs=P("data"), check_vma=False)(inner)
    else:
        f = jax.nn.silu

    x = jax.ShapeDtypeStruct((16, 32), jnp.float32)
    names = [v.name for v in trace_step_fn(lambda a: f(a).sum(), x).variables]
    assert not {"jit", "shard_map", "custom_jvp_call"} & set(names)
    assert len(names) >= 4  # the input, >=2 body temporaries, the sum
