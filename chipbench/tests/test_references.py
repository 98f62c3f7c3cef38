"""The plain references against the program at smoke size, on seeded weights,
and the SSD reference against the recurrence it evaluates."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights
from chipbench.reference import mamba2 as ref_mamba2
from chipbench.reference import qwen3 as ref_qwen3
from chipbench.tests import cells

# The smoke configurations compute in float32, like the references, so the
# two agree to float32 rounding of reductions taken in a different order.
TOL = 2e-5


def _model(cfg):
    from repro.models import build_model

    return build_model(cells.smoke_program_config(cfg))


def test_weights_fit_the_programs_layout():
    for cfg in (cells.MAMBA2, cells.QWEN3):
        params = jax.eval_shape(lambda: weights.init(cfg, weights.seed_key(1)))
        assert weights.layout_mismatch(params, _model(cfg).init_shapes()) is None


def test_seed_key_takes_seeds_past_32_bits():
    a, b = weights.seed_key(2**31 + 5), weights.seed_key(5)
    assert not np.array_equal(jax.random.key_data(a), jax.random.key_data(b))
    assert np.array_equal(jax.random.key_data(weights.seed_key(2**33 + 7)),
                          jax.random.key_data(weights.seed_key(2**33 + 7)))


def test_mamba2_loss_and_gradients_match_the_program():
    cfg = cells.MAMBA2
    model = _model(cfg)
    params = weights.make(cfg, 5)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, cfg["vocab_size"], (4, 64)), jnp.int32)
    (loss, _), grads = jax.value_and_grad(
        lambda p: model.loss(p, {"tokens": ids, "labels": ids}, remat=False), has_aux=True)(params)
    want_loss, want_grads = ref_mamba2.loss_and_grad(params, ids, cfg, rows=2)
    assert abs(float(loss) - float(want_loss)) <= TOL * abs(float(want_loss))
    for (name, g), (_, w) in zip(weights.leaf_slices(grads), weights.leaf_slices(want_grads)):
        err = float(jnp.linalg.norm(g - w)) / max(float(jnp.linalg.norm(w)), 1e-30)
        assert err <= 1e-4, name


def test_ssd_reference_is_the_recurrence():
    b, s, h, p, g, n, chunk = 2, 32, 4, 8, 1, 6, 8
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(k[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(k[2], (h,)) * 0.5)
    B = jax.random.normal(k[3], (b, s, g, n))
    C = jax.random.normal(k[4], (b, s, g, n))
    y = ref_mamba2.ssd(x, dt, A, B, C, chunk, ref_mamba2.REFERENCE)

    state = np.zeros((b, h, p, n))
    want = np.zeros((b, s, h, p))
    xn, dtn, An, Bn, Cn = (np.asarray(t, np.float64) for t in (x, dt, A, B, C))
    for t in range(s):
        decay = np.exp(dtn[:, t] * An)[:, :, None, None]
        state = state * decay + np.einsum("bhp,bn,bh->bhpn", xn[:, t], Bn[:, t, 0], dtn[:, t])
        want[:, t] = np.einsum("bhpn,bn->bhp", state, Cn[:, t, 0])
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-4)


def test_qwen3_prefill_and_decode_match_the_reference():
    cfg = cells.QWEN3
    model = _model(cfg)
    params = weights.make(cfg, 7)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, cfg["vocab_size"], (2, 40)), jnp.int32)
    P = 32
    last, cache = model.prefill(params, {"tokens": ids[:, :P]}, max_seq=40)
    steps = []
    for t in range(P, 40):
        logits, cache = model.decode_step(params, cache, ids[:, t:t + 1], jnp.asarray(t, jnp.int32))
        steps.append(logits[:, 0])
    decoded = jnp.stack(steps, axis=1)
    for row in range(2):
        want = ref_qwen3.logits(params, ids[row], P, cfg)
        scale = float(jnp.max(jnp.abs(want)))
        assert float(jnp.max(jnp.abs(decoded[row] - want))) <= TOL * scale
        want_last = ref_qwen3.logits(params, ids[row, :P], P - 1, cfg)[0]
        assert float(jnp.max(jnp.abs(last[row, 0] - want_last))) <= TOL * scale


@pytest.mark.parametrize("s", [40, 48, 1280])
def test_qwen3_query_blocks_divide_the_sequence(s):
    qb = ref_qwen3._q_block(s)
    assert s % qb == 0 and qb <= 512
