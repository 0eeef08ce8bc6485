"""The float32 reference against the program at a small size on the CPU,
where the program computes in float32 too: the model's loss and gradient,
and DSE-MVR rounds over a ring of 4 through the program's round executor."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import reference
import weights
from traffic import Traffic

SMALL = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
             vocab_size=256)


def _yi_small():
    return dict(harness.load_json(harness.ROOT / "bench/configs/yi-9b.json")["model"], **SMALL)


def _batch(m, seq=32, nodes=1, seed=3):
    tr = {"seq_len": seq, "tau": 4, "node_batch": 2, "pool_rounds": 1,
          "tokens": {"zipf": 1.3, "order": 2, "branch": 8, "contexts": 4096, "non_iid": nodes > 1}}
    return tr, Traffic.make(tr, m, nodes, seed)


def _perturbed(m, seed):
    p = weights.make_params(m, jax.random.key(seed))
    return jax.tree.map(lambda a: a + 0.02 * jax.random.normal(jax.random.key(seed + 1), a.shape), p)


def test_loss_and_gradient_match_the_program_in_float32():
    from repro.models import Model

    m = _yi_small()
    _, feed = _batch(m)
    b = jax.tree.map(lambda a: jnp.asarray(a[0, 0]), feed.round(0))
    p = _perturbed(m, 7)
    model = Model(harness.model_config(m))
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(lambda q: model.loss(q, b, dtype=jnp.float32))(p)
        lr, gr = jax.value_and_grad(lambda q: reference.loss_fn(m, q, b))(p)
    assert abs(float(lp) - float(lr)) <= 1e-5 * abs(float(lr))
    for path, g in reference.flat(gr).items():
        scale = float(jnp.max(jnp.abs(g)))
        np.testing.assert_allclose(reference.flat(gp)[path], g, rtol=2e-4, atol=1e-5 * scale,
                                   err_msg=path)


@pytest.mark.parametrize("other", [dict(use_bias=True), dict(tie_embeddings=True),
                                   dict(n_vision_tokens=16), dict(mrope_sections=[2, 3, 3])],
                         ids=["bias", "tied-head", "vision-prefix", "mrope"])
def test_an_uncovered_architecture_is_refused(other):
    """The weights and reference write out an untied text decoder only: a
    configuration with biases, a tied head or a vision prefix fails at once
    instead of being compared against the wrong model."""
    with pytest.raises(ValueError, match="do not cover"):
        weights.param_shapes(dict(_yi_small(), **other))


def test_dse_mvr_ring_rounds_match_the_program_executor():
    """Reference rounds (paper form, y and h_prev apart) against the
    program's executor (fused z buffer, roll gossip), both on the
    reference's float32 gradient."""
    from repro.core import make_algorithm, ring
    from repro.core.algorithm import make_round_step
    from repro.core.mixing import roll_mix

    m, nodes = _yi_small(), 4
    tr, feed = _batch(m, nodes=nodes)
    tr.update(lr=0.05, alpha=0.3)
    x0 = _perturbed(m, 11)
    rounds = [feed.round(0), feed.round(0)]

    def grad(p, bt):
        return jax.vmap(jax.grad(lambda q, b: reference.loss_fn(m, q, b)))(p, bt)

    alg = make_algorithm("dse_mvr", lr=tr["lr"], alpha=tr["alpha"], tau=4, fuse_tracking_buffers=True)
    step, _ = make_round_step(alg, roll_mix(ring(nodes)), grad_of_batch=grad)
    with jax.default_matmul_precision("highest"):
        st = alg.init(jax.tree.map(lambda a: jnp.broadcast_to(a, (nodes,) + a.shape), x0))
        for rb in rounds:
            st = jax.jit(step)(st, jax.tree.map(jnp.asarray, rb))
        want = reference.Reference(m, tr, nodes, jax.devices()[:1]).run(x0, rounds)
    got = {p: np.asarray(v) for p, v in reference.leaf_norms(
        jax.tree.map(lambda a, b: a - b[None], st.params, x0)).items()}
    for path, v in want["delta"].items():
        np.testing.assert_allclose(got[path], v, rtol=1e-4, err_msg=path)
    moved = min(float(np.min(v)) for v in want["delta"].values())
    assert moved > 0
