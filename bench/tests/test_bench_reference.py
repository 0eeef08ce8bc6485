"""The float32 reference against the program at a small size on the CPU,
where the program computes in float32 too: the model's loss and gradient
(the ``llama`` architecture module, found through the cell's loader), the
fp8 control's departure from it, and DSE-MVR rounds over a ring of 4
through the program's round executor."""
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


@pytest.fixture(scope="module")
def llama():
    """The architecture module that yi-9b's configuration names."""
    return harness.load_cell("yi9b.s2k.tau4").arch


def _yi_small():
    return dict(harness.load_json(harness.ROOT / "bench/configs/yi-9b.json")["model"], **SMALL)


def _batch(m, seq=32, nodes=1, seed=3):
    tr = {"seq_len": seq, "tau": 4, "node_batch": 2, "pool_rounds": 1,
          "tokens": {"zipf": 1.3, "order": 2, "branch": 8, "contexts": 4096, "non_iid": nodes > 1}}
    return tr, Traffic.make(tr, m, nodes, seed)


def _perturbed(arch, m, seed):
    p = weights.make_params(arch.param_shapes(m), jax.random.key(seed))
    return jax.tree.map(lambda a: a + 0.02 * jax.random.normal(jax.random.key(seed + 1), a.shape), p)


def _loss_and_grads(llama, quant=None):
    """``(program, reference)``, each ``(loss, gradient)`` of one batch at
    float32; the reference's matmuls rounded as ``quant`` says."""
    from repro.models import Model

    m = _yi_small()
    _, feed = _batch(m)
    b = jax.tree.map(lambda a: jnp.asarray(a[0, 0]), feed.round(0))
    p = _perturbed(llama, m, 7)
    model = Model(harness.model_config(m))
    mm = reference.matmul(quant)
    with jax.default_matmul_precision("highest"):
        prog = jax.value_and_grad(lambda q: model.loss(q, b, dtype=jnp.float32))(p)
        ref = jax.value_and_grad(lambda q: llama.loss_fn(m, q, b, mm))(p)
    return prog, ref


def test_loss_and_gradient_match_the_program_in_float32(llama):
    (lp, gp), (lr, gr) = _loss_and_grads(llama)
    assert abs(float(lp) - float(lr)) <= 1e-5 * abs(float(lr))
    for path, g in reference.flat(gr).items():
        scale = float(jnp.max(jnp.abs(g)))
        np.testing.assert_allclose(reference.flat(gp)[path], g, rtol=2e-4, atol=1e-5 * scale,
                                   err_msg=path)


def test_fp8_control_departs_from_the_float32_reference(llama):
    """The control's loss and gradient lie outside the tolerances that the
    program meets in float32 (the test above)."""
    (lp, gp), (lc, gc) = _loss_and_grads(llama, quant="fp8")
    assert abs(float(lc) - float(lp)) > 1e-5 * abs(float(lp))
    off = [path for path, g in reference.flat(gp).items()
           if not np.allclose(reference.flat(gc)[path], g, rtol=2e-4,
                              atol=1e-5 * float(jnp.max(jnp.abs(g))))]
    assert len(off) == len(reference.flat(gp)), off


@pytest.mark.parametrize("other", [dict(use_bias=True), dict(tie_embeddings=True),
                                   dict(n_vision_tokens=16), dict(mrope_sections=[2, 3, 3])],
                         ids=["bias", "tied-head", "vision-prefix", "mrope"])
def test_an_uncovered_architecture_is_refused(llama, other):
    """The llama module writes out an untied text decoder only: a
    configuration with biases, a tied head or a vision prefix fails at once
    instead of being compared against the wrong model."""
    with pytest.raises(ValueError, match="do not cover"):
        llama.param_shapes(dict(_yi_small(), **other))


def test_dse_mvr_ring_rounds_match_the_program_executor(llama):
    """Reference rounds (paper form, y and h_prev apart) against the
    program's executor (fused z buffer, roll gossip), both on the
    reference's float32 gradient."""
    from repro.core import make_algorithm, ring
    from repro.core.algorithm import make_round_step
    from repro.core.mixing import roll_mix

    m, nodes = _yi_small(), 4
    tr, feed = _batch(m, nodes=nodes)
    tr.update(lr=0.05, alpha=0.3)
    x0 = _perturbed(llama, m, 11)
    rounds = [feed.round(0), feed.round(0)]
    mm = reference.matmul()

    def grad(p, bt):
        return jax.vmap(jax.grad(lambda q, b: llama.loss_fn(m, q, b, mm)))(p, bt)

    alg = make_algorithm("dse_mvr", lr=tr["lr"], alpha=tr["alpha"], tau=4, fuse_tracking_buffers=True)
    step, _ = make_round_step(alg, roll_mix(ring(nodes)), grad_of_batch=grad)
    with jax.default_matmul_precision("highest"):
        st = alg.init(jax.tree.map(lambda a: jnp.broadcast_to(a, (nodes,) + a.shape), x0))
        for rb in rounds:
            st = jax.jit(step)(st, jax.tree.map(jnp.asarray, rb))
        want = reference.Reference(llama, m, tr, nodes, jax.devices()[:1]).run(x0, rounds)
    got = {p: np.asarray(v) for p, v in reference.leaf_norms(
        jax.tree.map(lambda a, b: a - b[None], st.params, x0)).items()}
    for path, v in want["delta"].items():
        np.testing.assert_allclose(got[path], v, rtol=1e-4, err_msg=path)
    moved = min(float(np.min(v)) for v in want["delta"].values())
    assert moved > 0
