"""Fused-op backend acceptance tests (repro.kernels.api).

Covers the api_redesign criteria:

  * for EVERY registered FusedOp: interpret-mode forward parity vs ``ref_fn``
    and ``jax.grad`` through the custom VJP vs ``jax.grad`` of the ref;
  * ``tree_apply`` issues exactly ONE kernel launch per fused op per step for
    a bucketed (homogeneous-dtype) tree — asserted via interpret-mode launch
    counting, including through the algorithms' ``local_update``/
    ``comm_update`` traces;
  * odd-length buffers stay on the kernel path (lane padding replaced the old
    ``while n % blk: blk //= 2`` halving loop) — regression for the
    mvr_update block-selection bug;
  * ``Simulator`` equivalence: ``use_fused=True`` matches the per-leaf jnp
    path for DSE-MVR and GT-HSGD (tolerance documented below), and all 8
    registered algorithms run fused end-to-end.

Fused-vs-jnp tolerance: both paths compute fp32 elementwise arithmetic; they
differ only in association order (e.g. fused ``x_ref - (params - gamma*v)``
vs per-leaf two-pass) so drift is O(ulp) per step.  Over the 12-round runs
here we assert rtol=5e-4 / atol=1e-5 and observe ~1e-8.
"""
import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ALGORITHMS, DSEMVR, Simulator, make_algorithm, ring
from repro.data import iid_partition, make_classification, partition_to_node_data
from repro.kernels import api

# interpret-mode parity targets: rtol/atol for fp32 (kernel computes fp32,
# the ref computes fp32 — differences are pure reassociation)
TOL = dict(rtol=1e-5, atol=1e-6)

# per-op scalar operands; unlisted ops get 0.1 per scalar slot so newly
# registered ops are swept without editing this file
_SCALAR_OVERRIDES = {"axpby": (-0.3, 1.0)}


def _scalars_for(name):
    return _SCALAR_OVERRIDES.get(name, (0.1,) * api.get(name).n_scalars)


def _inputs(op, key, shapes):
    """One random tree per op input, leaves of the given shapes."""
    trees = []
    for t in range(op.n_inputs):
        k = jax.random.fold_in(key, t)
        trees.append(
            {
                f"leaf{i}": jax.random.normal(jax.random.fold_in(k, i), shp)
                for i, shp in enumerate(shapes)
            }
        )
    return trees


def _elementwise_ops():
    return sorted(n for n, op in api.REGISTRY.items() if op.elementwise)


def _ref_tree(op, trees, scalars):
    """Per-leaf oracle application (the pre-redesign execution shape)."""
    outs = jax.tree.map(
        lambda *leaves: op.ref_fn(*leaves, *scalars), *trees
    )
    if op.n_outputs == 1:
        return (outs,)
    # unzip the per-leaf tuples into n_outputs trees
    return tuple(
        jax.tree.map(lambda o, j=j: o[j], outs, is_leaf=lambda x: isinstance(x, tuple))
        for j in range(op.n_outputs)
    )


# ------------------------------------------------------------- registry sweep
@pytest.mark.parametrize("name", _elementwise_ops())
@pytest.mark.parametrize(
    "shapes",
    [
        [(128,), (512,)],          # lane-aligned leaves
        [(3, 7), (1000,), ()],     # odd sizes + scalar leaf -> padding path
    ],
)
def test_elementwise_interpret_matches_ref(name, shapes):
    op = api.get(name)
    trees = _inputs(op, jax.random.key(zlib.crc32(name.encode())), shapes)
    scalars = _scalars_for(name)
    with api.dispatch_mode("interpret"):
        got = api.tree_apply(name, *trees, scalars=scalars)
    if op.n_outputs == 1:
        got = (got,)
    want = _ref_tree(op, trees, scalars)
    for g_tree, w_tree in zip(got, want):
        for g, w in zip(jax.tree.leaves(g_tree), jax.tree.leaves(w_tree)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), **TOL)


@pytest.mark.parametrize("name", _elementwise_ops())
def test_elementwise_grad_matches_ref(name):
    """jax.grad through the interpret-mode custom VJP == jax.grad of the ref,
    for every tensor input AND the scalar operands."""
    op = api.get(name)
    trees = _inputs(op, jax.random.key(7), [(96,), (5, 5)])
    scalars = tuple(jnp.asarray(s, jnp.float32) for s in _scalars_for(name))

    def loss_fused(trees, scalars):
        with api.dispatch_mode("interpret"):
            out = api.tree_apply(name, *trees, scalars=scalars)
        outs = out if isinstance(out, tuple) else (out,)
        return sum(jnp.sum(l**2) for t in outs for l in jax.tree.leaves(t))

    def loss_ref(trees, scalars):
        outs = _ref_tree(op, trees, scalars)
        return sum(jnp.sum(l**2) for t in outs for l in jax.tree.leaves(t))

    g1 = jax.grad(loss_fused, argnums=(0, 1))(tuple(trees), scalars)
    g2 = jax.grad(loss_ref, argnums=(0, 1))(tuple(trees), scalars)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_shaped_ops_registered_and_dispatch():
    """Every shaped op dispatches through api.call with ref parity (the deep
    shape/dtype sweeps live in test_kernels.py)."""
    key = jax.random.key(3)
    q = jax.random.normal(key, (1, 128, 2, 64))
    x = jax.random.normal(key, (6, 64))
    w = jax.random.normal(jax.random.fold_in(key, 1), (64,))
    r = jax.random.normal(key, (1, 32, 1, 16)) * 0.5
    logw = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (1, 32, 1, 16)) * 0.3)
    xs = jax.random.normal(jax.random.fold_in(key, 3), (4, 300))
    idx = jax.random.randint(jax.random.fold_in(key, 4), (4, 9), 0, 300).astype(jnp.int32)
    vals = jax.random.normal(jax.random.fold_in(key, 5), (4, 9))
    cases = {
        "flash_attention": ((q, q, q), dict(causal=True)),
        "rms_norm": ((x, w), dict(eps=1e-6, plus_one=False)),
        "wkv_chunk": ((r, r, r, logw), dict(chunk=16)),
        "top_k_pack": ((xs, idx), {}),
        "top_k_unpack": ((idx, vals), dict(d=300)),
    }
    shaped = {n for n, op in api.REGISTRY.items() if not op.elementwise}
    assert shaped == set(cases), shaped
    for name, (args, static) in cases.items():
        op = api.get(name)
        with api.dispatch_mode("interpret"):
            got = api.call(name, *args, **static)
        want = op.ref_fn(*args, **static)
        for g, w_ in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w_), rtol=2e-4, atol=2e-5
            )


# -------------------------------------------------------------- tile policy
def test_tile_policy_pads_to_lane_multiple():
    tp = api.TilePolicy()
    for n in (1, 7, 127, 128, 129, 1000003):
        block, n_pad = tp.plan(n)
        assert block % tp.lane == 0
        assert n_pad % block == 0 and n_pad >= n
        assert n_pad - n < block  # padding never exceeds one block
    # above max_block the block stays full width
    block, n_pad = tp.plan((1 << 16) + 1)
    assert block == 1 << 16 and n_pad == 2 << 16


def test_tile_policy_groups_bound_each_launch():
    tp = api.TilePolicy(max_bucket=100)
    assert tp.groups([30, 30, 30]) == [[0, 1, 2]]
    assert tp.groups([60, 50, 10, 250, 5]) == [[0], [1, 2], [3], [4]]
    assert tp.groups([0, 0]) == [[0, 1]]


@pytest.mark.parametrize("n", [12345, 2 * (1 << 16) + 384])
def test_mvr_update_odd_buffer_stays_on_kernel_path(n):
    """Regression (block-selection satellite): an odd-length buffer used to
    degrade to 1-element blocks and the oracle fallback; now it is padded to
    a lane multiple and takes ONE kernel launch.  The second size spans
    several full blocks and a padded last one."""
    ks = jax.random.split(jax.random.key(n), 3)
    gn, v, go = (jax.random.normal(k, (n,)) for k in ks)
    api.reset_counters()
    with api.dispatch_mode("interpret"):
        out = api.tree_apply("mvr_update", gn, v, go, scalars=(0.05,))
    assert api.launch_counts() == {"mvr_update": 1}
    from repro.kernels.mvr_update.ref import mvr_update_ref

    np.testing.assert_allclose(
        np.asarray(out), np.asarray(mvr_update_ref(gn, v, go, 0.05)), **TOL
    )


def test_legacy_entry_points_warn_and_match():
    ks = jax.random.split(jax.random.key(0), 3)
    gn, v, go = (jax.random.normal(k, (300,)) for k in ks)
    from repro.kernels.mvr_update import mvr_update, mvr_update_ref, mvr_update_tree

    with pytest.warns(DeprecationWarning):
        out = mvr_update(gn, v, go, 0.1)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(mvr_update_ref(gn, v, go, 0.1)), **TOL
    )
    with pytest.warns(DeprecationWarning):
        tree_out = mvr_update_tree({"a": gn}, {"a": v}, {"a": go}, 0.1)
    np.testing.assert_allclose(np.asarray(tree_out["a"]), np.asarray(out), **TOL)


# ----------------------------------------------------------- launch counting
def test_tree_apply_single_launch_per_bucket():
    key = jax.random.key(1)
    mk = lambda k, dt: {  # noqa: E731
        f"l{i}": jax.random.normal(jax.random.fold_in(k, i), shp).astype(dt)
        for i, shp in enumerate([(64,), (3, 5), (200,), (8, 8, 8), ()])
    }
    # homogeneous dtype: 5 leaves -> ONE launch
    trees = [mk(jax.random.fold_in(key, t), jnp.float32) for t in range(3)]
    api.reset_counters()
    with api.dispatch_mode("interpret"):
        api.tree_apply("add_sub", *trees)
    assert api.launch_counts() == {"add_sub": 1}

    # mixed dtypes: one launch per dtype bucket
    trees_f32 = [mk(jax.random.fold_in(key, t), jnp.float32) for t in range(3)]
    trees_mixed = [
        {**t, "bf": jnp.ones((77,), jnp.bfloat16)} for t in trees_f32
    ]
    api.reset_counters()
    with api.dispatch_mode("interpret"):
        api.tree_apply("add_sub", *trees_mixed)
    assert api.launch_counts() == {"add_sub": 2}


def test_algorithm_step_launches_one_kernel_per_fused_op():
    """Acceptance: tracing one DSE-MVR local step / communication round with
    use_fused=True dispatches exactly one bucketed launch per fused op, not
    one per parameter leaf."""
    alg = DSEMVR(lr=0.1, alpha=0.1, tau=4, use_fused=True)
    params = {
        "w1": jnp.ones((13, 7)), "b1": jnp.ones((7,)),
        "w2": jnp.ones((7, 3)), "b2": jnp.ones((3,)),
    }
    state = alg.init(params)
    grad_fn = lambda p: jax.tree.map(jnp.ones_like, p)  # noqa: E731
    mix_fn = lambda t: t  # noqa: E731

    api.reset_counters()
    with api.dispatch_mode("interpret"):
        jax.make_jaxpr(lambda s: alg.local_update(s, grad_fn))(state)
    # x step (axpby) + MVR direction update: one launch each for the 4-leaf tree
    assert api.launch_counts() == {"axpby": 1, "mvr_update": 1}

    alg_z = dataclasses.replace(alg, fuse_tracking_buffers=True)
    state_z = alg_z.init(params)
    api.reset_counters()
    with api.dispatch_mode("interpret"):
        jax.make_jaxpr(
            lambda s: alg_z.comm_update(s, mix_fn, grad_fn, grad_fn)
        )(state_z)
    # dual-slow combine once; axpby twice (z refresh + post-mix SPA)
    assert api.launch_counts() == {"dse_combine": 1, "axpby": 2}


# ------------------------------------------------------ simulator equivalence
N_NODES = 4
DIM, CLASSES = 8, 3


def _problem(seed=0):
    x, y = make_classification(400, DIM, CLASSES, seed=seed, class_sep=2.0)
    parts = iid_partition(len(x), N_NODES, seed=seed)
    return partition_to_node_data(x, y, parts)


def _loss(params, batch):
    xb, yb = batch
    logits = xb @ params["w"] + params["b"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, yb[..., None], axis=-1).mean()


def _params():
    return {"w": jnp.zeros((DIM, CLASSES)), "b": jnp.zeros(CLASSES)}


def _run(alg, steps=12):
    sim = Simulator(alg, ring(N_NODES), _loss, _problem(), batch_size=16)
    return sim.run(_params(), jax.random.key(0), num_steps=steps)["state"]


@pytest.mark.parametrize("name", ["dse_mvr", "gt_hsgd"])
@pytest.mark.parametrize("fuse_tracking", [False, True])
def test_simulator_fused_matches_jnp(name, fuse_tracking):
    """use_fused=True must reproduce the per-leaf jnp path through whole
    Simulator runs (12 steps, tau=4 rounds for DSE-MVR; every-step GT-HSGD).
    Tolerance: rtol=5e-4/atol=1e-5 (documented header); observed ~1e-8."""
    kw = dict(lr=0.1, alpha=0.1, beta=0.5, tau=4, fuse_tracking_buffers=fuse_tracking)
    ref = _run(make_algorithm(name, **kw, use_fused=False))
    got = _run(make_algorithm(name, **kw, use_fused=True))
    for a, b in zip(jax.tree.leaves(ref.params), jax.tree.leaves(got.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-5
        )


def test_all_algorithms_run_fused():
    """Every entry in ALGORITHMS runs through the Simulator with
    use_fused=True and stays finite (the sharded-engine counterpart lives in
    test_distributed.py::test_train_job_builds_for_every_algorithm)."""
    for name in sorted(ALGORITHMS):
        alg = make_algorithm(
            name, lr=0.1, alpha=0.1, beta=0.5, tau=2, use_fused=True
        )
        state = _run(alg, steps=6)
        for leaf in jax.tree.leaves(state.params):
            assert np.all(np.isfinite(np.asarray(leaf))), name
