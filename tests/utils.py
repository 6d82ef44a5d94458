"""Shared test helpers (parity: reference test TestUtils gradient checks)."""
import jax
import jax.numpy as jnp
import numpy as np


def check_gradient(module, x, eps=1e-3, tol=2e-2, seed=0):
    """Finite-difference vs vjp gradient check for input gradient and
    parameter gradients (parity: TestUtils.checkEstimateGradient)."""
    module.ensure_initialized()
    module.evaluate()  # deterministic
    rng = np.random.RandomState(seed)
    x = jnp.asarray(x, jnp.float32)

    def scalar_loss(params, inp):
        out, _ = module.apply(params, module.state, inp, training=False)
        leaves = jax.tree_util.tree_leaves(out)
        return sum(jnp.sum(jnp.sin(l)) for l in leaves)

    g_params, g_in = jax.grad(scalar_loss, argnums=(0, 1))(module.params, x)

    # input grad check at a few random positions
    xf = np.asarray(x, np.float64).reshape(-1)
    gf = np.asarray(g_in).reshape(-1)
    idxs = rng.choice(xf.size, size=min(8, xf.size), replace=False)
    for i in idxs:
        xp, xm = xf.copy(), xf.copy()
        xp[i] += eps
        xm[i] -= eps
        fp = float(scalar_loss(module.params,
                               jnp.asarray(xp.reshape(x.shape), jnp.float32)))
        fm = float(scalar_loss(module.params,
                               jnp.asarray(xm.reshape(x.shape), jnp.float32)))
        num = (fp - fm) / (2 * eps)
        assert abs(num - gf[i]) < tol * max(1.0, abs(num)), \
            f"input grad mismatch at {i}: fd={num} ad={gf[i]}"

    # parameter grad check on one leaf
    leaves, treedef = jax.tree_util.tree_flatten(module.params)
    if leaves:
        gleaves = jax.tree_util.tree_leaves(g_params)
        li = rng.randint(len(leaves))
        pf = np.asarray(leaves[li], np.float64).reshape(-1)
        pg = np.asarray(gleaves[li]).reshape(-1)
        for i in rng.choice(pf.size, size=min(4, pf.size), replace=False):
            pp, pm = pf.copy(), pf.copy()
            pp[i] += eps
            pm[i] -= eps

            def with_leaf(vals):
                new = list(leaves)
                new[li] = jnp.asarray(vals.reshape(leaves[li].shape),
                                      jnp.float32)
                return jax.tree_util.tree_unflatten(treedef, new)
            fp = float(scalar_loss(with_leaf(pp), x))
            fm = float(scalar_loss(with_leaf(pm), x))
            num = (fp - fm) / (2 * eps)
            assert abs(num - pg[i]) < tol * max(1.0, abs(num)), \
                f"param grad mismatch leaf {li} idx {i}: fd={num} ad={pg[i]}"
    return True


def allclose(a, b, tol=1e-5):
    return np.allclose(np.asarray(a), np.asarray(b), atol=tol, rtol=tol)


def jaxpr_equations(jaxpr, closed=()):
    """Every equation of a jaxpr and of the jaxprs its equations hold
    (``remat``, ``custom_vjp_call``, ``pjit``, ``shard_map``); the bodies of
    the primitives named in ``closed`` (a ``pallas_call``'s kernel) are
    not entered."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name in closed:
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from jaxpr_equations(sub, closed)
