"""Model state from the seed, on the device, in one jitted call.

`jax.eval_shape(module.init, ...)` gives the parameter tree without compiling
a forward pass only to make weights; `fill` then draws every leaf from
the seed. The rule, by the leaf's name (a departure from `module.init`, which
the speed does not depend on and the reference shares, since it is given the
same tree):

  kernel, w<degree>         normal / sqrt(fan_in), fan_in = shape[0]
  w3_* [mid, c_in*F, c_out] normal / sqrt(mid * c_in*F): both contracted axes.
                            Flax scales by mid alone, which multiplies the
                            features by ~25 in every convolution: the softmax
                            saturates and the function, so also its comparison
                            with any reference, is ill-conditioned (PERF.md)
  embedding                 normal / sqrt(features)
  scale*                    1 + 0.1 normal   (Flax: ones)
  bias, b3_*                0.1 normal       (Flax: zeros)

Scales and biases are perturbed so that the comparison with the reference
covers them: at exactly one and zero a wrong bias add would go unseen.
"""
import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed, n=2):
    """`n` uint32 words from any whole-number seed (the driver's are above
    2**31)."""
    return np.random.SeedSequence(int(seed)).generate_state(n)


def prng_key(seed, stream=0):
    """A JAX key from any whole-number seed."""
    hi, lo = (int(w) for w in seed_words(seed))
    return jax.random.fold_in(jax.random.PRNGKey(lo & 0x7fffffff),
                              (hi ^ stream) & 0x7fffffff)


def _leaf_name(path):
    k = path[-1]
    return str(getattr(k, 'key', k))


def make_fill(abstract):
    """abstract: a pytree of ShapeDtypeStructs. Returns jitted fill(key)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def fill(key):
        leaves = []
        for i, (path, sds) in enumerate(flat):
            name = _leaf_name(path)
            z = jax.random.normal(jax.random.fold_in(key, i), sds.shape,
                                  jnp.float32)
            if name.startswith('scale'):
                leaf = 1.0 + 0.1 * z
            elif name == 'bias' or name.startswith('b3_'):
                leaf = 0.1 * z
            elif name == 'embedding':
                leaf = z * sds.shape[-1] ** -0.5
            elif name.startswith('w3_'):
                leaf = z * (sds.shape[0] * sds.shape[1]) ** -0.5
            else:
                leaf = z * sds.shape[0] ** -0.5
            leaves.append(leaf.astype(sds.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    jitted = jax.jit(fill)
    # params - fill(key), without keeping a second copy of the weights
    jitted.delta = jax.jit(lambda p, key: jax.tree_util.tree_map(
        jnp.subtract, p, fill(key)))
    return jitted


def param_count(abstract):
    return sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(abstract))


@jax.jit
def _norms(leaves):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for a in leaves]


def leaf_norms(tree):
    """{path: L2 norm} over the leaves of a pytree, as Python floats."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = _norms([a for _, a in flat])
    return {'/'.join(_leaf_name((k,)) for k in path): float(x)
            for (path, _), x in zip(flat, norms)}
