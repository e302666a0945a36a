"""Cookbook: every usage pattern of the reference README, in JAX.

Each section mirrors a snippet from /root/reference/README.md (cited by
line) so a user of the reference can switch 1:1. Run end-to-end with:

    python examples/cookbook.py [--cpu]    # tiny shapes, default backend

All examples use the eager `SE3Transformer` wrapper (lazy seeded init,
jitted apply). For training-scale use the functional
`SE3TransformerModule` + your own jit/pjit (see denoise.py and
se3_transformer_tpu/training).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# examples run on the default backend; --cpu forces the CPU (a sandbox)
if '--cpu' in sys.argv:
    jax.config.update('jax_platforms', 'cpu')

import jax.numpy as jnp
import numpy as np

from se3_transformer_tpu import SE3Transformer
from se3_transformer_tpu.utils import fourier_encode

rng = np.random.RandomState(0)
R = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)


def basic_usage():
    """README.md:19-63 — continuous type-0 features."""
    model = SE3Transformer(dim=16, heads=2, depth=1, dim_head=8,
                           num_degrees=2, valid_radius=10)
    feats = R(1, 32, 16)
    coors = R(1, 32, 3)
    mask = jnp.ones((1, 32), bool)
    out = model(feats, coors, mask, return_type=0)
    assert out.shape == (1, 32, 16)


def token_embedding():
    """README.md:64-86 — atom-token embedding handled by the model."""
    model = SE3Transformer(num_tokens=28, dim=16, heads=2, depth=1,
                           num_degrees=2, num_neighbors=4)
    atoms = jnp.asarray(rng.randint(0, 28, (1, 32)))
    coors = R(1, 32, 3)
    mask = jnp.ones((1, 32), bool)
    out = model(atoms, coors, mask, return_type=0)
    assert out.shape == (1, 32, 16)


def type1_inputs_coord_refinement():
    """README.md:88-111 — atoms type 0, predicted coordinates type 1
    (AlphaFold2-style refinement)."""
    model = SE3Transformer(dim=16, heads=2, depth=1, input_degrees=2,
                           num_degrees=2, output_degrees=2,
                           reduce_dim_out=True, differentiable_coors=True,
                           num_neighbors=4)
    atom_feats = R(1, 32, 16, 1)
    pred_coors = R(1, 32, 16, 3)
    coors = R(1, 32, 3)
    mask = jnp.ones((1, 32), bool)
    refinement = model({'0': atom_feats, '1': pred_coors}, coors, mask,
                       return_type=1)
    refined = coors + refinement
    assert refined.shape == (1, 32, 3)


def edge_tokens():
    """README.md:113-170 — discrete bond types + continuous edge feats."""
    model = SE3Transformer(dim=16, depth=1, num_degrees=2, num_neighbors=4,
                           edge_dim=4, num_edge_tokens=4)
    feats = R(1, 16, 16)
    bonds = jnp.asarray(rng.randint(0, 4, (1, 16, 16)))
    coors = R(1, 16, 3)
    mask = jnp.ones((1, 16), bool)
    out = model(feats, coors, mask, edges=bonds, return_type=0)

    # continuous pairwise scalars -> fourier features (README.md:141-169)
    model2 = SE3Transformer(dim=16, depth=1, num_degrees=2, output_degrees=2,
                            attend_self=True, edge_dim=34, num_neighbors=4)
    pairwise = jnp.asarray(rng.randint(0, 4, (1, 16, 16, 2)), jnp.float32)
    edges = fourier_encode(pairwise, num_encodings=8, include_self=True)
    out2 = model2(feats, coors, mask, edges=edges, return_type=1)
    assert out2.shape == (1, 16, 16, 3)


def sparse_neighbors():
    """README.md:172-265 — attend only along bonds (+ Nth-degree rings)."""
    model = SE3Transformer(dim=16, depth=1, attend_self=True,
                           num_degrees=2, output_degrees=2, num_neighbors=0,
                           attend_sparse_neighbors=True, num_adj_degrees=2,
                           adj_dim=4, max_sparse_neighbors=8)
    feats = R(1, 32, 16)
    coors = R(1, 32, 3)
    mask = jnp.ones((1, 32), bool)
    i = np.arange(32)
    adj_mat = jnp.asarray(np.abs(i[:, None] - i[None, :]) == 1)
    out = model(feats, coors, mask, adj_mat=adj_mat, return_type=0)
    assert out.shape == (1, 32, 16)


def neighbor_mask():
    """README.md:267-302 — mask out nodes from neighbor consideration."""
    model = SE3Transformer(dim=16, depth=1, attend_self=True, num_degrees=2,
                           output_degrees=2, num_neighbors=5)
    feats = R(1, 16, 16)
    coors = R(1, 16, 3)
    mask = jnp.ones((1, 16), bool)
    nb_mask = jnp.asarray(rng.rand(1, 16, 16) > 0.2)
    out = model(feats, coors, mask, neighbor_mask=nb_mask, return_type=0)
    assert out.shape == (1, 16, 16)


def global_nodes():
    """README.md:304-335 — global feature nodes attended by every node."""
    model = SE3Transformer(dim=16, depth=1, num_degrees=2, num_neighbors=4,
                           global_feats_dim=8)
    feats = R(1, 16, 16)
    coors = R(1, 16, 3)
    mask = jnp.ones((1, 16), bool)
    global_feats = R(1, 2, 8)
    out = model(feats, coors, mask, return_type=0, global_feats=global_feats)
    assert out.shape == (1, 16, 16)


def autoregressive():
    """README.md:337-360 — causal attention (past nodes only)."""
    model = SE3Transformer(dim=16, depth=1, num_degrees=2, num_neighbors=4,
                           causal=True, attend_self=True)
    feats = R(1, 16, 16)
    coors = R(1, 16, 3)
    mask = jnp.ones((1, 16), bool)
    out = model(feats, coors, mask, return_type=0)
    assert out.shape == (1, 16, 16)


def memory_lean_attention_variants():
    """README.md:362-437 — linear-projected keys / one-headed kv / tied kv."""
    for kwargs in (dict(linear_proj_keys=True),
                   dict(one_headed_key_values=True),
                   dict(tie_key_values=True)):
        model = SE3Transformer(dim=16, depth=1, num_degrees=2,
                               num_neighbors=4, attend_self=True, **kwargs)
        out = model(R(1, 16, 16), R(1, 16, 3), jnp.ones((1, 16), bool),
                    return_type=0)
        assert out.shape == (1, 16, 16)


def egnn_backbone():
    """README.md:439-493 — EGNN layers for scaling depth/degrees."""
    model = SE3Transformer(dim=16, depth=2, num_degrees=2, num_neighbors=4,
                           use_egnn=True, egnn_hidden_dim=16,
                           egnn_weights_clamp_value=2.0, egnn_feedforward=True)
    out = model(R(1, 16, 16), R(1, 16, 3), jnp.ones((1, 16), bool),
                return_type=1)
    assert out.shape == (1, 16, 16, 3)


def scaling_reversible():
    """README.md:495-526 — reversible networks -> rematerialized blocks."""
    model = SE3Transformer(dim=16, depth=3, num_degrees=2, num_neighbors=4,
                           attend_self=True, reversible=True)
    out = model(R(1, 16, 16), R(1, 16, 3), jnp.ones((1, 16), bool),
                return_type=0)
    assert out.shape == (1, 16, 16)


ALL = [basic_usage, token_embedding, type1_inputs_coord_refinement,
       edge_tokens, sparse_neighbors, neighbor_mask, global_nodes,
       autoregressive, memory_lean_attention_variants, egnn_backbone,
       scaling_reversible]

if __name__ == '__main__':
    for fn in ALL:
        fn()
        print(f'{fn.__name__}: ok')
    print('cookbook complete')
