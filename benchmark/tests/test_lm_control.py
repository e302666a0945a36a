"""The token decoder's control: the plain reference with every learned
operand rounded to float8_e4m3fn (`jax.lax.reduce_precision`) must come out
not correct under the limits the configuration file carries, at a size a test
run can hold; PERF.md has the readings at the cell's own size on the chip."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from harness import lm_reference, lm_train, lm_traffic, state

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = dict(vocab_rows=512, hidden_size=128, intermediate_size=256,
             moe_intermediate_size=64, num_hidden_layers=2,
             first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=48,
             kv_lora_rank=32, qk_nope_head_dim=24, qk_rope_head_dim=8,
             v_head_dim=32, n_routed_experts=16, n_shared_experts=1,
             num_experts_per_tok=4, experts_held=4, expert_rank=0,
             routed_scaling_factor=1.8, norm_topk_prob=True,
             num_nextn_predict_layers=1, rope_theta=1e6, rms_norm_eps=1e-5)
MIX = dict(kind='lm_train_closed', seq=128, batch=1, n_batches=3,
           zipf_exponent=1.1, document_tokens=dict(median=40, sigma=1.2))


def test_training_steps_with_fp8_operands_fail_a_limit():
    from se3_transformer_tpu.training.recipes import RECIPES
    limits = json.load(open(os.path.join(
        BENCH, 'configs', 'glm47-flash-ep8-train.json')))['correct']
    module = RECIPES['token_decoder'](**MODEL)
    tokens = lm_traffic.token_batches(MIX, 2**31 + 11, MODEL['vocab_rows'])
    abstract = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                              jax.ShapeDtypeStruct((1, 128), jnp.int32))
    theta = lm_train.make_fill(abstract['params'])(
        state.prng_key(2**31 + 11, 0))

    def steps(operand_bits):
        vg = jax.jit(jax.value_and_grad(
            lambda p, t: lm_reference.loss(p, t, MODEL, attn_block=64,
                                           chunk=64,
                                           operand_bits=operand_bits),
            has_aux=True))
        th = theta
        mu = jax.tree_util.tree_map(jnp.zeros_like, th)
        nu = jax.tree_util.tree_map(jnp.zeros_like, th)
        out = dict(losses=[])
        for t in range(1, 4):
            (loss, chosen), g = vg(th, jnp.asarray(tokens[t - 1]))
            out['losses'].append(float(loss))
            if t == 1:
                out['grad'] = state.leaf_norms(g)
                out['grad_tree'] = [np.asarray(a) for a in
                                    jax.tree_util.tree_leaves(g)]
                out['choice'] = np.asarray(chosen)
            th, mu, nu = lm_reference.adam_update(th, g, mu, nu, float(t),
                                                  lr=1e-4)
        out['delta'] = state.leaf_norms(jax.tree_util.tree_map(
            jnp.subtract, th, theta))
        return out

    ref, ctl = steps(None), steps(lm_reference.FP8_E4M3)
    assert lm_train.compare(ref, ref, limits).ok        # the reference passes
    assert not lm_train.compare(ctl, ref, limits).ok    # one precision below
