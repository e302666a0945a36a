"""Next-token loss of the token decoder (models/token_decoder.py), two heads
over the vocabulary rows held here:

    loss = CE(main head, token t + 1) + mtp_weight * CE(prediction block,
    token t + 2), each a mean over its valid positions

The head's matrix is the module's to name (`head_kernel(params)`: a `head`
subtree, or the embedding's transpose where the model ties them, which then
takes both gradients). The logits are taken in chunks of tokens, each chunk
recomputed in the backward pass: two [tokens, vocab_rows] float32 arrays with
their cotangents are never held. `aux` carries the expert layers' counters as device scalars
(fetched with the loss) and the step's choices (left on the device unless
asked for).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..observability import named_scope
from ..ops.expert_layer import balance_bias


def chunked_cross_entropy(h, kernel, targets, valid, chunk: int = 1024):
    """h [N, d], kernel [d, V], targets [N] int, valid [N] bool -> the mean
    over valid rows of logsumexp(h kernel) - (h kernel)[target], float32.
    All of it is the leaf `lm_head`."""
    with named_scope('lm_head'):
        return _chunked_cross_entropy(h, kernel, targets, valid, chunk)


def _chunked_cross_entropy(h, kernel, targets, valid, chunk):
    n = h.shape[0]
    chunk = min(chunk, n)
    assert n % chunk == 0, (n, chunk)

    @jax.checkpoint
    def one(hc, tc, vc):
        logits = jnp.dot(hc, kernel, preferred_element_type=jnp.float32)
        picked = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        nll = jax.nn.logsumexp(logits, axis=-1) - picked
        return jnp.sum(jnp.where(vc, nll, 0.0))

    # a Python loop, not a scan: inside a scanned, checkpointed body the
    # operations lose their scopes, and these are the head's products
    total = sum(one(h[i:i + chunk], targets[i:i + chunk], valid[i:i + chunk])
                for i in range(0, n, chunk))
    return total / jnp.maximum(jnp.sum(valid), 1)


def expert_counters(stats):
    """The step's `aux` from the expert layers' stats (one entry a layer)."""
    load = jnp.stack([s['load'] for s in stats])         # [layers, held]
    return dict(
        moe_local_pairs=jnp.sum(load),
        moe_load_max=jnp.max(load),
        moe_load_mean=jnp.mean(load.astype(jnp.float32)),
        moe_dropped=sum(s['dropped'] for s in stats),
        moe_bounded=sum(s['bounded'] for s in stats),
        moe_choice=jnp.stack([s['chosen'] for s in stats]).astype(jnp.uint8))


def make_lm_loss(module, mtp_weight: float = 0.3, chunk: int = 1024):
    """loss_fn(params, batch, rng) -> (loss, aux) for
    `make_sharded_train_step`; batch = {'tokens': [B, T] int32}."""

    def loss_fn(params, batch, rng):
        del rng
        # one scope around everything: under `value_and_grad` the name stack
        # wraps the FIRST scope it meets (`jvp(loss)`), and those inside it
        # stay readable as leaves
        with named_scope('loss'):
            return _loss(params, batch['tokens'])

    def _loss(params, tokens):
        b, t = tokens.shape
        main, ahead, stats = module.apply({'params': params}, tokens,
                                          method='hidden_states')
        kernel = module.head_kernel(params)
        pos = jnp.broadcast_to(jnp.arange(t), (b, t)).reshape(-1)
        d = main.shape[-1]
        loss = chunked_cross_entropy(
            main.reshape(-1, d), kernel,
            jnp.roll(tokens, -1, axis=1).reshape(-1), pos < t - 1, chunk)
        aux = dict(loss_main=loss)
        if ahead is not None:
            aux['loss_mtp'] = chunked_cross_entropy(
                ahead.reshape(-1, d), kernel,
                jnp.roll(tokens, -2, axis=1).reshape(-1), pos < t - 2, chunk)
            loss = loss + mtp_weight * aux['loss_mtp']
        if stats:
            aux.update(expert_counters(stats))
        return loss, aux

    return loss_fn


def balance_expert_load(module, params, batches, steps: int = 300):
    """`params` with every expert layer's correction bias settled by the
    aux-loss-free balancing rule (`ops.expert_layer.balance_bias`) on the
    router's scores over `batches` (a list of {'tokens': [B, T]}), layer by
    layer from the first: a layer's bias moves what every later layer sees.
    Stands for what training has done to the buffer; it takes no gradient."""
    names = module.expert_layer_names()
    scores_of = jax.jit(lambda p, tokens: [
        s['scores'] for s in module.apply({'params': p}, tokens,
                                          method='hidden_states')[2]])
    settle = jax.jit(lambda scores, bias: balance_bias(
        scores, bias, module.num_experts_per_tok, steps))
    for i, name in enumerate(names):
        scores = jnp.concatenate(
            [scores_of(params, b['tokens'])[i] for b in batches])
        moe = params[name]['moe']
        bias = settle(scores, moe['correction_bias'])
        params = {**params, name: {**params[name], 'moe': {
            **moe, 'correction_bias': bias}}}
    return params
