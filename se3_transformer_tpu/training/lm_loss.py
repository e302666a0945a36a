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

A second objective beside it, for a decoder trained by diffusion over blocks
(`make_block_diffusion_loss`): the model reads a noised copy of the sequence
and the clean one in one pass (`hidden_states(tokens, noised,
block_length)`) and predicts the masked tokens in place, each weighted by
1 / t:

    loss = (1 / (B L)) sum_sequences sum_i m_i (1 / t) [logsumexp(h~_i W)
                                                        - (h~_i W)[x_i]]

over the noised stream's positions, in the same chunked head. The step draws
nothing: the batch carries the noise (`noise_tokens` makes one).

A third, for a looped stack (`make_looped_lm_loss`; `HybridDecoder` with
`total_ut_steps` P above 1): every pass's normed state is an exit, read by
the one head, and a learned gate says where a token leaves. With l_t[n] the
cross-entropy of pass t at token n and lam_t[n] = sigmoid(gate(g_t[n])),

    p_t = lam_t prod_{s < t} (1 - lam_s)   (t < P),
    p_P = prod_{s < P} (1 - lam_s)         (the last pass takes what is left)
    loss = mean_n [ sum_t p_t[n] l_t[n] - beta H(p[n]) ],
    H(p) = - sum_t p_t log p_t

with log p from log-sigmoids, in float32. Each pass goes through the chunked
head once, its rows weighted by p_t, and the weight is differentiated: the
gate's gradient comes through it.
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
        return _chunked_nll(h, kernel, targets, valid, chunk,
                            lambda vc, nll: jnp.where(vc, nll, 0.0)) \
            / jnp.maximum(jnp.sum(valid), 1)


def chunked_weighted_nll(h, kernel, targets, weight, chunk: int = 1024):
    """The same head with a float32 weight [N] a row: the SUM over rows of
    weight * (logsumexp(h kernel) - (h kernel)[target]); the caller
    divides. A weight [N, k] gives the k sums of one pass through the
    head."""
    with named_scope('lm_head'):
        return _chunked_nll(
            h, kernel, targets, weight, chunk,
            lambda wc, nll: wc * (nll if wc.ndim == 1 else nll[:, None]))


def _chunked_nll(h, kernel, targets, rows, chunk, weigh):
    """sum over chunks of sum(weigh(rows, nll)), `rows` cut as h is."""
    n = h.shape[0]
    chunk = min(chunk, n)
    assert n % chunk == 0, (n, chunk)

    @jax.checkpoint
    def one(hc, tc, rc):
        logits = jnp.dot(hc, kernel, preferred_element_type=jnp.float32)
        picked = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        nll = jax.nn.logsumexp(logits, axis=-1) - picked
        return jnp.sum(weigh(rc, nll), axis=0)

    # a Python loop, not a scan: inside a scanned, checkpointed body the
    # operations lose their scopes, and these are the head's products
    return sum(one(h[i:i + chunk], targets[i:i + chunk], rows[i:i + chunk])
               for i in range(0, n, chunk))


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


def noise_tokens(key, tokens, mask_id: int, eps: float = 1e-3):
    """The forward process of a masked diffusion, for `make_block_diffusion_
    loss`: one noise level t ~ U(eps, 1] a sequence, every token masked
    independently with probability t. tokens [B, L] -> {'tokens', 'noised'
    (tokens, `mask_id` where masked), 'weight' [B, L] float32 (1 / t where
    masked, else 0)}."""
    k_t, k_m = jax.random.split(key)
    b = tokens.shape[0]
    t = eps + (1.0 - eps) * (1.0 - jax.random.uniform(k_t, (b, 1)))
    masked = jax.random.uniform(k_m, tokens.shape) < t
    return dict(tokens=tokens,
                noised=jnp.where(masked, mask_id, tokens).astype(tokens.dtype),
                weight=jnp.where(masked, 1.0 / t, 0.0).astype(jnp.float32))


def make_block_diffusion_loss(module, block_length: int, chunk: int = 1024):
    """loss_fn(params, batch, rng) -> (loss, aux) for
    `make_sharded_train_step`; batch = {'tokens' [B, L] int32, 'noised'
    [B, L] int32, 'weight' [B, L] float32 = m / t}. `aux` carries the expert
    layers' counters (over both streams' 2 L positions) and `bd_masked` (the
    step's targets), `bd_weight` (the sum of the weights)."""

    def loss_fn(params, batch, rng):
        del rng
        with named_scope('loss'):      # as `make_lm_loss` opens it
            return _loss(params, batch['tokens'], batch['noised'],
                         batch['weight'])

    def _loss(params, tokens, noised, weight):
        main, _, stats = module.apply(
            {'params': params}, tokens, noised, block_length,
            method='hidden_states')
        d = main.shape[-1]
        with named_scope('bd_streams'):
            weight = weight.astype(jnp.float32).reshape(-1)
            targets = tokens.reshape(-1)
        loss = chunked_weighted_nll(
            main.reshape(-1, d), module.head_kernel(params), targets, weight,
            chunk) / tokens.size
        with named_scope('bd_streams'):
            aux = dict(loss_main=loss,
                       bd_masked=jnp.sum(weight > 0, dtype=jnp.int32),
                       bd_weight=jnp.sum(weight))
        if stats:
            aux.update(expert_counters(stats))
        return loss, aux

    return loss_fn


def make_looped_lm_loss(module, beta: float = 0.1, chunk: int = 1024):
    """loss_fn(params, batch, rng) -> (loss, aux) for
    `make_sharded_train_step`, the objective over a looped stack's exits
    (this file's head); batch = {'tokens': [B, T] int32}. `aux`: `loss_main`
    (the last pass's plain cross-entropy), `loss_ut` [P] (each pass's),
    `exit_share` [P] (the mean of p_t over the valid tokens),
    `exit_entropy` (the mean of H), `exit_mass_last` and `exit_tokens` (the
    sum of p_P and the count of valid tokens: their ratio is
    `exit_share[-1]`, for a reader that divides), and the expert layers'
    counters where the pattern has any."""

    def loss_fn(params, batch, rng):
        del rng
        with named_scope('loss'):      # as `make_lm_loss` opens it
            return _loss(params, batch['tokens'])

    def states(mdl, tokens):
        main, _, stats = mdl.hidden_states(tokens)
        return main, mdl.exit_logits(main), stats

    def _loss(params, tokens):
        b, t = tokens.shape
        main, gate, stats = module.apply({'params': params}, tokens,
                                         method=states)
        kernel = module.head_kernel(params)
        passes, d = main.shape[0], main.shape[-1]
        targets = jnp.roll(tokens, -1, axis=1).reshape(-1)
        valid = (jnp.broadcast_to(jnp.arange(t), (b, t)).reshape(-1)
                 < t - 1).astype(jnp.float32)
        n_tokens = jnp.sum(valid)
        n_valid = jnp.maximum(n_tokens, 1)
        with named_scope('exit_mix'):
            gate = gate.astype(jnp.float32).reshape(passes - 1, -1)
            stay = jnp.cumsum(jax.nn.log_sigmoid(-gate), axis=0)
            zero = jnp.zeros_like(gate[:1])
            # log p_t = log lam_t + sum_{s < t} log (1 - lam_s); lam_P = 1
            log_p = jnp.concatenate((jax.nn.log_sigmoid(gate), zero)) \
                + jnp.concatenate((zero, stay))
            p = jnp.exp(log_p)
            entropy = -jnp.sum(p * log_p, axis=0)
        # a pass through the head gives two sums: p_t l_t, which is
        # differentiated, and the plain l_t for `aux`
        sums = jnp.stack([chunked_weighted_nll(
            main[i].reshape(-1, d), kernel, targets,
            jnp.stack((p[i] * valid, valid), axis=-1), chunk)
            for i in range(passes)])
        with named_scope('exit_mix'):
            entropy = jnp.sum(entropy * valid) / n_valid
            mass = jnp.sum(p * valid, axis=1)
            loss = jnp.sum(sums[:, 0]) / n_valid - beta * entropy
            aux = dict(loss_main=sums[-1, 1] / n_valid,
                       loss_ut=sums[:, 1] / n_valid,
                       exit_share=mass / n_valid, exit_entropy=entropy,
                       exit_mass_last=mass[-1], exit_tokens=n_tokens)
        if stats:
            aux.update(expert_counters(stats))
        return loss, aux

    return loss_fn


def balance_expert_load(module, params, batches, steps: int = 300,
                        block_length: int = 0):
    """`params` with every expert layer's correction bias settled by the
    aux-loss-free balancing rule (`ops.expert_layer.balance_bias`) on the
    router's scores over `batches` (a list of {'tokens': [B, T]}; with a
    `block_length`, of block-diffusion batches, both streams' scores), layer
    by layer from the first: a layer's bias moves what every later layer
    sees. Stands for what training has done to the buffer; it takes no
    gradient."""
    names = module.expert_layer_names()
    streams = (lambda b: (b['tokens'], b['noised'], block_length)) \
        if block_length else (lambda b: (b['tokens'],))
    scores_of = jax.jit(lambda p, batch: [
        s['scores'] for s in module.apply({'params': p}, *streams(batch),
                                          method='hidden_states')[2]])
    # the rule's rates at the scale of the scores: about 0.5, or 1 / E
    scale = 2.0 / module.n_routed_experts \
        if getattr(module, 'scoring_func', 'sigmoid') == 'softmax' else 1.0
    settle = jax.jit(lambda scores, bias: balance_bias(
        scores, bias, module.num_experts_per_tok, steps, scale))
    for i, name in enumerate(names):
        scores = jnp.concatenate(
            [scores_of(params, b)[i] for b in batches])
        moe = params[name]['moe']
        bias = settle(scores, moe['correction_bias'])
        params = {**params, name: {**params[name], 'moe': {
            **moe, 'correction_bias': bias}}}
    return params
