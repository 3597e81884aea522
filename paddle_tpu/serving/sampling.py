"""Token sampling for the decode loop — greedy + temperature, all under
explicit PRNG keys so a serving trace is reproducible given (seed,
arrival order): request ``r``'s ``n``-th sampled token always uses
``fold_in(fold_in(base_key, r), n)`` regardless of which batch slot or
step it lands in."""

from __future__ import annotations


def request_keys(base_key, request_ids, token_indices):
    """Per-row sampling keys: fold the request id then the per-request
    token index into ``base_key`` (both [B] int32)."""
    import jax  # deferred: the package imports this module eagerly

    def one(rid, n):
        return jax.random.fold_in(jax.random.fold_in(base_key, rid), n)

    return jax.vmap(one)(request_ids, token_indices)


def sample_tokens(logits, keys, temperatures):
    """logits [B, V], keys [B] PRNG keys, temperatures [B] -> tokens [B].

    Rows with ``temperature <= 0`` are greedy (argmax); others draw from
    softmax(logits / temperature) with that row's key."""
    import jax
    import jax.numpy as jnp

    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    temps = jnp.maximum(temperatures, 1e-6)[:, None]
    drawn = jax.vmap(
        lambda k, l: jax.random.categorical(k, l)
    )(keys, logits.astype(jnp.float32) / temps).astype(jnp.int32)
    return jnp.where(temperatures > 0, drawn, greedy)


def sample_block(logits, base_key, request_ids, first_index, temperatures):
    """A block pass's choice at every position: logits [B, T, V] (float32),
    request_ids / first_index / temperatures [B] -> (tokens [B, T] int32,
    confidence [B, T] float32).  Position ``t`` of row ``b`` is greedy at
    ``temperature <= 0``, else drawn under the key of index
    ``first_index[b] + t`` of its request; its confidence is the softmax
    probability of the token chosen, at temperature 1.  When every row
    is greedy nothing is drawn."""
    import jax
    import jax.numpy as jnp

    b, t, v = logits.shape
    flat = logits.reshape(b * t, v)
    temps = jnp.repeat(temperatures, t)

    def drawn():
        keys = request_keys(
            base_key, jnp.repeat(request_ids, t),
            (first_index[:, None] + jnp.arange(t)[None, :]).reshape(-1))
        return sample_tokens(flat, keys, temps)

    toks = jax.lax.cond(
        jnp.any(temperatures > 0), drawn,
        lambda: jnp.argmax(flat, axis=-1).astype(jnp.int32))
    chosen = jnp.take_along_axis(flat, toks[:, None], axis=-1)[:, 0]
    conf = jnp.exp(chosen - jax.nn.logsumexp(flat, axis=-1))
    return toks.reshape(b, t), conf.reshape(b, t)


def choose_unmask(policy, masked, confidence, count):
    """Which masked positions a denoising pass unmasks: masked [B, T]
    bool, confidence [B, T], count [B] how many -> [B, T] bool.
    "low_confidence_static": the ``count`` most confident masked positions
    (ties: the leftmost); "sequential": the ``count`` leftmost."""
    import jax.numpy as jnp

    t = masked.shape[1]
    if policy == "sequential":
        rank = jnp.cumsum(masked, axis=1) - 1
    elif policy == "low_confidence_static":
        score = jnp.where(masked, confidence, -1.0)
        before = jnp.arange(t)[:, None] < jnp.arange(t)[None, :]   # [u, t]
        ahead = (score[:, :, None] > score[:, None, :]) | (
            (score[:, :, None] == score[:, None, :]) & before)
        rank = jnp.sum(ahead, axis=1)
    else:
        raise ValueError(f"unknown unmask policy {policy!r}")
    return masked & (rank < count[:, None])
