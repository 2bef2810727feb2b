"""Helpers the tests share that answer for the INSTALLED libraries by
probing them, never by version string."""


def shard_index_set(arr):
    """Distinct shard indices of a jax Array, as hashable tuples."""
    return {tuple((sl.start, sl.stop, sl.step) for sl in s.index)
            for s in arr.addressable_shards}


def optax_belief_uses_stale_mu() -> bool:
    """True when this optax's AdaBelief computes the prediction error
    against the PRE-update EMA (``g - state.mu``), as the installed optax
    does — the paper (and our sparse kernel, embedding/sparse_optim.py)
    uses the POST-update EMA (``g - m_t``), so an exact match is
    impossible under such an optax.  Probed numerically (one scalar step
    from zero state distinguishes the two closed forms), so the gate
    answers for whatever optax is actually installed."""
    import jax.numpy as jnp
    import optax

    opt = optax.adabelief(1.0, b1=0.9, b2=0.9, eps=0.0, eps_root=0.0)
    p = jnp.float32(0.0)
    up, _ = opt.update(jnp.float32(1.0), opt.init(p), p)
    # stale mu: nu=(1-b2)g² → |update| = 1;  post-update mu:
    # nu=(1-b2)(b1·g)² → |update| = 1/b1 ≈ 1.111
    return abs(float(up)) < 1.05
