"""What every builder shares: weights made on the device in one jitted
call from the seed, and installed in the program's model."""

from __future__ import annotations

import numpy as np


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    import jax
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0] >> 1)),
                              int(words[1] >> 1))


def install_on_device(model, key, init=None):
    """Initialise ``model`` on the device in ONE jitted call and leave it
    in the state ``model.reset(key)`` leaves it in: parameters, fresh
    state, and the Torch shell's gradient accumulator, one float32 zero
    per parameter **on the device** (``Module.reset``:
    ``tree_zeros_like(params)``).  Neither ``Optimizer.optimize()`` nor
    ``LMServingEngine`` reads the accumulator, and at OPT-6.7B widths it
    holds 3.3 GB; that is the program's cost, every user of the public
    API pays it, and so does every cell (PERF.md 7e).

    ``init(key) -> params`` defaults to the model's own ``_init_params``:
    the program's initialisers, traced once instead of dispatched leaf by
    leaf."""
    import jax
    import jax.numpy as jnp
    model._params = jax.jit(init or model._init_params)(key)
    model._state = model._init_state()
    model._grads = jax.tree_util.tree_map(jnp.zeros_like, model._params)
    if hasattr(model, "_adopt"):
        model._adopt()
    model._jit_apply = None
    return model


def n_parameters(model) -> int:
    import jax
    return int(sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(model.params)))
