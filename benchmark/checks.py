"""The comparisons that decide ``correct``: the program against the plain
reference, outside the measured window, on the weights of the run."""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from benchmark import cells


def _release(*arrays) -> None:
    for a in arrays:
        try:
            a.delete()
        except Exception:  # noqa: BLE001 - already gone or not a device array
            pass


def lm_forward_against_reference(ctx, model, toks) -> Dict[str, Any]:
    """The program's own float32 forward of the seeded model on generated
    sequences against the reference's log-probabilities.

    Both sides run under ``jax.default_matmul_precision("highest")``: on
    a TPU a float32 product otherwise runs in bf16 passes, and that
    rounding, not the architecture, would set the difference (0.010 nats
    here at the default, measured in PR 23).  What is left is the order
    of summation and the flash kernel's own products.  The tolerance
    (``job.logprob_tolerance``) is a few times the largest difference
    measured on the chip (cell file; PERF.md has the origin); the
    reduced-precision path is held by the first-loss check."""
    import jax
    import jax.numpy as jnp
    cfg, job = ctx.config, ctx.cell["job"]
    ref = cells.reference(cfg["reference"])
    builder = cells.builder(cfg["builder"])
    n_head = int(cfg["num_attention_heads"])
    x = jnp.asarray(np.asarray(toks)[:, :-1])
    model.evaluate()
    own = jax.jit(lambda p, s, x: model.apply(p, x, s, training=False)[0])
    with jax.default_matmul_precision("highest"):
        got = own(model.params, model.state, x)
    want = jax.jit(ref.log_probs, static_argnums=2)(
        builder.reference_weights(model), x, n_head)
    diff = jnp.abs(got - want)
    worst = float(jnp.max(diff))
    mean = float(jnp.mean(diff))
    # the reference's arg-max token at each position: how often the
    # program's own arg-max is within the margin of it
    agree = float(jnp.mean(jnp.argmax(got, -1) == jnp.argmax(want, -1)))
    _release(got, want, diff, x)
    tol = float(job["logprob_tolerance"])
    ctx.say(f"forward vs reference: max |dlogp| {worst:.5f}, mean "
            f"{mean:.6f}, arg-max agreement {agree:.4f} (tolerance {tol})")
    return {"checks": {"forward_matches_reference": worst <= tol},
            "detail": {"logprob_max_abs_diff": worst,
                       "logprob_mean_abs_diff": mean,
                       "argmax_agreement": agree}}


def lm_reference_losses(ctx, model, toks) -> np.ndarray:
    """The reference's next-token loss of each sequence, one at a time
    (one compiled shape), at the weights the run starts from."""
    import jax
    import jax.numpy as jnp
    cfg = ctx.config
    ref = cells.reference(cfg["reference"])
    weights = cells.builder(cfg["builder"]).reference_weights(model)
    n_head = int(cfg["num_attention_heads"])

    @jax.jit
    def loss(w, seq):
        lp = ref.log_probs(w, seq[None, :-1], n_head)
        return ref.next_token_loss(lp, seq[None, 1:])

    toks = np.asarray(toks)
    return np.array([float(loss(weights, jnp.asarray(t))) for t in toks])


def lm_generate_against_reference(ctx, eng, model) -> Dict[str, Any]:
    """Prefill and decode through the paged cache against the reference's
    full forward.

    ``generate()`` (the very steps the scheduler dispatches) produces
    ``check_new_tokens`` greedy tokens from each seeded prompt.  The
    reference then runs prompt + those tokens in one float32 pass; at
    every position the token the engine chose must lie within
    ``token_margin`` nats of the reference's best token there.  Tokens
    are not compared for equality: with random weights the best two
    log-probabilities are often closer than the engine's rounding.  The
    margin is a few times the largest shortfall measured on the chip
    (cell file, PERF.md); a cache that returned another position's keys,
    or a product in 8 bits, falls short by far more."""
    import jax
    import jax.numpy as jnp
    cfg, job = ctx.config, ctx.cell["job"]
    ref = cells.reference(cfg["reference"])
    weights = cells.builder(cfg["builder"]).reference_weights(model)
    n_head = int(cfg["num_attention_heads"])
    vocab = int(cfg["vocab_size"])
    new = int(job["check_new_tokens"])
    lengths = [int(n) for n in job["check_prompt_tokens"]]
    pad_to = max(lengths) + new
    rng = np.random.default_rng([ctx.seed & 0xFFFFFFFF, 31])
    forward = jax.jit(ref.log_probs, static_argnums=2)
    worst = 0.0
    exact = total = 0
    for n in lengths:
        prompt = rng.integers(1, vocab + 1, n, dtype=np.int32)
        toks = eng.generate(prompt, max_new_tokens=new)
        seq = np.ones((1, pad_to), np.int32)      # causal: padding after
        seq[0, :n + new] = np.concatenate([prompt, toks])
        lp = forward(weights, jnp.asarray(seq), n_head)[0]
        rows = np.asarray(lp[n - 1:n + new - 1])  # row p predicts p + 1
        chosen = rows[np.arange(new), np.asarray(toks) - 1]
        short = rows.max(axis=1) - chosen
        worst = max(worst, float(short.max()))
        exact += int((short == 0).sum())
        total += new
        _release(lp)
    margin = float(job["token_margin"])
    ctx.say(f"generate vs reference: worst shortfall {worst:.5f} nats over "
            f"{total} tokens ({exact} are the reference's arg-max; margin "
            f"{margin})")
    return {"checks": {"generate_matches_reference": worst <= margin},
            "detail": {"token_worst_shortfall": worst,
                       "token_argmax_agree": exact, "token_checked": total}}


def image_forward_against_reference(ctx, model, images) -> Dict[str, Any]:
    """The program's float32 training-mode forward (batch statistics, as
    the step computes) on a few generated images against the reference.
    The program's side runs under ``jax.default_matmul_precision(
    "highest")`` as the reference does: at the TPU's default (bf16
    passes) 53 layers of convolution and batch statistics leave 0.22 nats
    of rounding (measured in PR 23), which would hide a wrong layer.
    Tolerance ``job.logprob_tolerance``: a few times what the chip
    showed at "highest"."""
    import jax
    import jax.numpy as jnp
    cfg, job = ctx.config, ctx.cell["job"]
    ref = cells.reference(cfg["reference"])
    weights = cells.builder(cfg["builder"]).reference_weights(model)
    own = jax.jit(lambda p, s, x: model.apply(p, x, s, training=True)[0])
    with jax.default_matmul_precision("highest"):
        got = own(model.params, model.state, images)
    want = jax.jit(ref.log_probs)(weights, images)
    worst = float(jnp.max(jnp.abs(got - want)))
    agree = float(jnp.mean(jnp.argmax(got, -1) == jnp.argmax(want, -1)))
    _release(got, want)
    tol = float(job["logprob_tolerance"])
    ctx.say(f"forward vs reference on {images.shape[0]} images: max "
            f"|dlogp| {worst:.5f}, arg-max agreement {agree:.3f} "
            f"(tolerance {tol})")
    return {"checks": {"forward_matches_reference": worst <= tol},
            "detail": {"logprob_max_abs_diff": worst,
                       "argmax_agreement": agree}}


def image_reference_losses(ctx, model, xs, ys) -> np.ndarray:
    """The reference's loss of every resident batch at the weights the run
    starts from."""
    import jax
    cfg = ctx.config
    ref = cells.reference(cfg["reference"])
    weights = cells.builder(cfg["builder"]).reference_weights(model)
    loss = jax.jit(lambda w, x, y: ref.nll_loss(ref.log_probs(w, x), y))
    return np.array([float(loss(weights, xs[i], ys[i]))
                     for i in range(xs.shape[0])])
