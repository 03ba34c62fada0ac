"""The served model against the plain reference, log-prob by log-prob:
the limit that tells the configuration's precision from the one below.

The role's check (``checks.lm_generate_against_reference``) is handed the
tokens ``generate()`` chose and nothing else, and on random weights the
chosen token's shortfall does not tell bf16 from 8 bits (PERF.md 6).
This file compares what the paged path computed: a prefill of
``prompt_tokens`` and ``new_tokens - 1`` decode steps through an engine
of the built model (``LMServingEngine.generate(return_logps=True)``: the
steps the scheduler dispatches, one slot) against the reference's ONE
full forward of the prompt and the chosen tokens.  Per decode step it
takes the largest |d log-prob| over the vocabulary, and over the steps
the **median**: a router that takes 8 of 256 and an indexer that takes
2048 of thousands flip under rounding and move single steps by half a
nat, in the reference as in the program; the median is deaf to a few
such steps, the mean (printed beside it) is not.

It runs twice.  ``builders/<family>.build`` calls :func:`gate` on every
model it builds for a configuration whose ``program.served_logprob_gate``
gives the sizes and the tolerance, before the cell's engine exists, and
a model over the tolerance is not measured (``SystemExit``, as
``run.py`` refuses a CPU).  And as a command, the probe that set the
tolerance: the same comparison over several seeds, and the control, the
reference with every matrix rounded to a narrower dtype, which has to
come out over the tolerance:

    chiprun -- python3 benchmark/served_logprobs.py \\
        --workload glm52-serve-longdoc-r80 --seeds 3000000077,2147483999 \\
        --control float8_e4m3fn
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
from typing import Any, Dict

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def compare(model, config: Dict[str, Any], prompt_seed,
            control: str = "") -> Dict[str, Any]:
    """Prefill-then-decode of ``model`` against the reference's full
    forward, at the sizes ``config["program"]["served_logprob_gate"]``
    gives.  ``control``: a dtype's name; the reference's matrices are
    rounded to it first (the program's stay as they are), and a second
    set of readings says how far that reference lands from the program."""
    import jax
    import jax.numpy as jnp

    from benchmark import cells
    from bigdl_tpu.serving.lm import LMServingEngine
    g = config["program"]["served_logprob_gate"]
    n, new = int(g["prompt_tokens"]), int(g["new_tokens"])
    ref = cells.reference(config["reference"])
    weights = cells.builder(config["builder"]).reference_weights(model)
    n_head = int(config["num_attention_heads"])
    rng = np.random.default_rng(prompt_seed)
    prompt = rng.integers(1, int(config["vocab_size"]) + 1, n,
                          dtype=np.int32)
    eng = LMServingEngine(model, max_batch=1,
                          max_context=int(g["max_context"]),
                          block_size=int(g["block_size"]),
                          prefill_buckets=[int(g["max_context"])])
    try:
        eng.warmup()
        toks, rows = eng.generate(prompt, max_new_tokens=new,
                                  return_logps=True)
    finally:
        eng.stop(grace=0.0)
    del eng
    gc.collect()        # its pools, and its hold on the parameters, go now
    seq = jnp.asarray(np.concatenate([prompt, toks])[None])
    forward = jax.jit(ref.log_probs, static_argnums=2)

    def against(w) -> Dict[str, float]:
        # row p of the reference predicts position p + 1, as the engine's
        # i-th decode step (fed the token at n + i) does
        want = np.asarray(forward(w, seq, n_head)[0, n:n + new - 1])
        steps = np.abs(np.stack(rows) - want).max(axis=1)
        return {"median": float(np.median(steps)),
                "mean": float(steps.mean()), "max": float(steps.max())}

    out = {"prompt_tokens": n, "decode_steps": new - 1,
           "max_abs_dlogp_over_steps": against(weights)}
    if control:
        low = dict(weights, hyper=dataclasses.replace(
            weights["hyper"], weights_as=control))
        out["control"] = control
        out["control_max_abs_dlogp_over_steps"] = against(low)
    return out


def gate(model, config: Dict[str, Any], key) -> Dict[str, Any]:
    """What a builder calls on the model it built: the comparison, with
    the prompt drawn from the run's key, held to the configuration's
    tolerance.  Returns the readings; over the tolerance nothing is
    measured."""
    import jax
    tol = float(config["program"]["served_logprob_gate"]["tolerance"])
    words = np.asarray(jax.random.key_data(key)).ravel().tolist()
    got = compare(model, config, [int(w) for w in words] + [26])
    median = got["max_abs_dlogp_over_steps"]["median"]
    print(f"[bench] served log-probs vs reference: {json.dumps(got)} "
          f"(tolerance on the median {tol})", flush=True)
    if not median <= tol:
        raise SystemExit(
            f"benchmark: the served model is not the reference at the "
            f"configuration's precision: over {got['decode_steps']} decode "
            f"steps after {got['prompt_tokens']} prompt tokens the median "
            f"of max |d log-prob| is {median:.5f}, tolerance {tol} "
            f"(program.served_logprob_gate)")
    return got


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="1",
                   help="comma-separated; weights and prompt from each")
    p.add_argument("--control", default="",
                   help="a dtype the reference's matrices are rounded to")
    p.add_argument("--data-dir", default=None)
    p.add_argument("--allow-cpu", action="store_true")
    args = p.parse_args(argv)
    from benchmark import cells
    from benchmark.builders.common import seed_key
    if args.data_dir:
        cells.DATA_DIRS.insert(0, os.path.abspath(args.data_dir))
    config = cells.load_config(cells.load_cell(args.workload)["config"])
    tol = float(config["program"]["served_logprob_gate"]["tolerance"])
    from bigdl_tpu.engine import init_compile_cache
    init_compile_cache()
    import jax
    on_tpu = jax.devices()[0].platform == "tpu"
    if not (on_tpu or args.allow_cpu):
        raise SystemExit("benchmark: not a TPU; nothing is measured on "
                         "anything else")
    # the builder's own gate would stop at the first model over the
    # tolerance; the probe reads every seed
    ungated = dict(config, program={k: v for k, v in config["program"].items()
                                    if k != "served_logprob_gate"})
    builder = cells.builder(config["builder"])
    bad = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        model = builder.build(ungated, seed_key(seed), on_tpu=on_tpu)
        got = compare(model, config, [seed & 0xFFFFFFFF, 26], args.control)
        holds = got["max_abs_dlogp_over_steps"]["median"] <= tol
        line = dict(got, seed=seed, tolerance=tol, holds=holds)
        if args.control:
            line["control_fails"] = (
                got["control_max_abs_dlogp_over_steps"]["median"] > tol)
            bad += not line["control_fails"]
        bad += not holds
        print(json.dumps(line), flush=True)
        del model
        gc.collect()                    # before the next seed's weights
    return 1 if bad else 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
