"""Offline sizing: compile each cell's step for a described v5e, print
``memory_analysis()``.  No chip, no chip time, nothing runs.

    JAX_PLATFORMS=cpu python benchmark/sizing.py [cell ...] [--set key=value]

The TPU compiler is installed in the sandbox and compiles for the
``v5e:2x2`` topology it is told about.  This script hands the program's
own step builders shapes on a described device (parameters from
``jax.eval_shape`` of the model's initialiser) and reports the bytes the
compiled program needs: arguments, outputs, temporaries, and what
donation aliases.  It counts one program, and adds the one thing the program
always holds beside it (``module_grads_gb``: the gradient accumulator
``Module.reset()`` makes, one float32 zero per parameter); the cell
files add what else lives beside the step (resident batches, a pool
copy still in flight).

It reaches into the program (``_build_step``, ``init_slots``,
``_build_decode_fn``) because a compile needs the jitted function itself;
a run never does.  ``jax.default_backend`` is patched to say "tpu" while
lowering, since the flash layer asks it before it emits the kernel.
A compile that passes is not a chip run and is never reported as one.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def _gb(n) -> float:
    return round(float(n) / 1e9, 3)


def _tree_gb(tree) -> float:
    import jax
    import numpy as np
    return _gb(sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(tree)))


def _report(name: str, compiled, params=None) -> dict:
    m = compiled.memory_analysis()
    out = {
        "program": name,
        "argument_gb": _gb(m.argument_size_in_bytes),
        "output_gb": _gb(m.output_size_in_bytes),
        "alias_gb": _gb(m.alias_size_in_bytes),
        "temp_gb": _gb(m.temp_size_in_bytes),
        "code_gb": _gb(m.generated_code_size_in_bytes),
    }
    out["live_gb"] = round(out["argument_gb"] + out["output_gb"]
                           - out["alias_gb"] + out["temp_gb"]
                           + out["code_gb"], 3)
    if params is not None:
        # Module.reset() keeps a float32 zero per parameter on the device
        # (the Torch shell's gradient accumulator) that no step takes
        out["module_grads_gb"] = _tree_gb(params)
        out["held_gb"] = round(out["live_gb"] + out["module_grads_gb"], 3)
    text = compiled.as_text()
    out["tpu_custom_calls"] = text.count("tpu_custom_call")
    for op in ("all-reduce", "reduce-scatter", "all-gather"):
        out[op] = sum(text.count(f" {op}{s}(") for s in ("", "-start"))
    return out


def _spec_tree(tree, sharding):
    import jax
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


class _as_tpu:
    """``jax.default_backend()`` answers "tpu" inside the block."""

    def __enter__(self):
        import jax
        self._orig = jax.default_backend
        jax.default_backend = lambda: "tpu"

    def __exit__(self, *exc):
        import jax
        jax.default_backend = self._orig


def _load(cell_name: str, overrides):
    from benchmark import cells
    cell = cells.load_cell(cell_name)
    cells.apply_overrides(cell, overrides)
    return cell, cells.load_config(cell["config"])


def _shape_model(config):
    """The configuration's model with no array made, from its builder."""
    from benchmark import cells
    return cells.builder(config["builder"]).shape_model(config)


def _train_step_specs(model, optim_method, inputs, targets, sharding):
    import jax
    import jax.numpy as jnp
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(model._init_params, key)
    slots = jax.eval_shape(optim_method.init_slots, params)
    mstate = jax.eval_shape(model._init_state)
    # python floats in the real call too (weak-typed scalars)
    hyper = optim_method.hyper()
    rng = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=sharding)
    return (_spec_tree(params, sharding), _spec_tree(slots, sharding),
            _spec_tree(mstate, sharding), inputs, targets, hyper, rng)


def size_local_train(cell, config, topo) -> dict:
    """The fused step of ``LocalOptimizer`` for a training cell."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import LocalDataSet
    from bigdl_tpu.optim.optimizer import LocalOptimizer

    from benchmark import training
    one = SingleDeviceSharding(topo.devices[0])
    job, mix = cell["job"], cell["mix"]
    if cell["role"] == "image_train":
        from bigdl_tpu.models.perf import _resnet50
        model = _resnet50(config.get("program", {}).get("layout", "NHWC"))
        b, s = int(mix["batch"]), int(config["image_size"])
        inputs = jax.ShapeDtypeStruct((b, 3, s, s), jnp.float32,
                                      sharding=one)
        targets = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one)
        criterion = nn.ClassNLLCriterion()
    else:
        model = _shape_model(config)
        b, t = int(mix["batch"]), int(mix["seq_len"])
        inputs = jax.ShapeDtypeStruct((b, t), jnp.int32, sharding=one)
        targets = jax.ShapeDtypeStruct((b, t), jnp.int32, sharding=one)
        criterion = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(),
                                                size_average=True)
    opt = LocalOptimizer(model, LocalDataSet([]), criterion)
    opt.set_precision(job.get("precision", "bf16"))
    method = training.optim_method(job)
    opt.set_optim_method(method)
    with _as_tpu():
        step = opt._build_step()
        args = _train_step_specs(model, method, inputs, targets, one)
        compiled = step.lower(*args).compile()
    return _report(f"{cell['name']}: LocalOptimizer fused step", compiled,
                   args[0])


def size_serve(cell, config, topo) -> list:
    """The decode step and the largest prefill bucket of the LM server."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from bigdl_tpu.serving import lm as _lm
    one = SingleDeviceSharding(topo.devices[0])
    eng = cell["job"]["engine"]
    B, ctx = int(eng["max_batch"]), int(eng["max_context"])
    bs = int(eng.get("block_size", 16))
    MB = -(-ctx // bs)
    n_blocks = B * MB + 1
    model = _shape_model(config)
    for m in model.modules():
        if hasattr(m, "flash"):
            m.flash = False          # the server has its own attention
    graph = _lm._LMGraph(model)
    params = jax.eval_shape(model._init_params, jax.random.PRNGKey(0))
    leaves = _spec_tree(params, one)

    def dp_from(p):
        layers = []
        for blk in p[2:-3]:
            (ln1, att), (ln2, ffn) = blk
            layers.append({
                "ln1": {"w": ln1["weight"], "b": ln1["bias"]},
                "attn": {k: {"w": att[k], "b": att["b" + k[-1]]}
                         for k in ("wq", "wk", "wv", "wo")},
                "ln2": {"w": ln2["weight"], "b": ln2["bias"]},
                "ffn": {"up": {"w": ffn[0]["weight"], "b": ffn[0]["bias"]},
                        "down": {"w": ffn[2]["weight"],
                                 "b": ffn[2]["bias"]}}})
        return {"embed": p[0]["weight"], "layers": layers,
                "lnf": {"w": p[-3]["weight"], "b": p[-3]["bias"]},
                "head": {"w": p[-2]["weight"], "b": p[-2]["bias"]}}

    dp = dp_from(leaves)
    pool = jax.ShapeDtypeStruct(
        (graph.n_layers, n_blocks, bs, graph.n_head, graph.head_dim),
        jnp.float32, sharding=one)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    # the pools are donated, as the server's own steps donate them
    decode = jax.jit(_lm._build_decode_fn(graph, bs, MB),
                     donate_argnums=(1, 2))
    prefill = jax.jit(_lm._build_prefill_fn(graph, bs),
                      donate_argnums=(1, 2))
    out = []
    c = decode.lower(dp, pool, pool, i32(B, 1), i32(B), i32(B, MB),
                     jax.ShapeDtypeStruct((B,), jnp.bool_,
                                          sharding=one)).compile()
    out.append(_report(f"{cell['name']}: lm_decode (B{B}, {n_blocks} "
                       f"blocks)", c, leaves))
    c = prefill.lower(dp, pool, pool, i32(1, ctx), i32(), i32(MB)).compile()
    out.append(_report(f"{cell['name']}: lm_prefill bucket {ctx}", c,
                       leaves))
    return out


def size_dp_train(cell, config, topo) -> dict:
    raise NotImplementedError(
        "DistriOptimizer._optimize builds its AllReduceParameter from "
        "materialised parameters placed on jax.devices(); its step "
        "cannot be lowered from shapes without an edit to the program.  "
        "The arithmetic in PERF.md stands in and a chip run decides.")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("cells", nargs="*")
    p.add_argument("--set", action="append", default=[],
                   metavar="key=value",
                   help="override a value of the cell file, e.g. "
                        "mix.batch=4")
    p.add_argument("--topology", default="v5e:2x2")
    args = p.parse_args(argv)
    from jax.experimental import topologies

    from benchmark import cells
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    names = args.cells or cells.all_cells()
    rc = 0
    for name in names:
        cell, config = _load(name, args.set)
        try:
            if cell["role"] in ("lm_train", "image_train"):
                res = [size_local_train(cell, config, topo)]
            elif cell["role"] == "lm_serve_open_loop":
                res = size_serve(cell, config, topo)
            elif cell["role"] == "lm_train_dp":
                res = [size_dp_train(cell, config, topo)]
            else:
                raise NotImplementedError(f"no sizing for role "
                                          f"{cell['role']!r}")
        except NotImplementedError as e:
            print(json.dumps({"cell": name, "not_sized": str(e)}))
            continue
        except Exception as e:  # the compiler's refusal is the finding
            print(json.dumps({"cell": name, "refused": repr(e)[:2000]}))
            rc = 1
            continue
        for r in res:
            print(json.dumps(r))
    return rc


if __name__ == "__main__":
    sys.exit(main())
