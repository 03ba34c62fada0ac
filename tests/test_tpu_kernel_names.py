"""The flash kernels' names in the device trace, checked at no chip time.

A Mosaic call is named in the trace after the innermost scope it is born
in, and the benchmark's ``flash_roofline.train`` finds the three flash
kernels by those names (``trace.kernel_pattern`` of the training cell).
This compiles a small fused train step for a described (not attached)
v5e and reads the names off the compiled program: whatever scopes the
program grows around its attention, the three kernels keep names the
pattern matches.

The topology is described inside a fixture, never at import: only the
worker that runs this file loads the TPU's compiler.
"""

import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu.dataset import LocalDataSet
from bigdl_tpu.models.transformer import transformer_lm
from bigdl_tpu.optim.optimizer import LocalOptimizer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_flash_kernels_keep_the_names_the_benchmark_reads(one_chip,
                                                          monkeypatch):
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "opt67b-train-t2048.json")) as f:
        pattern = re.compile(json.load(f)["trace"]["kernel_pattern"])
    model = transformer_lm(512, d_model=256, n_head=2, n_layers=1,
                           max_len=256)
    for m in model.modules():
        if isinstance(m, nn.MultiHeadAttention):
            m.flash = True
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(),
                                       size_average=True)
    opt = LocalOptimizer(model, LocalDataSet([]), crit)
    opt.set_precision("bf16")
    method = optim.SGD(0.01, momentum=0.9)
    opt.set_optim_method(method)

    def spec(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(model._init_params, key)
    tokens = jax.ShapeDtypeStruct((2, 256), jnp.int32, sharding=one_chip)
    args = (spec(params), spec(jax.eval_shape(method.init_slots, params)),
            spec(jax.eval_shape(model._init_state)), tokens, tokens,
            method.hyper(),
            jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip))
    # the flash layer asks the backend before it emits the kernel
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # the suite asks for float32 matmul passes (conftest); the kernel is
    # compiled as production compiles it
    with jax.default_matmul_precision("default"):
        text = opt._build_step().lower(*args).compile().as_text()
    kernels = [ln.strip().split(" = ")[0].lstrip("%")
               for ln in text.splitlines()
               if "custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(kernels) == 3, kernels            # forward, dkv, dq
    assert all(pattern.search(k) for k in kernels), kernels
    assert sorted(k.split(".")[0][:17] for k in kernels) == [
        "flash_mha_bwd_dkv", "flash_mha_bwd_dq_", "flash_mha_fwd"]


def test_lm_decode_reads_no_whole_layer_of_a_pool_on_the_chip(one_chip):
    """The LM server's decode step as the chip's compiler leaves it, pools
    donated: both pools aliased to their outputs (the scatters run in
    place, the attention loop carries the pools without a copy), no
    operation whose result is a whole layer of a pool, every slot's whole
    table or every slot's chunk (the loop reads a group of slots a trip),
    and temporaries smaller than one layer of one pool.  Sixteen slots,
    as the chat cell serves, so that the slots make more than one
    group."""
    from bigdl_tpu.serving import lm as _lm
    B, bs, MB = 16, 16, 32
    assert B > _lm._KV_GROUP
    model = transformer_lm(512, d_model=256, n_head=2, n_layers=2,
                           max_len=MB * bs)
    graph = _lm._LMGraph(model)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    dp = jax.tree_util.tree_map(lambda x: spec(x.shape, x.dtype),
                                _lm._extract_params(graph))
    L, NB, H, Dh = graph.n_layers, B * MB + 1, graph.n_head, graph.head_dim
    pool = spec((L, NB, bs, H, Dh), jnp.float32)
    step = jax.jit(_lm._build_decode_fn(graph, bs, MB),
                   donate_argnums=(1, 2))
    compiled = step.lower(dp, pool, pool, spec((B, 1), jnp.int32),
                          spec((B,), jnp.int32), spec((B, MB), jnp.int32),
                          spec((B,), jnp.bool_)).compile()
    layer_bytes = NB * bs * H * Dh * 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * L * layer_bytes
    assert mem.temp_size_in_bytes < layer_bytes, mem
    text = compiled.as_text()
    cb = _lm._chunk_blocks(bs, MB)
    for shape in ((NB, bs, H, Dh), (1, NB, bs, H, Dh), (B, MB, bs, H, Dh),
                  (B * MB, bs, H, Dh), (B, MB * bs, H, Dh),
                  (B, cb, bs, H, Dh)):
        made = [ln.strip()[:200] for ln in text.splitlines()
                if re.search(r"= f32\[" + ",".join(map(str, shape)) + r"\]",
                             ln)]
        assert not made, made
    group = ",".join(map(str, (_lm._KV_GROUP, cb, bs, H, Dh)))
    assert re.search(r"= f32\[" + group + r"\]", text)
    assert " while(" in text
