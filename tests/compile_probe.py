"""Test support: one trainer + validation lifecycle in a process of its
own, against a given executable cache (``bigdl.compile.cacheDir``).

``python tests/compile_probe.py <cache dir>`` trains a 3-layer MLP for 6
steps and evaluates a ragged 57 records through the bucketed eval
forward (buckets ``8,16``, strict retrace sentinel), then prints one
JSON line: per fused step its cache hits, misses and compiles, the eval
sentinel's retraces and a CRC of the trained weights.  The audit passes
stay at their default (warn), so every committed entry carries its
program census in the manifest: what the offline auditor reads
(``python -m bigdl_tpu.analysis.hlo_audit <cache dir> --baselines
audit_baselines.json``).

Run once on an empty directory it is the cold start, run again on the
same one the warm start of a REAL second process: the claim the
persistent cache makes and that no in-process test can show
(``tests/test_compile_cache.py``, ``tests/test_hlo_audit.py``).
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_child(cache_dir: str) -> dict:
    """Run the lifecycle in a child process; returns what it printed."""
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), cache_dir],
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main(cache_dir: str) -> None:
    import jax
    import numpy as np

    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.dataset.transformer import SampleToMiniBatch
    from bigdl_tpu.optim.evaluator import evaluate_dataset
    from bigdl_tpu.optim.optimizer import Optimizer
    from bigdl_tpu.optim.validation_method import Top1Accuracy
    from bigdl_tpu.utils import config
    from bigdl_tpu.utils.random_generator import RandomGenerator
    from bigdl_tpu.visualization.crc32c import crc32c

    config.set_property("bigdl.compile.cacheDir", cache_dir)
    config.set_property("bigdl.compile.buckets", "8,16")
    config.set_property("bigdl.analysis.retrace", "strict")
    RandomGenerator.RNG().set_seed(1234)
    rng = np.random.RandomState(0)
    samples = [Sample(rng.normal(size=(8,)).astype(np.float32),
                      np.int64(i % 3 + 1)) for i in range(64)]
    m = (nn.Sequential().add(nn.Linear(8, 32)).add(nn.Tanh())
         .add(nn.Linear(32, 3)).add(nn.LogSoftMax()))
    m.reset(jax.random.PRNGKey(7))
    o = Optimizer.create(m, samples, nn.ClassNLLCriterion(), batch_size=16)
    o.set_optim_method(optim.SGD(learning_rate=0.1))
    o.set_end_when(optim.max_iteration(6))
    o.optimize()
    train_step = getattr(o._step_fn, "__wrapped__", o._step_fn)

    # 57 records -> batches of 16, 16, 16, 9
    evaluate_dataset(m, list(SampleToMiniBatch(16)(iter(samples[:57]))),
                     [Top1Accuracy()])
    eval_fn = m._eval_jit[id(None)]
    eval_step = getattr(eval_fn, "__wrapped__", eval_fn)

    def counts(step):
        return {"hits": step.cache_hits, "misses": step.cache_misses,
                "compiles": step.compiles}

    weights = np.concatenate([np.ravel(np.asarray(x))
                              for x in jax.tree_util.tree_leaves(m.params)])
    print(json.dumps({
        "steps": {"train/local": counts(train_step),
                  "eval": counts(eval_step)},
        "eval_retraces": eval_fn.sentinel.retraces,
        "weights_crc": int(crc32c(weights.tobytes())),
    }))


if __name__ == "__main__":
    sys.path.insert(0, REPO)
    main(sys.argv[1])
