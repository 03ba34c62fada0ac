"""Transformer language-model Train driver (beyond-reference family).

Run::

    python -m bigdl_tpu.models.transformer.train --synthetic 256
    python -m bigdl_tpu.models.transformer.train -f corpus.txt --seq-len 128
    python -m bigdl_tpu.models.transformer.train --synthetic 256 \
        --partitions 4 --seq-parallel 2       # dp x sp mesh, ring attention
    python -m bigdl_tpu.models.transformer.train --synthetic 256 \
        --partitions 2 --tensor-parallel 4    # dp x tp GSPMD Megatron
    python -m bigdl_tpu.models.transformer.train --synthetic 256 \
        --moe-experts 8 --partitions 2 --expert-parallel 4   # dp x ep MoE
    python -m bigdl_tpu.models.transformer.train --synthetic 256 \
        --pipeline 4                          # GPipe over a stage mesh

Every parallelism mode trains through the public Optimizer API:
``--seq-parallel N`` shards time over a ``("data", "seq")`` mesh (ring
attention); ``--tensor-parallel N`` Megatron-splits MLPs/heads over
``("data", "model")`` (XLA GSPMD inserts the collectives);
``--expert-parallel N`` dispatches MoE FFNs with all_to_all over
``("data", "expert")`` and folds the load-balancing aux loss into the
objective; ``--pipeline S`` runs S decoder blocks as a GPipe scan over a
``stage`` mesh (optionally x dp with ``--partitions``).
"""

import numpy as np

import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu.dataset import Sample
from bigdl_tpu.dataset.text import Dictionary, SentenceTokenizer
from bigdl_tpu.models import driver_utils
from bigdl_tpu.models.transformer import (transformer_lm,
                                          transformer_lm_pipeline)

VOCAB = 64


def _synthetic(n: int, seq_len: int, seed: int = 1,
               vocab: int = VOCAB) -> list:
    """Learnable next-token structure: token_{t+1} = f(token_t) pattern."""
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        start = rng.randint(1, vocab + 1)
        step = rng.randint(1, 5)
        toks = (np.arange(seq_len + 1) * step + start) % vocab + 1
        out.append(Sample(toks[:-1].astype(np.float32),
                          toks[1:].astype(np.float32)))
    return out


def _load_corpus(path: str, seq_len: int):
    with open(path, errors="ignore") as f:
        words = next(SentenceTokenizer()(iter([f.read()])), [])
    d = Dictionary([words], vocab_size=VOCAB - 1)
    idx = np.asarray([d.get_index(w) + 1 for w in words], np.float32)
    out = []
    for i in range(0, len(idx) - seq_len - 1, seq_len):
        out.append(Sample(idx[i:i + seq_len], idx[i + 1:i + seq_len + 1]))
    return out


def _partial_mesh(Engine, shape, names):
    """Mesh over the first prod(shape) devices — a parallelism request
    smaller than the machine should run on a sub-mesh, not error."""
    import numpy as _np
    needed = int(_np.prod(shape))
    return Engine.create_mesh(shape, names,
                              devices=Engine.devices()[:needed])


def main(argv=None):
    p = driver_utils.base_parser("Train a decoder-only transformer LM")
    p.add_argument("--seq-len", type=int, default=32)
    p.add_argument("--d-model", type=int, default=64)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--seq-parallel", type=int, default=0,
                   help="N>1: shard time over a ('data','seq') mesh")
    p.add_argument("--tensor-parallel", type=int, default=0,
                   help="N>1: Megatron-split over a ('data','model') mesh")
    p.add_argument("--moe-experts", type=int, default=0,
                   help="E>0: Switch-MoE FFNs with E experts per block")
    p.add_argument("--expert-parallel", type=int, default=0,
                   help="N>1: all_to_all MoE dispatch over a "
                        "('data','expert') mesh (needs --moe-experts)")
    p.add_argument("--moe-top-k", type=int, default=1,
                   help="experts per token: 1 = Switch (default), "
                        "2 = the GShard configuration (needs --moe-experts)")
    p.add_argument("--pipeline", type=int, default=0,
                   help="S>1: GPipe the S decoder blocks over a 'stage' "
                        "mesh axis (sets --layers S)")
    p.add_argument("--n-micro", type=int, default=4,
                   help="GPipe microbatches per replica (with --pipeline)")
    p.add_argument("--remat", choices=["full", "dots", "save_attn"],
                   default=None,
                   help="activation-checkpoint every decoder block: 'full' "
                        "saves nothing per block, 'dots' keeps matmul "
                        "outputs, 'save_attn' keeps only the attention "
                        "context (trade FLOPs for HBM — how the >=1B "
                        "single-chip point fits)")
    args = p.parse_args(argv)
    driver_utils.init_logging()
    batch = args.batch_size or 32
    modes = [m for m, on in (("--seq-parallel", args.seq_parallel > 1),
                             ("--tensor-parallel", args.tensor_parallel > 1),
                             ("--expert-parallel", args.expert_parallel > 1),
                             ("--pipeline", args.pipeline > 1)) if on]
    if len(modes) > 1 and set(modes) != {"--pipeline", "--tensor-parallel"}:
        raise SystemExit(f"pick one parallelism mode, got {modes} "
                         "(--pipeline composes with --tensor-parallel "
                         "only)")
    if args.expert_parallel > 1 and not args.moe_experts:
        raise SystemExit("--expert-parallel needs --moe-experts")
    if args.moe_top_k != 1 and not args.moe_experts:
        raise SystemExit("--moe-top-k needs --moe-experts")

    remat = {"full": True, None: False}.get(args.remat, args.remat)

    if args.synthetic:
        records = _synthetic(args.synthetic, args.seq_len)
    else:
        records = _load_corpus(args.folder, args.seq_len)

    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(),
                                       size_average=True)
    lr = args.learning_rate or 1e-3
    from bigdl_tpu.dataset import SampleToMiniBatch
    from bigdl_tpu.dataset.dataset import ShardedDataSet
    from bigdl_tpu.engine import Engine
    from bigdl_tpu.parallel import DistriOptimizer, PipelineOptimizer
    dp = max(1, args.partitions or 1)

    if args.pipeline > 1:
        # GPipe: S homogeneous decoder blocks over a stage mesh (x dp)
        if args.model or args.state:
            raise SystemExit("--pipeline does not support --model/--state "
                             "snapshot resume yet")
        tp_n = args.tensor_parallel if args.tensor_parallel > 1 else 0
        if tp_n and args.moe_experts:
            raise SystemExit("pick one of --tensor-parallel / "
                             "--moe-experts per block")
        embed, blocks, head = transformer_lm_pipeline(
            VOCAB, args.d_model, args.heads, n_layers=args.pipeline,
            max_len=max(4096, args.seq_len), moe_experts=args.moe_experts,
            moe_top_k=args.moe_top_k, remat=remat, tp=bool(tp_n))
        shape = (dp, args.pipeline) if dp > 1 else (args.pipeline,)
        names = ("data", "stage") if dp > 1 else ("stage",)
        if tp_n:
            # 3-D composition: ('data','stage','model') (or 2-D without dp)
            shape = shape + (tp_n,)
            names = names + ("model",)
        mesh = _partial_mesh(Engine, shape, names)
        ds = driver_utils.make_dataset(records, args, batch)
        opt = PipelineOptimizer(blocks, ds, crit, mesh=mesh,
                                n_micro=args.n_micro, embed=embed,
                                head=head)
        opt.set_optim_method(optim.Adam(learning_rate=lr))
        model = opt.model
    else:
        model, method = driver_utils.load_snapshots(
            args, lambda: transformer_lm(VOCAB, args.d_model, args.heads,
                                         args.layers,
                                         max_len=max(4096, args.seq_len),
                                         tp=args.tensor_parallel > 1,
                                         moe_experts=args.moe_experts,
                                         moe_top_k=args.moe_top_k,
                                         remat=remat),
            lambda: optim.Adam(learning_rate=lr))
        if args.seq_parallel > 1:
            mesh = _partial_mesh(Engine, (dp, args.seq_parallel),
                                 ("data", "seq"))
        elif args.tensor_parallel > 1:
            mesh = _partial_mesh(Engine, (dp, args.tensor_parallel),
                                 ("data", "model"))
        elif args.expert_parallel > 1:
            mesh = _partial_mesh(Engine, (dp, args.expert_parallel),
                                 ("data", "expert"))
        else:
            mesh = None
        if mesh is not None:
            ds = ShardedDataSet(records, dp).transform(
                SampleToMiniBatch(batch, dp))
            opt = DistriOptimizer(model, ds, crit, mesh=mesh)
        else:
            ds = driver_utils.make_dataset(records, args, batch)
            opt = optim.Optimizer.create(model, ds, crit)
        opt.set_optim_method(method)
    driver_utils.configure(opt, args, default_epochs=10,
                           app_name="transformer")
    trained = opt.optimize()

    # report next-token accuracy on the training set
    x = np.stack([s.feature for s in records[:64]])
    y = np.stack([s.label for s in records[:64]])
    pred = np.asarray(trained.forward(x)).argmax(-1) + 1
    acc = float((pred == y).mean())
    print(f"Final next-token accuracy: {acc:.4f}")
    return trained


if __name__ == "__main__":
    main()
