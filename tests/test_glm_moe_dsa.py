"""The ``glm_moe_dsa`` family (GLM-5.2): the module against the plain
reference, the LM server's paged path against the reference's full
forward, the share-of-experts identity, the two pools, and what the model
holds per parameter.  Toy widths that keep the structure: 1 dense + 4
expert layers typed full | shared, shared, shared, full; top-k smaller
than the context; 16 experts top-2 + 1 shared."""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import glm_moe_dsa as builder
from benchmark.references import glm_moe_dsa as ref
from bigdl_tpu.models.transformer.glm_moe_dsa import glm_moe_dsa_lm
from bigdl_tpu.nn import latent_attention as la
from bigdl_tpu.nn.moe import SigmoidRoutedExperts, sigmoid_routed_experts
from bigdl_tpu.serving.lm import LMServingEngine, UnsupportedModelError

VOCAB, HEADS, TOPK = 50, 4, 6
TYPES = ["full", "shared", "shared", "shared", "full"]


def toy(held=(4, 4), param_dtype=None, topk=TOPK, key=0, init_depth=None):
    # 16 indexer heads: with few, relu leaves exact zeros, and ties at
    # the threshold are where "the k highest" and "at or above the k-th"
    # part ways
    m = glm_moe_dsa_lm(
        vocab_size=VOCAB, hidden_size=32, num_attention_heads=HEADS,
        q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=6,
        qk_rope_head_dim=4, v_head_dim=8, index_n_heads=16,
        index_head_dim=8, index_topk=topk, indexer_types=TYPES,
        mlp_layer_types=["dense"] + ["sparse"] * 4, intermediate_size=64,
        moe_intermediate_size=16, n_routed_experts=16,
        num_experts_per_tok=2, routed_scaling_factor=2.5, rope_theta=8e6,
        experts_held=held, param_dtype=param_dtype, init_depth=init_depth)
    return m.reset(jax.random.PRNGKey(key))


@pytest.fixture(scope="module")
def model():
    return toy()


@pytest.fixture(scope="module")
def reference(model):
    weights = builder.reference_weights(model)
    fn = jax.jit(ref.log_probs, static_argnums=2)
    return lambda tokens: np.asarray(fn(weights, jnp.asarray(tokens), HEADS))


def tokens_of(seed, *shape):
    return np.random.default_rng(seed).integers(1, VOCAB + 1, shape,
                                                dtype=np.int32)


# ---------------------------------------------------------------------------
# the module
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("batch,length", [(1, 5), (2, 20), (3, 33)])
def test_module_forward_matches_the_reference(model, reference, batch,
                                              length):
    """Logits, not tokens; the longer ones are past index_topk, so the
    selection binds, in full and in shared layers."""
    x = tokens_of(length, batch, length)
    model.evaluate()
    got = np.asarray(model.forward(jnp.asarray(x)))
    np.testing.assert_allclose(got, reference(x), atol=2e-5)
    np.testing.assert_allclose(np.exp(got).sum(-1), 1.0, atol=1e-5)


def test_selection_changes_the_answer(model, reference):
    """The toy's top-k does bind: with it off the logits differ."""
    x = tokens_of(1, 1, 24)
    wide = toy(topk=64)
    wide.evaluate()
    assert np.abs(np.asarray(wide.forward(jnp.asarray(x)))
                  - reference(x)).max() > 1e-3


def test_shared_layers_own_no_indexer_parameters(model):
    layers = model.params["layers"]
    assert [("indexer" in l) for l in layers] == [t == "full" for t in TYPES]
    assert [("ffn" in l, "moe" in l) for l in layers] == (
        [(True, False)] + [(False, True)] * 4)
    assert model.spec.index_layers == (0, 4)


def test_seeded_norms_and_selection_bias_are_not_trivial(model):
    """So that leaving one out cannot pass the comparison."""
    p = model.params
    for w in (p["ln_f"], p["layers"][0]["ln1"],
              p["layers"][0]["attn"]["q_a_norm"],
              p["layers"][0]["indexer"]["k_norm_w"]):
        assert float(jnp.abs(w - 1).max()) > 0.01
    assert float(jnp.abs(p["layers"][0]["indexer"]["k_norm_b"]).max()) > 0.01
    # the selection bias is small (it must not unbalance the experts'
    # loads) and still decides some token's experts: without it the
    # logits move
    toks = tokens_of(3, 3, 33)
    unbiased = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x)
        if getattr(path[-1], "key", None) == "bias" else x, p)
    with_bias = model.apply(p, toks, model.state)[0]
    without = model.apply(unbiased, toks, model.state)[0]
    assert float(jnp.abs(with_bias - without).max()) > 1e-2


def test_builder_refuses_what_the_family_is_not():
    with pytest.raises(ValueError, match="starts with 'full'"):
        glm_moe_dsa_lm(50, 32, 4, 16, 8, 6, 4, 8, 2, 8, 6,
                       ["shared", "full"], ["dense", "sparse"], 64, 16, 16, 2)
    with pytest.raises(ValueError, match="no range"):
        toy(held=(12, 8))


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", [1, 3, 7, 16])
def test_kth_largest_is_exact(k):
    rng = np.random.default_rng(k)
    x = rng.normal(size=(3, 5, 16)).astype(np.float32)
    x[0, 0, :4] = -np.inf
    x[1, 2, 3] = x[1, 2, 4]                       # a tie
    x[2, 1] = np.where(rng.random(16) < 0.5, 0.0, -0.0)
    keys = la._sortable(jnp.asarray(x))
    thr = np.asarray(la.kth_largest(keys, k))
    want = np.sort(np.asarray(keys), axis=-1)[..., -k]
    np.testing.assert_array_equal(thr, want)
    # same order as the floats
    assert (np.argsort(np.asarray(keys)[0, 1], kind="stable")
            == np.argsort(x[0, 1], kind="stable")).all()


def test_rotary_turns_adjacent_pairs():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 3, 8)),
                    jnp.float32)
    pos = jnp.asarray([[0, 1, 5]])
    y = np.asarray(la.rotary_interleaved(x, pos, 100.0))
    np.testing.assert_allclose(y[0, 0], np.asarray(x)[0, 0], atol=1e-6)
    for i in range(4):
        ang = 5 * 100.0 ** (-2 * i / 8)
        a, b = np.asarray(x)[0, 2, 2 * i], np.asarray(x)[0, 2, 2 * i + 1]
        np.testing.assert_allclose(
            y[0, 2, 2 * i:2 * i + 2],
            [a * np.cos(ang) - b * np.sin(ang),
             a * np.sin(ang) + b * np.cos(ang)], atol=1e-5)
    np.testing.assert_allclose(np.linalg.norm(y, axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)


def test_swiglu_in_row_chunks_is_swiglu(monkeypatch):
    m = la.SwiGLU(8, 16)
    p = m._init_params(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 11, 8)),
                    jnp.float32)
    whole = la.swiglu(p, x)
    monkeypatch.setattr(la, "_FFN_ROWS", 4)
    np.testing.assert_allclose(la.swiglu(p, x), whole, atol=1e-6)
    want = (jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])) @ p["w2"]
    np.testing.assert_allclose(whole, want, atol=1e-5)


def test_query_blocks_do_not_change_attention(model, monkeypatch):
    x = tokens_of(3, 1, 37)
    model.evaluate()
    whole = np.asarray(model.apply(model.params, jnp.asarray(x), {})[0])
    monkeypatch.setattr(la, "query_block", lambda t: 8)
    monkeypatch.setattr(la, "key_groups", lambda t: [(0, 16), (16, t)])
    blocked = np.asarray(model.apply(model.params, jnp.asarray(x), {})[0])
    np.testing.assert_allclose(blocked, whole, atol=2e-5)


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------


def test_shares_of_an_expert_layer_sum_to_the_uncut_layer():
    """Four chips with four experts each: the sum of their outputs, the
    shared expert counted once, is the layer that holds all sixteen."""
    key = jax.random.PRNGKey(3)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 9, 32)),
                    jnp.float32)
    whole = SigmoidRoutedExperts(32, 16, 16, 2, 2.5)
    pw = whole._init_params(key)
    want, _ = whole.apply(pw, x, {})
    shared = la.swiglu(pw["shared"], x)
    total = shared
    made = held_total = 0
    for first in (0, 4, 8, 12):
        part = SigmoidRoutedExperts(32, 16, 16, 2, 2.5, held=(first, 4))
        pp = part._init_params(key)
        np.testing.assert_array_equal(pp["w1"], pw["w1"][first:first + 4])
        np.testing.assert_array_equal(pp["gate"], pw["gate"])
        y, counts = sigmoid_routed_experts(pp, x.reshape(18, 32), 2, 2.5,
                                           (first, 4))
        total = total + (y.reshape(x.shape) - shared)
        made, held_total = int(counts[0]), held_total + int(counts[1])
    np.testing.assert_allclose(total, want, atol=1e-5)
    assert made == 18 * 2 and held_total == made


def test_expert_layer_runs_in_chunks_and_counts_valid_tokens(monkeypatch):
    from bigdl_tpu.nn import moe
    layer = SigmoidRoutedExperts(32, 16, 16, 2, 2.5, held=(2, 5))
    p = layer._init_params(jax.random.PRNGKey(1))
    x = jnp.asarray(np.random.default_rng(1).normal(size=(13, 32)),
                    jnp.float32)
    valid = jnp.arange(13) < 9
    whole, counts = sigmoid_routed_experts(p, x, 2, 2.5, (2, 5), valid)
    monkeypatch.setattr(moe, "_EXPERT_ROWS", 4)
    chunked, counts4 = sigmoid_routed_experts(p, x, 2, 2.5, (2, 5), valid)
    np.testing.assert_allclose(chunked, whole, atol=1e-5)
    assert int(counts[0]) == 18 and counts.tolist() == counts4.tolist()
    # the padding rows got the shared expert and nothing else
    np.testing.assert_allclose(whole[9:], la.swiglu(p["shared"], x[9:]),
                               atol=1e-6)


@pytest.mark.parametrize("chunk_rows", [4096, 4, 7])
def test_rows_outside_every_group_never_reach_the_output(monkeypatch,
                                                         chunk_rows):
    """``lax.ragged_dot`` leaves the rows beyond its groups undefined; on
    the TPU they are old memory, NaN among it (PR 26: the unpaged forward
    of 632 tokens came out NaN).  Poisoned here, they must not show."""
    from bigdl_tpu.nn import moe
    layer = SigmoidRoutedExperts(32, 16, 16, 2, 2.5, held=(2, 5))
    p = layer._init_params(jax.random.PRNGKey(1))
    x = jnp.asarray(np.random.default_rng(2).normal(size=(13, 32)),
                    jnp.float32)
    monkeypatch.setattr(moe, "_EXPERT_ROWS", chunk_rows)
    want, _ = sigmoid_routed_experts(p, x, 2, 2.5, (2, 5))
    real = jax.lax.ragged_dot

    def poisoned(lhs, rhs, group_sizes, **kw):
        out = real(lhs, rhs, group_sizes, **kw)
        inside = jnp.arange(out.shape[0]) < jnp.sum(group_sizes)
        return jnp.where(inside[:, None], out, jnp.nan)

    monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)
    got, counts = sigmoid_routed_experts(p, x, 2, 2.5, (2, 5))
    assert 0 < int(counts[1]) < int(counts[0])     # some rows are outside
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=1e-6)


# ---------------------------------------------------------------------------
# the server
# ---------------------------------------------------------------------------


def engine(model, **kw):
    args = dict(max_batch=2, max_context=48, block_size=4)
    args.update(kw)
    return LMServingEngine(model, **args)


def test_prefill_then_paged_decode_matches_the_reference(model, reference):
    """Two slots of different lengths decode side by side through the
    block table, across block boundaries, past index_topk: every step's
    logits against the reference's full forward of the whole sequence."""
    eng = engine(model)
    eng.warmup()
    prompts = [tokens_of(11, 11), tokens_of(12, 18)]
    new = 9
    B, MB = eng.max_batch, eng._max_blocks
    rows, toks = [], []
    for i, p in enumerate(prompts):
        eng.cache.allocate(-10 - i, p.size + new)
        tok, row = eng._prefill_step_raw(-10 - i, p)
        rows.append(row)
        toks.append([tok])
    got = [[], []]
    for step in range(new - 1):
        tokens = np.array([[t[-1]] for t in toks], np.int32)
        positions = np.array([p.size + step for p in prompts], np.int32)
        with eng._lock:
            tok, lp, eng.cache.pools, aux = eng._decode(
                eng._dp, *eng.cache.pools, tokens, positions, np.stack(rows),
                np.ones((B,), bool))
        lp = np.asarray(lp)
        np.testing.assert_array_equal(np.asarray(tok)[:, 0],
                                      np.argmax(lp, axis=-1) + 1)
        made, _, read = np.asarray(aux).tolist()
        assert made == 2 * 2 * 4          # 2 tokens, top-2, 4 layers
        # the device's own count of the rows attention read, a layer
        assert read == int(np.minimum(positions + 1, TOPK).sum())
        for i in range(B):
            got[i].append(lp[i])
            toks[i].append(int(np.argmax(lp[i])) + 1)
    for i, p in enumerate(prompts):
        seq = np.concatenate([p, toks[i]])[None]
        want = reference(seq)[0]
        assert toks[i][0] == int(np.argmax(want[p.size - 1])) + 1
        for step, row in enumerate(got[i]):
            np.testing.assert_allclose(row, want[p.size + step], atol=2e-5)
        eng.cache.free_seq(-10 - i)
    assert _pools_clean(eng.cache)


def test_step_token_is_the_argmax_of_its_own_row(model):
    """The second family's steps pass through the same one place: over a
    run of decode steps with a live and an idle slot, ``tok`` is
    ``argmax(lp) + 1`` of the ``lp`` the same call returns, bit for bit,
    in the prefill's scalar too."""
    eng = engine(model)
    eng.warmup()
    prompt = tokens_of(41, 14)
    eng.cache.allocate(-3, prompt.size + 8)
    padded = np.ones((1, eng._prefill_bucket(prompt.size)), np.int32)
    padded[0, :prompt.size] = prompt
    row = np.full((eng._max_blocks,), 0, np.int32)
    blocks = eng.cache.table(-3)
    row[:len(blocks)] = blocks
    tok, lp, eng.cache.pools, aux = eng._prefill(
        eng._dp, *eng.cache.pools, padded, np.int32(prompt.size), row)
    assert np.asarray(tok).shape == () and np.asarray(aux).shape == (3,)
    assert int(tok) == int(np.argmax(np.asarray(lp))) + 1
    B = eng.max_batch
    tokens = np.ones((B, 1), np.int32)
    positions = np.zeros((B,), np.int32)
    tables = np.zeros((B, eng._max_blocks), np.int32)
    active = np.zeros((B,), bool)
    tokens[1, 0], positions[1], tables[1], active[1] = (
        int(tok), prompt.size, row, True)
    for _ in range(7):
        tok, lp, eng.cache.pools, aux = eng._decode(
            eng._dp, *eng.cache.pools, tokens, positions, tables, active)
        tok, lp = np.asarray(tok), np.asarray(lp)
        assert tok.shape == (B, 1) and tok.dtype == np.int32
        assert lp.shape == (B, VOCAB)
        np.testing.assert_array_equal(tok[:, 0], np.argmax(lp, axis=-1) + 1)
        tokens[1] = tok[1]
        positions[1] += 1
    eng.cache.free_seq(-3)
    assert _pools_clean(eng.cache)


def test_scheduler_pulls_once_a_step_tokens_and_counts_together(model):
    """A decode step of the family that counts brings ``4 x max_batch +
    12`` bytes across in ONE pull (its counts had a round trip of their
    own before), a prefill one int32 and the same 12."""
    from bigdl_tpu import telemetry
    from bigdl_tpu.analysis import hostsync
    eng = engine(model, deadline_ms=60000)
    eng.warmup()
    before = telemetry.counter("LM/decode_pulled_bytes").value
    d2h = telemetry.counter("LM/d2h_bytes").value
    pulls = hostsync.STATS.explicit_pulls
    eng.start()
    try:
        streams = [eng.submit(tokens_of(51, 9), max_new_tokens=6),
                   eng.submit(tokens_of(52, 15), max_new_tokens=4)]
        for s in streams:
            s.result(timeout=120)
    finally:
        eng.stop(grace=0.0)
    st = eng.stats()
    per_step = 4 * eng.max_batch + 12
    assert st["decode_steps"] > 0 and st["prefills"] == 2
    assert st["decode_pulled_bytes"] == st["decode_steps"] * per_step
    assert telemetry.counter("LM/decode_pulled_bytes").value - before == \
        st["decode_pulled_bytes"]
    assert hostsync.STATS.explicit_pulls - pulls == st["decode_steps"] + 2
    assert telemetry.counter("LM/d2h_bytes").value - d2h == \
        st["decode_pulled_bytes"] + 2 * (4 + 12)
    assert st["moe_assignments"] > 0 and st["attn_selected_tokens"] > 0
    # offline generate() pulls its tokens and leaves the counts where
    # they are
    d2h = telemetry.counter("LM/d2h_bytes").value
    ref = engine(model)
    assert ref.generate(tokens_of(51, 9), max_new_tokens=6) == \
        streams[0].result()
    assert telemetry.counter("LM/d2h_bytes").value - d2h == \
        4 + 5 * 4 * ref.max_batch
    assert ref.stats()["decode_pulled_bytes"] == 0


def test_paged_selection_is_the_references(model):
    """Layer by layer the positions decode gathers are the reference's
    S_t, in a layer typed full and as handed up to the shared ones."""
    a = model.spec.attention
    p = model.params["layers"][0]
    T, P, bs = 22, 9, 4
    x = jnp.asarray(np.random.default_rng(5).normal(size=(1, T, 32)),
                    jnp.float32)
    pos = jnp.arange(T)[None]
    own, _, sel = la.latent_attention(a, p["attn"], p["indexer"], x, pos)
    # the reference's mask from the same indexer inputs
    w = builder.reference_weights(model)
    q_i = la._index_queries(a, sel.wq, sel.c_q, pos)
    mask = ref.selected(w["hyper"], q_i, sel.k,
                        sel.w, T)(q_i, sel.w, pos)[0]       # (T, T)
    table = jnp.arange(1, 7)
    cache = la.LatentCache(
        jnp.zeros((1, 8, bs, a.latent_row_width)),
        jnp.zeros((1, 8, bs, a.index_head_dim)),
        table[jnp.arange(P) // bs][None], (jnp.arange(P) % bs)[None])
    out, cache, _ = la.latent_attention(a, p["attn"], p["indexer"],
                                        x[:, :P], pos[:, :P], cache)
    np.testing.assert_allclose(out, own[:, :P], atol=1e-5)
    tables = jnp.concatenate([table, jnp.zeros(2, jnp.int32)])[None]
    for t in range(P, T):
        cache = la.LatentCache(cache.latent, cache.index,
                               tables[:, t // bs][:, None],
                               jnp.array([[t % bs]]), tables)
        out, cache, s = la.latent_attention(
            a, p["attn"], p["indexer"], x[:, t:t + 1], pos[:, t:t + 1],
            cache)
        got = set(np.asarray(s.positions[0])[np.asarray(s.valid[0])]
                  .tolist())
        assert got == set(np.flatnonzero(np.asarray(mask[t])).tolist())
        assert len(got) == min(TOPK, t + 1)
        np.testing.assert_allclose(out, own[:, t:t + 1], atol=1e-5)
        # a shared layer attends under the positions it is handed
        shared, _, s2 = la.latent_attention(
            a, p["attn"], None, x[:, t:t + 1], pos[:, t:t + 1], cache,
            sel=s)
        assert s2 is s
        np.testing.assert_allclose(shared, out, atol=1e-6)


@pytest.fixture(scope="module")
def wide(model):
    """An engine of the benchmark cell's 16 slots."""
    return engine(model, max_batch=16, max_context=32, block_size=4)


def _decode_live(eng, tokens, positions, tables, active):
    """The family's decode step, not donating (the pools stay for the
    next call), and the selection layer 0 makes inside it from the same
    inputs."""
    from bigdl_tpu.serving.lm import _build_glm_steps
    a, bs = eng.graph.spec.attention, eng.block_size

    def layer0(dp, latent, index, tokens, positions, tables, active):
        lyr = dp["layers"][0]
        x = jnp.take(dp["embed"], tokens - 1, axis=0).astype(jnp.float32)
        blk = jnp.where(active, tables[jnp.arange(tables.shape[0]),
                                       positions // bs], 0)
        cache = la.LatentCache(latent, index, blk[:, None],
                               (positions % bs)[:, None], tables)
        _, _, sel = la.latent_attention(
            a, lyr["attn"], lyr["indexer"], la.rms_norm(x, lyr["ln1"], a.eps),
            positions[:, None], cache, valid=active[:, None])
        return sel.positions, sel.valid

    args = (eng._dp, *eng.cache.pools, tokens, positions, tables, active)
    lp, pools, counts = jax.jit(_build_glm_steps(eng.graph, bs)[0])(*args)
    chosen, ok = jax.jit(layer0)(*args)
    return (np.asarray(lp), [np.array(p) for p in pools],
            int(counts[2]), np.asarray(chosen), np.asarray(ok))


@pytest.mark.parametrize("live", [(), (0,), (3, 7, 12), tuple(range(16))],
                         ids=["none", "slot0", "holes", "all"])
def test_decode_reads_the_live_slots_only(model, reference, wide, live,
                                          monkeypatch):
    """The decode step selects and attends for the live slots only, a
    chunk of them a trip.  Against the same step in one trip over all
    sixteen slots (every slot read, as before the loop): each live slot's
    log-probs and selection, and the step's count of rows read, are the
    same, and the log-probs are the reference's; idle slots write the
    dump block and nothing else; ``rows_fetched`` on the host copies is
    the chunk times the trips the step's own function makes."""
    eng, B, bs, MB = wide, wide.max_batch, wide.block_size, wide._max_blocks
    tokens = np.ones((B, 1), np.int32)
    positions = np.zeros((B,), np.int32)
    tables = np.zeros((B, MB), np.int32)
    active = np.zeros((B,), bool)
    seqs = {}
    for i in live:
        prompt = tokens_of(100 + i, 9 + 5 * (i % 3))     # all past TOPK
        eng.cache.allocate(-100 - i, prompt.size + 1)
        tok, row = eng._prefill_step_raw(-100 - i, prompt)
        seqs[i] = np.append(prompt, tok)
        tokens[i, 0], positions[i], tables[i], active[i] = (
            tok, prompt.size, row, True)
    try:
        before = [np.array(p) for p in eng.cache.pools]
        chunk = la.slot_chunk(B)
        trips = int(jax.jit(la.live_trips, static_argnums=1)(active, chunk))
        assert trips == max(1, -(-len(live) // chunk))
        assert eng.graph.rows_fetched(positions, active, bs, MB) == (
            chunk * trips * TOPK)
        lp, pools, read, chosen, ok = _decode_live(
            eng, tokens, positions, tables, active)
        monkeypatch.setattr(la, "_SLOT_CHUNK", B)
        lp1, pools1, read1, chosen1, ok1 = _decode_live(
            eng, tokens, positions, tables, active)
    finally:
        for i in live:
            eng.cache.free_seq(-100 - i)
    rows = list(live)
    assert read == read1 == TOPK * len(live)
    np.testing.assert_array_equal(ok, ok1)
    assert (ok.sum(axis=1) == np.where(active, TOPK, 0)).all()
    np.testing.assert_array_equal(np.where(ok, chosen, -1),
                                  np.where(ok1, chosen1, -1))
    np.testing.assert_allclose(lp[rows], lp1[rows], atol=1e-6)
    for n in {s.size for s in seqs.values()}:
        group = [i for i in rows if seqs[i].size == n]
        want = reference(np.stack([seqs[i] for i in group]))[:, -1]
        np.testing.assert_allclose(lp[group], want, atol=2e-5)
    # what the step wrote: the live slots' new rows (the same in both),
    # the dump block (whose junk differs), and nothing else
    for new, old, new1 in zip(pools, before, pools1):
        for p in (new, old, new1):
            p[:, 0] = 0
        np.testing.assert_array_equal(new, new1)
        for p in (new, old):
            p[:, tables[rows, positions[rows] // bs], positions[rows] % bs] = 0
        np.testing.assert_array_equal(new, old)


def test_generate_and_submit_agree_with_the_reference(model, reference):
    eng = engine(model, max_new_tokens=8, deadline_ms=60000)
    eng.warmup()
    prompt = tokens_of(21, 13)
    toks, lps = eng.generate(prompt, max_new_tokens=8, return_logps=True)
    # the tallies are of what the scheduler served: none of generate()
    assert eng.stats()["moe_assignments"] == 0
    assert eng.stats()["attn_context_tokens"] == 0
    want = reference(np.concatenate([prompt, toks])[None])[0]
    for i, lp in enumerate(lps):
        np.testing.assert_allclose(lp, want[prompt.size + i], atol=2e-5)
    seq = eng.generate_sequential(prompt, max_new_tokens=8)
    assert seq == toks
    eng.start()
    try:
        streams = [eng.submit(prompt, max_new_tokens=8),
                   eng.submit(tokens_of(22, 7), max_new_tokens=5)]
        assert streams[0].result(timeout=120) == toks
        assert len(streams[1].result(timeout=120)) == 5
    finally:
        eng.stop(grace=0.0)
    st = eng.stats()
    assert st["completed"] == 2 and st["unaccounted"] == 0
    assert st["prompt_tokens"] == 13 + 7
    # slot by slot and step by step min(TOPK, pos + 1), counted on the
    # device: 7 decode steps from position 13, 4 from position 7
    assert st["attn_context_tokens"] == sum(range(14, 21)) + sum(range(8, 12))
    assert st["attn_selected_tokens"] == TOPK * (7 + 4)
    assert 0 < st["moe_assignments_held"] < st["moe_assignments"]
    assert all(s.retraces == 0 for s in eng.sentinels.values())
    assert _pools_clean(eng.cache)


def _pools_clean(cache):
    return all(bool((np.asarray(p)[:, 1:] == 0).all()) for p in cache.pools)


def _serve_by_hand(eng, arrivals, limit=300):
    """The scheduler's passes made by hand on an engine that was not
    started (so the schedule is the test's): ``arrivals[pass number]`` are
    submitted before that pass; the streams in order of arrival."""
    streams = []
    for n in range(limit):
        for kw in arrivals.get(n, ()):
            streams.append(eng.submit(**kw))
        if (n > max(arrivals) and eng._q.empty() and not eng._pending
                and not eng._any_active()):
            return streams
        if eng._pending or not eng._q.empty():
            eng._admit_waiting(None)
        if eng._any_active():
            eng._decode_iteration(None)
    raise AssertionError(f"still busy after {limit} passes")


@pytest.mark.parametrize("slots", [2, 1], ids=["two_slots", "one_slot"])
def test_streams_served_a_step_ahead_are_generates(model, slots):
    """The family's decode step takes the previous step's ``tok`` from
    the device as the other family's does: over admissions in mid-run,
    ends by length and an end on ``eos_id`` (whose row in the step in
    flight is dropped, and with one slot the slot's next stream never
    sees it) every stream is ``generate()``'s, the counts are still those
    of the live rows' steps, and both pools are scrubbed."""
    ref = engine(model, max_batch=slots)
    plan = [(0, 13, 9, None), (0, 7, 8, 2), (5, 10, 6, None),
            (12, 5, 2, None), (14, 9, 1, None)]
    arrivals, want = {}, []
    for at, n, new, eos_from in plan:
        prompt = tokens_of(60 + n, n)
        toks = ref.generate(prompt, max_new_tokens=new)
        eos = None
        if eos_from is not None:
            cut = next(i for i in range(eos_from, new)
                       if toks[i] not in toks[:i])
            assert cut < new - 1
            eos = toks[cut]
        want.append(ref.generate(prompt, max_new_tokens=new, eos_id=eos))
        arrivals.setdefault(at, []).append(
            dict(prompt=prompt, max_new_tokens=new, eos_id=eos))
    eng = engine(model, max_batch=slots, deadline_ms=60000)
    eng.warmup()
    streams = _serve_by_hand(eng, arrivals)
    assert [s.result(timeout=1) for s in streams] == want
    st = eng.stats()
    assert st["completed"] == len(plan) and st["unaccounted"] == 0
    assert st["decode_steps_ahead"] > st["decode_steps"] // 2
    assert st["decode_rows_wasted"] == 1
    assert st["decode_pulled_bytes"] == st["decode_steps"] * (
        4 * eng.max_batch + 12)
    # the step's own count of the rows read covers the wasted row too
    # (it was live on the device); the host's context count as well
    assert st["attn_selected_tokens"] >= TOPK * (
        sum(len(w) - 1 for w in want))
    assert all(s.retraces == 0 for s in eng.sentinels.values())
    assert eng._inflight is None and eng.cache.used_blocks == 0
    assert _pools_clean(eng.cache)


def test_both_pools_are_scrubbed_and_fully_aliased(model):
    eng = engine(model)
    eng.warmup()
    a = model.spec.attention
    assert eng.cache.kinds == ("latent", "index")
    assert eng.cache.pool("latent").shape == (5, 25, 4, a.latent_row_width)
    assert eng.cache.pool("index").shape == (2, 25, 4, a.index_head_dim)
    assert a.latent_row_width % 128 == 0 >= a.latent_width - 128
    st = eng.stats()
    assert st["kv_aliased_bytes"] == eng.cache.pool_nbytes == sum(
        st["pool_bytes"].values())
    assert st["cache_bytes_per_token"] * 25 * 4 == eng.cache.pool_nbytes
    eng.generate(tokens_of(31, 10), max_new_tokens=6)
    # the sequence is freed: everything but the dump block is zero again
    assert _pools_clean(eng.cache)
    before = eng.cache.pools
    eng.cache.allocate(7, 9)
    tok, _ = eng._prefill_step_raw(7, tokens_of(32, 9))
    assert all(p.is_deleted() for p in before)            # donated
    held = eng.cache.table(7)
    for pool in eng.cache.pools:
        assert float(np.abs(np.asarray(pool)[:, held]).max()) > 0
    eng.cache.free_seq(7)
    assert _pools_clean(eng.cache)


@pytest.mark.parametrize("given,want", [
    (None, [4, 8, 16, 32, 40]), ([8, 24], [8, 24, 40]),
    ("8,24", [8, 24, 40])])
def test_prefill_ladder_is_the_engines_argument(model, given, want):
    """A deployment gives its ladder at the constructor (or through
    bigdl.lm.prefillBuckets); the default is powers of two from the block
    size, whatever the context, with max_context always in it."""
    eng = engine(model, max_context=40, prefill_buckets=given)
    assert eng._buckets == want
    assert sorted(eng._prefill_specs) == want


def _writers(lyr):
    """The projections of a layer that write to the residual stream."""
    out = {"o": lyr["attn"]["o"]}
    if "ffn" in lyr:
        out["ffn"] = lyr["ffn"]["w2"]
    else:
        out["experts"] = lyr["moe"]["w2"]
        out["shared"] = lyr["moe"]["shared"]["w2"]
    return out


@pytest.mark.parametrize("li", range(len(TYPES)))
def test_init_depth_scales_what_writes_to_the_residual_stream(model, li):
    """``init_depth=d``: the same draws, ``o`` and every ``w2`` times
    ``1 / sqrt(2 d)``; every other parameter as it was."""
    deep = toy(init_depth=8)
    scale = 1 / np.sqrt(2 * 8)
    plain, scaled = model.params["layers"][li], deep.params["layers"][li]
    writers = _writers(plain)
    for name, w in _writers(scaled).items():
        np.testing.assert_allclose(np.asarray(w),
                                   scale * np.asarray(writers[name]),
                                   rtol=1e-6, atol=0)
    same = jax.tree_util.tree_map(
        lambda a, b: bool(np.array_equal(np.asarray(a), np.asarray(b))),
        plain, scaled)
    changed = sum(not x for x in jax.tree_util.tree_leaves(same))
    assert changed == len(writers)
    assert np.array_equal(np.asarray(model.params["head"]),
                          np.asarray(deep.params["head"]))


def test_int8_tier_is_refused_for_the_family(model):
    with pytest.raises(UnsupportedModelError, match="no quantized tier"):
        engine(model, quantize="int8")


def test_context_beyond_the_models_positions_is_refused():
    m = glm_moe_dsa_lm(50, 32, 4, 16, 8, 6, 4, 8, 2, 8, 6, ["full"],
                       ["dense"], 64, 16, 16, 2, max_position_embeddings=32)
    with pytest.raises(ValueError, match="longest position"):
        LMServingEngine(m, max_batch=1, max_context=64, block_size=4)


def _scopes(lowered):
    import re
    return set(re.findall(r'layer\d+/[\w/]+', lowered.as_text(debug_info=True)))


def test_steps_carry_the_layer_scopes(model):
    """Every scope of a layer's attention in both steps; in decode the
    selection, the gather and the core run in the loop over live slots,
    whose body's paths gain ``while/body``."""
    eng = engine(model)
    dec = _scopes(eng._decode_cached.lower(*eng._decode_specs))
    pre = _scopes(eng._prefill_cached.lower(
        *eng._prefill_specs[eng._buckets[-1]]))
    flat = {s.replace("while/body/", "") for s in dec}
    for scope in ("q_latent", "kv_latent", "indexer", "select",
                  "latent_gather", "core", "out"):
        assert any(s.startswith(f"layer0/attn/{scope}") for s in flat), scope
    for scope in ("select", "latent_gather", "core"):
        assert any(s.startswith(f"layer0/attn/while/body/{scope}")
                   for s in dec), scope
    for scope in ("q_latent", "kv_latent", "indexer", "select", "kv_expand",
                  "core", "out"):
        assert any(s.startswith(f"layer4/attn/{scope}") for s in pre), scope
    for steps in (dec, pre):
        for scope in ("router", "dispatch", "experts", "shared", "combine"):
            assert any(s.startswith(f"layer1/moe/{scope}") for s in steps)
        assert not any(s.startswith("layer1/attn/indexer") for s in steps)
        assert any(s.startswith("layer0/ffn") for s in steps)


# ---------------------------------------------------------------------------
# what a model holds per parameter
# ---------------------------------------------------------------------------


def _live_bytes():
    gc.collect()
    return sum(a.nbytes for a in jax.live_arrays())


def test_engine_holds_the_weights_once_and_the_pools():
    base = _live_bytes()
    m = toy(param_dtype=jnp.bfloat16, key=5)
    eng = engine(m, max_batch=4, max_context=64)
    weights = sum(l.nbytes for l in jax.tree_util.tree_leaves(m.params))
    assert weights == 2 * m.n_parameters()
    assert all(l.dtype == jnp.bfloat16
               for l in jax.tree_util.tree_leaves(m.params))
    assert eng.cache.pools[0].dtype == jnp.bfloat16
    held = _live_bytes() - base
    want = weights + eng.cache.pool_nbytes
    assert abs(held - want) <= 0.05 * want, (held, want)
    # and says so itself: what engine_hbm_gb.serve reads
    st = eng.stats()
    assert st["weight_bytes"] == weights
    assert st["weight_bytes"] + sum(st["pool_bytes"].values()) == want
    # of those, what the products read: all but the embedding table and
    # the vectors (norms, the router's bias), at the bf16 they are held
    dots = sum(l.nbytes for l in jax.tree_util.tree_leaves(
        {k: v for k, v in m.params.items() if k != "embed"})
        if l.ndim >= 2)
    assert st["dot_weight_bytes"] == dots
    assert 0 < dots < weights - m.params["embed"].nbytes
    assert m._grads_tree is None
    eng.warmup()
    out = eng.generate(tokens_of(41, 12), max_new_tokens=4)
    assert len(out) == 4 and m._grads_tree is None
    del eng


def test_bf16_model_stays_close_to_its_float32_twin(reference):
    """The same seed at bf16: the initialisers' float32 values rounded,
    and a forward within bf16's reach of the float32 one."""
    m16 = toy(param_dtype=jnp.bfloat16)
    m32 = toy()
    for a, b in zip(jax.tree_util.tree_leaves(m16.params),
                    jax.tree_util.tree_leaves(m32.params)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b.astype(jnp.bfloat16),
                                                 np.float32))
    x = tokens_of(9, 2, 5)
    m16.evaluate()
    got = np.asarray(m16.forward(jnp.asarray(x)))
    assert got.dtype == np.float32
    assert np.abs(got - reference(x)).max() < 0.25


def test_grads_are_allocated_on_first_use():
    import bigdl_tpu.nn as nn
    m = nn.Sequential().add(nn.Linear(4, 3)).add(nn.Tanh()).add(
        nn.Linear(3, 2))
    m.reset(jax.random.PRNGKey(0))
    assert m._grads_tree is None
    assert all(c._grads_tree is None for c in m.modules())
    m.evaluate()
    m.forward(jnp.ones((2, 4)))
    assert m._grads_tree is None                    # a forward needs none
    g = m.grads                                     # first use
    assert m._grads_tree is g
    assert jax.tree_util.tree_structure(g) == jax.tree_util.tree_structure(
        m.params)
    assert all(float(jnp.abs(l).max()) == 0
               for l in jax.tree_util.tree_leaves(g))
    assert m.children[0]._grads_tree is g[0]        # children adopted
    m.reset(jax.random.PRNGKey(1))
    assert m._grads_tree is None                    # dropped with a reset


@pytest.mark.parametrize("use", ["grads", "parameters", "backward",
                                 "zero_grad_parameters", "get_parameters"])
def test_each_first_use_allocates_the_accumulator(use):
    import bigdl_tpu.nn as nn
    m = nn.Sequential().add(nn.Linear(4, 3))
    m.reset(jax.random.PRNGKey(0))
    x = jnp.ones((2, 4))
    if use == "grads":
        got = m.grads
    elif use == "parameters":
        got = m.parameters()[1]
    elif use == "get_parameters":
        got = m.get_parameters()[1]
    elif use == "zero_grad_parameters":
        m.zero_grad_parameters()
        got = m._grads_tree
    else:
        m.forward(x)
        m.backward(x, jnp.ones((2, 3)))
        got = m._grads_tree
        assert float(jnp.abs(got[0]["weight"]).max()) > 0
    assert got is not None and m._grads_tree is not None


def test_a_childs_own_accumulation_survives_the_parents_first_use():
    import bigdl_tpu.nn as nn
    lin = nn.Linear(4, 3)
    m = nn.Sequential().add(lin)
    m.reset(jax.random.PRNGKey(0))
    x = jnp.ones((2, 4))
    lin.forward(x)
    lin.backward(x, jnp.ones((2, 3)))
    assert m._grads_tree is None
    assert m.grads[0] is lin._grads_tree
    assert float(jnp.abs(m.grads[0]["weight"]).max()) > 0


def test_param_dtype_on_any_module():
    """Under a narrow param_dtype reset() runs the initialisers as one
    compiled call; the values are the float32 initialisers', rounded."""
    import bigdl_tpu.nn as nn
    m = nn.Sequential().add(nn.Linear(4, 3)).add(nn.Linear(3, 2))
    m.set_param_dtype(jnp.bfloat16)
    m.reset(jax.random.PRNGKey(0))
    assert all(l.dtype == jnp.bfloat16
               for l in jax.tree_util.tree_leaves(m.params))
    assert m.children[1].params["weight"] is m.params[1]["weight"]
    wide = nn.Sequential().add(nn.Linear(4, 3)).add(nn.Linear(3, 2))
    wide.reset(jax.random.PRNGKey(0))
    for a, b in zip(jax.tree_util.tree_leaves(m.params),
                    jax.tree_util.tree_leaves(wide.params)):
        assert b.dtype == jnp.float32
        np.testing.assert_array_equal(
            np.asarray(a, np.float32),
            np.asarray(b.astype(jnp.bfloat16), np.float32))


def test_pickle_from_before_the_accumulator_was_lazy():
    import pickle

    import bigdl_tpu.nn as nn
    m = nn.Linear(4, 3)
    m.reset(jax.random.PRNGKey(0))
    state = m.__getstate__()
    assert state["_grads_tree"] is None
    state.pop("_grads_tree")
    state["_grads"] = jax.tree_util.tree_map(np.ones_like, state["_params"])
    old = nn.Linear.__new__(nn.Linear)
    old.__setstate__(state)
    assert float(old.grads["weight"].min()) == 1.0
    m.zero_grad_parameters()
    again = pickle.loads(pickle.dumps(m))
    assert again._grads_tree is not None
