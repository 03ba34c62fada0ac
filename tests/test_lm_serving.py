"""LM token serving: continuous batching over a paged KV cache.

The claims under test (ISSUE 18 acceptance criteria): the paged pool's
three invariants (structured exhaustion — never OOM; dump block never
allocated; freed blocks zero-scrubbed, bit-asserted); the paged decode
path's parity with a teacher-forced full forward (greedy tokens
bit-identical, per-position log-probs allclose); iteration-level
batching legible in the decode-step/token ratio with zero post-warmup
retraces across prefill AND decode; per-request streaming with
partially-streamed-then-failed as a first-class outcome; the chaos trio
(``poisonPromptAt`` / ``hangDecodeAt`` / ``evictBlockAt``) and the
combined-chaos accounting identity ``completed + shed + rejected +
quarantined == submitted``, exact; and the int8 decode tier's admission
gate (auditor precision pass + fp-vs-int8 logits allclose — either
failing refuses to serve quantized).
"""

import os
import re
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu.serving import lm as _lm
from bigdl_tpu.models.transformer import transformer_lm
from bigdl_tpu.serving import (LMServingEngine, Overloaded, PagedKVCache,
                               QuantizationGateError, UnsupportedModelError,
                               run_lm_open_loop, sample_lm_workload)
from bigdl_tpu.serving.engine import (DeadlineExceeded, HungDispatchError,
                                      OUTCOMES, ServingDataError,
                                      ServingInfraError)
from bigdl_tpu.serving.kv_cache import DUMP_BLOCK
from bigdl_tpu.utils import chaos, config, elastic

VOCAB = 32

_LM_KEYS = (
    "bigdl.analysis.retrace", "bigdl.lm.stallFactor",
    "bigdl.lm.warmupSteps", "bigdl.lm.quantizeRtol",
    "bigdl.lm.quantizeAtol", "bigdl.lm.prefillBuckets",
    "bigdl.chaos.poisonPromptAt", "bigdl.chaos.hangDecodeAt",
    "bigdl.chaos.evictBlockAt", "bigdl.chaos.burstArrivals",
)


@pytest.fixture(autouse=True)
def _lm_env():
    """Disarmed chaos, cleared preemption, clean knobs around every
    test."""
    elastic.clear_preemption()
    yield
    chaos.uninstall()
    elastic.clear_preemption()
    for k in _LM_KEYS:
        config.clear_property(k)


def _model(seed=3, vocab=VOCAB, **kw):
    kw.setdefault("d_model", 16)
    kw.setdefault("n_head", 2)
    kw.setdefault("n_layers", 2)
    kw.setdefault("max_len", 64)
    m = transformer_lm(vocab, **kw)
    m.reset(jax.random.PRNGKey(seed))
    return m


def _engine(model=None, warm=True, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_context", 32)
    kw.setdefault("block_size", 4)
    kw.setdefault("deadline_ms", 30000.0)
    eng = LMServingEngine(model if model is not None else _model(), **kw)
    if warm:
        eng.warmup()
    return eng


def _prompt(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(1, VOCAB + 1, size=n).astype(np.int32)


def _assert_identity(stats_or_rec):
    assert stats_or_rec["unaccounted"] == 0, stats_or_rec
    total = sum(stats_or_rec[o] for o in OUTCOMES)
    assert total == stats_or_rec["submitted"], stats_or_rec


#: the combined-chaos plan's hung decode step.  It must be a step the
#: load is SURE to reach: the seed-9 workload decodes 58 tokens, less 3
#: for the poisoned prompt and at most 7 for the evicted stream, over 4
#: slots — 12 steps when arrivals batch perfectly, more when they do
#: not.  (It was 20, which a host whose decode step outlasts the 5 ms
#: arrival gap never reaches: 17 steps on jax 0.9.0's CPU backend.)
_COMBINED_HANG = "12:3.0"
#: and its watchdog's stall factor.  20 x a 2.4 ms step is 47 ms, which a
#: step on a loaded host can pass with no hang at all (a 49 ms prefill did,
#: in a whole-suite run: the spurious abort is swallowed as that prompt's
#: own shed, and the load then ends short of the hung step); 100 x is a
#: quarter of a second, still a twelfth of the 3 s hang
_COMBINED_STALL = 100.0


def _zero_retraces(eng):
    retr = {label: s.retraces for label, s in eng.sentinels.items()}
    assert retr and all(v == 0 for v in retr.values()), \
        f"post-warmup retraces: {retr}"


# ---------------------------------------------------------------------------
# paged KV cache invariants
# ---------------------------------------------------------------------------

class TestPagedKVCache:
    def test_exhaustion_is_structured_overloaded_never_oom(self):
        cache = PagedKVCache.kv(2, 2, 8, n_blocks=4, block_size=4)
        cache.allocate(1, 12)                     # 3 blocks = the pool
        with pytest.raises(Overloaded) as ei:
            cache.allocate(2, 8)                  # needs 2, 0 free
        assert ei.value.retriable
        assert ei.value.blocks_needed == 2 and ei.value.blocks_free == 0
        cache.free_seq(1)
        assert cache.can_allocate(8)              # retriable for real

    def test_dump_block_never_allocated(self):
        cache = PagedKVCache.kv(2, 2, 8, n_blocks=5, block_size=4)
        blocks = cache.allocate(1, 16)            # the whole free-list
        assert DUMP_BLOCK not in blocks
        assert sorted(blocks) == [1, 2, 3, 4]

    def test_block_reuse_is_zero_initialized_bitwise(self):
        cache = PagedKVCache.kv(2, 2, 8, n_blocks=5, block_size=4)
        blocks = cache.allocate(7, 10)
        # simulate a decode having written k/v into the blocks
        cache.pools = (cache.k.at[:, np.array(blocks)].set(1.5),
                       cache.v.at[:, np.array(blocks)].set(-2.25))
        assert float(np.abs(np.asarray(cache.k[:, blocks])).max()) > 0
        cache.free_seq(7)
        # the scrub is the no-cross-request-leakage proof: bit-exact zero
        assert (np.asarray(cache.k[:, blocks]) == 0).all()
        assert (np.asarray(cache.v[:, blocks]) == 0).all()
        again = cache.allocate(8, 10)
        assert sorted(again) == sorted(blocks)    # same ids, clean bits

    def test_double_allocate_and_idempotent_free(self):
        cache = PagedKVCache.kv(1, 1, 4, n_blocks=3, block_size=2)
        cache.allocate(1, 2)
        with pytest.raises(ValueError, match="already holds"):
            cache.allocate(1, 2)
        assert cache.free_seq(1) == 1
        assert cache.free_seq(1) == 0             # idempotent

    def test_pool_needs_room_beyond_the_dump_block(self):
        with pytest.raises(ValueError, match="dump block"):
            PagedKVCache.kv(1, 1, 4, n_blocks=1, block_size=2)


# ---------------------------------------------------------------------------
# engine construction / validation
# ---------------------------------------------------------------------------

class TestEngineValidation:
    def test_non_lm_model_is_refused_structurally(self):
        m = (nn.Sequential().add(nn.Linear(4, 8)).add(nn.Tanh())
             .add(nn.Linear(8, 3)))
        m.reset(jax.random.PRNGKey(0))
        with pytest.raises(UnsupportedModelError,
                           match="transformer_lm-shaped"):
            LMServingEngine(m)

    def test_max_context_beyond_position_table_is_refused(self):
        with pytest.raises(ValueError, match="PositionalEncoding"):
            LMServingEngine(_model(max_len=64), max_context=128)

    def test_never_fits_prompt_rejected_at_the_door(self):
        # pool of 3 allocatable blocks x 4 slots = 12 tokens max
        eng = _engine(warm=False, cache_blocks=4)
        with pytest.raises(Overloaded, match="kv blocks exhausted"):
            eng.submit(_prompt(8), max_new_tokens=8)     # 16 > 12
        eng.close()
        _assert_identity(eng.stats())

    def test_over_context_prompt_is_quarantined(self):
        with _engine() as eng:
            eng.start()
            s = eng.submit(_prompt(30), max_new_tokens=8)   # 38 > 32
            with pytest.raises(ServingDataError, match="maxContext"):
                s.result(timeout=10)
            assert s.outcome == "quarantined"
            stats = eng.stats()
        _assert_identity(stats)


# ---------------------------------------------------------------------------
# decode-vs-full-forward parity (the paged-path correctness proof)
# ---------------------------------------------------------------------------

#: a table of four chunks of the decode step's attention loop (512
#: positions in blocks of 16: chunks end at 128, 256, 384, 512)
_LONG = dict(max_context=512, block_size=16)


def _long_engine(**kw):
    return _engine(_model(max_len=512), **dict(_LONG, **kw))


def _one_decode_step(eng, fn, dp, prompt, slot):
    """Prefill ``prompt``, then ONE decode step with only ``slot`` live:
    the prefill's token and that slot's row of the step's log-probs."""
    B, MB = eng.max_batch, eng._max_blocks
    seq = eng._offline_seq_id()
    eng.cache.allocate(seq, len(prompt) + 1)
    try:
        tok, row = eng._prefill_step_raw(seq, prompt)
        tokens = np.ones((B, 1), np.int32)
        positions = np.zeros((B,), np.int32)
        tables = np.full((B, MB), DUMP_BLOCK, np.int32)
        active = np.zeros((B,), bool)
        tokens[slot, 0], positions[slot] = tok, len(prompt)
        tables[slot], active[slot] = row, True
        chosen, lp, eng.cache.pools, _ = fn(dp, *eng.cache.pools, tokens,
                                            positions, tables, active)
        lp = np.asarray(lp)
        # the step's own choice is the arg-max of the row it returns
        assert int(np.asarray(chosen)[slot, 0]) == int(np.argmax(lp[slot])) + 1
        return tok, lp[slot]
    finally:
        eng.cache.free_seq(seq)


class TestDecodeParity:
    # prompt, new tokens: decode steps read contexts of prompt + 1 ...
    # prompt + new - 1 rows, across the loop's chunk edges
    @pytest.mark.parametrize("engine,n,new", [
        (_engine, 9, 12),            # inside the first (only) chunk
        (_long_engine, 125, 5),      # 126, 127, 128, 129: over the first edge
        (_long_engine, 253, 5),      # 254 ... 257: over the second
        (_long_engine, 500, 12),     # the last chunk, up to 511 of 512 rows
    ], ids=["one_chunk", "first_edge", "second_edge", "last_chunk"])
    def test_greedy_tokens_bit_identical_logps_allclose(self, engine, n,
                                                        new):
        eng = engine()
        toks_paged, lp_paged = eng.generate(
            _prompt(n, seed=5), max_new_tokens=new, return_logps=True)
        toks_full, lp_full = eng.generate_sequential(
            _prompt(n, seed=5), max_new_tokens=new, return_logps=True)
        assert toks_paged == toks_full          # greedy: bit-identical
        # paged logps cover generated tokens 2..N (the prefill's first
        # token has no decode row); sequential covers 1..N
        assert len(lp_paged) == len(lp_full) - 1
        for a, b in zip(lp_paged, lp_full[1:]):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
        eng.close()

    @pytest.mark.parametrize("n,slot", [(127, 0), (128, 3), (129, 1),
                                        (383, 2), (511, 3)])
    def test_one_live_slot_among_inactive_matches_full_forward(self, n,
                                                               slot):
        """The longest context one short of a chunk's edge, at it, past
        it, and at ``max_context``; the live slot is not always the
        first."""
        eng = _long_engine()
        prompt = _prompt(n, seed=n)
        tok, got = _one_decode_step(eng, eng._decode, eng._dp, prompt, slot)
        padded = np.ones((1, 512), np.int32)
        padded[0, :n], padded[0, n] = prompt, tok
        want = np.asarray(eng._full(eng._dp, padded))[n]
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        assert int(np.argmax(got)) == int(np.argmax(want))
        eng.close()

    @pytest.mark.parametrize("lengths", [(3, 130, 260, 480), (200,),
                                         (127, 128, 129)],
                             ids=["spread", "alone", "at_an_edge"])
    def test_slots_of_different_lengths_share_a_step(self, lengths):
        """The scheduler's steps hold contexts of very different lengths
        at once (the loop runs to the longest; every shorter slot's later
        chunks are masked whole): each stream is the sequential
        baseline's."""
        config.set_property("bigdl.analysis.retrace", "strict")
        with _long_engine() as eng:
            eng.start()
            streams = [eng.submit(_prompt(n, seed=n), max_new_tokens=6)
                       for n in lengths]
            outs = [s.result(timeout=60) for s in streams]
            stats = eng.stats()
            _zero_retraces(eng)
        _assert_identity(stats)
        if len(lengths) > 1:            # the slots did share iterations
            assert stats["decode_steps"] < (stats["tokens_out"]
                                            - stats["prefills"]), stats
        ref = _long_engine()
        for n, o in zip(lengths, outs):
            assert o == ref.generate_sequential(_prompt(n, seed=n),
                                                max_new_tokens=6)
        ref.close()

    def test_generate_crosses_every_bound_in_one_program(self):
        """100 -> 400 rows of context: the loop makes 1, 2, 3 and 4 trips
        in ONE ``lm_decode`` program at one signature, no retrace."""
        config.set_property("bigdl.analysis.retrace", "strict")
        eng = _long_engine()
        toks = eng.generate(_prompt(100, seed=2), max_new_tokens=300)
        assert toks == eng.generate_sequential(_prompt(100, seed=2),
                                               max_new_tokens=300)
        _zero_retraces(eng)
        assert len(eng.timings["lm_decode"]) == 1
        eng.close()

    def test_mixed_prompt_lengths_share_one_decode_shape(self):
        eng = _engine()
        for n in (1, 3, 8, 17):
            assert eng.generate(_prompt(n, seed=n), max_new_tokens=4) == \
                eng.generate_sequential(_prompt(n, seed=n),
                                        max_new_tokens=4)
        _zero_retraces(eng)
        eng.close()

    def test_generate_refused_while_scheduler_runs(self):
        with _engine() as eng:
            eng.start()
            with pytest.raises(ServingInfraError, match="offline"):
                eng.generate(_prompt(4))


# ---------------------------------------------------------------------------
# the decode step reads the live slots only, each group of them only as far
# as the group's own longest context
# ---------------------------------------------------------------------------

#: slots a trip of the decode step's attention reads
_G = _lm._KV_GROUP
#: slots of the engines that hold more than one group
_WIDE = 16


def _chunks_of(eng):
    from bigdl_tpu.serving.lm import _chunk_blocks
    cb = _chunk_blocks(eng.block_size, eng._max_blocks)
    return cb, cb * eng.block_size


def _trips_of(positions, active, chunk, group):
    """Chunks each trip reads, counted here without the program: the
    contexts longest first, a group of them a trip, each group as far as
    its first member, at least one chunk and one trip."""
    ctx = sorted(np.where(active, positions + 1, 0), reverse=True)
    trips = max(1, -(-int(active.sum()) // group))
    return [max(1, -(-int(ctx[t * group]) // chunk)) for t in range(trips)]


def _wide_engine(**kw):
    return _long_engine(max_batch=_WIDE, **kw)


def _live_inputs(eng, live, seed=0):
    """Inputs of one decode step with the slots of ``live`` (slot ->
    position) active: random tokens, random positions for the idle slots
    too, every slot's table blocks of its own, and pools of random
    values."""
    rng = np.random.default_rng(seed)
    B, MB = eng.max_batch, eng._max_blocks
    tokens = rng.integers(1, VOCAB + 1, size=(B, 1)).astype(np.int32)
    positions = rng.integers(0, MB * eng.block_size, size=B).astype(np.int32)
    active = np.zeros((B,), bool)
    for s, p in live.items():
        positions[s], active[s] = p, True
    tables = (1 + rng.permutation(B * MB)).reshape(B, MB).astype(np.int32)
    pools = [rng.standard_normal(p.shape).astype(np.float32)
             for p in eng.cache.pools]
    return tokens, positions, tables, active, pools


class TestBoundedFetch:
    def test_host_and_device_agree_on_the_trip_count(self):
        """``LM/kv_rows_fetched`` is counted on the host by the function
        the step itself calls on the device: the two agree on every
        input, no live slot included, and with what is counted here
        without the program; the order is every slot longest first, ties
        in slot order, padded with the slot written nowhere."""
        from bigdl_tpu.serving.lm import _group_chunks
        rng = np.random.default_rng(0)
        for B, group in ((16, 2), (16, _G), (16, 16), (10, 4)):
            on_device = jax.jit(
                lambda p, a: _group_chunks(p, a, 128, group))
            n_groups = -(-B // group)
            for _ in range(30):
                positions = rng.integers(0, 2048, size=B).astype(np.int32)
                active = rng.random(B) < rng.random()
                want = _trips_of(positions, active, 128, group)
                want += [0] * (n_groups - len(want))
                ctx = np.where(active, positions + 1, 0)
                order = sorted(range(B), key=lambda s: (-ctx[s], s))
                order += [B] * (n_groups * group - B)
                for got in (_group_chunks(positions, active, 128, group, np),
                            on_device(jnp.asarray(positions),
                                      jnp.asarray(active))):
                    np.testing.assert_array_equal(np.asarray(got[1]), want)
                    np.testing.assert_array_equal(np.asarray(got[0]), order)

    @pytest.mark.parametrize("n,trips", [(100, 1), (128, 2), (300, 3),
                                         (511, 4)])
    def test_step_reads_the_chunks_the_host_counts_and_no_more(self, n,
                                                               trips):
        """The step's own trips, seen from outside.  A group of ``_G``
        slots at 500 positions reads four chunks; slot 1, at ``n``, heads
        the next group, which reads its own ``trips``.  A table entry of
        slot 1 past its group's bound may point at a block of NaN and
        nothing changes (never read); one in the last chunk inside the
        bound, past the slot's own position and so masked, turns its row
        NaN (read: 0 x NaN).  Every table entry of a slot no trip reaches
        may point at NaN, and every row, its own included, stays finite.
        ``rows_fetched`` counts exactly those chunks."""
        eng = _long_engine(max_batch=3 * _G)
        cb, chunk = _chunks_of(eng)
        B, MB = eng.max_batch, eng._max_blocks
        long_slots = list(range(B - _G, B))
        positions = np.zeros((B,), np.int32)
        active = np.zeros((B,), bool)
        positions[long_slots], active[long_slots] = 500, True
        positions[1], active[1] = n, True
        assert eng.graph.rows_fetched(positions, active, eng.block_size,
                                      MB) == _G * chunk * (4 + trips)
        poison = eng.cache.n_blocks - 1
        eng.cache.pools = tuple(p.at[:, poison].set(np.nan)
                                for p in eng.cache.pools)

        def step(tables):
            tokens = np.ones((B, 1), np.int32)
            _, lp, eng.cache.pools, _ = eng._decode(
                eng._dp, *eng.cache.pools, tokens, positions, tables, active)
            return np.asarray(lp)

        tables = np.full((B, MB), DUMP_BLOCK, np.int32)
        for i, s in enumerate(long_slots + [1]):
            end = positions[s] // eng.block_size + 1
            tables[s, :end] = 1 + i * MB + np.arange(end)
        clean = step(tables)
        assert np.isfinite(clean).all()
        beyond = tables.copy()
        beyond[1, trips * cb:] = poison           # slot 1's later chunks
        np.testing.assert_array_equal(step(beyond), clean)
        unread = np.arange(B)[~active][_G - 1:]   # past the second group
        assert len(unread) == B - 2 * _G
        nobody = tables.copy()
        nobody[unread] = poison
        got = step(nobody)
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got[active], clean[active])
        if trips * chunk > n + 1 + eng.block_size:
            inside = tables.copy()
            inside[1, trips * cb - 1] = poison    # masked, but fetched
            row = step(inside)
            assert np.isnan(row[1]).all()
            np.testing.assert_array_equal(row[long_slots], clean[long_slots])
        eng.close()

    def test_counter_is_the_sum_of_the_steps_own_counts(self):
        from bigdl_tpu import telemetry
        with _long_engine(max_batch=2 * _G) as eng:
            _, chunk = _chunks_of(eng)
            real, seen = eng._decode, []

            def spy(dp, k, v, tokens, positions, tables, active):
                seen.append((positions.copy(), active.copy()))
                return real(dp, k, v, tokens, positions, tables, active)

            eng._decode = spy
            before = telemetry.counter("LM/kv_rows_fetched").value
            eng.start()
            # three long-lived streams, one crossing 384, and a group's
            # worth of short-lived ones: one trip, then two, then one again
            lengths = (5, 124, 380) + tuple(30 + 40 * i for i in range(_G))
            streams = [eng.submit(_prompt(n, seed=n),
                                  max_new_tokens=8 if i < 3 else 3)
                       for i, n in enumerate(lengths)]
            for s in streams:
                s.result(timeout=60)
            stats = eng.stats()
        want = sum(_G * chunk * sum(_trips_of(p, a, chunk, _G))
                   for p, a in seen)
        assert len(seen) == stats["decode_steps"] and want > 0
        assert stats["kv_rows_fetched"] == want
        assert telemetry.counter("LM/kv_rows_fetched").value - before == want
        # contexts crossed a chunk's edge and more than a group was live
        # at once: the steps did not all read alike, and never fewer rows
        # than the live contexts hold
        assert len({tuple(_trips_of(p, a, chunk, _G)) for p, a in seen}) > 1
        assert max(int(a.sum()) for _, a in seen) > _G
        assert stats["kv_rows_fetched"] >= stats["attn_context_tokens"] > 0
        # offline generate() is not the scheduler's: it counts nothing
        eng2 = _long_engine()
        eng2.generate(_prompt(9), max_new_tokens=4)
        assert eng2.stats()["kv_rows_fetched"] == 0
        eng2.close()

    @pytest.mark.parametrize("quantize", ["off", "int8"])
    def test_lowered_decode_holds_no_whole_layer_of_a_pool(self, quantize):
        """No slice, copy or gather in the lowered step has a whole layer
        of a pool, or every slot's whole table, as its result: the only
        arrays of a pool's extent are the 5-D pools themselves; and the
        loop's body gathers one chunk of a group's tables, not of every
        slot's."""
        eng = _wide_engine(quantize=quantize)
        cached, specs = ((eng._decode_q_cached, eng._decode_q_specs)
                         if quantize == "int8"
                         else (eng._decode_cached, eng._decode_specs))
        text = cached.lower(*specs).as_text()
        L, NB, bs, H, Dh = eng.cache.k.shape
        B, MB = eng.max_batch, eng._max_blocks
        cb, chunk = _chunks_of(eng)
        assert f"tensor<{L}x{NB}x{bs}x{H}x{Dh}xf32>" in text
        for shape in ((NB, bs, H, Dh), (1, NB, bs, H, Dh),
                      (B, MB, bs, H, Dh), (B, MB * bs, H, Dh),
                      (B, cb, bs, H, Dh)):
            assert "tensor<" + "x".join(map(str, shape)) + "xf32>" \
                not in text, shape
        # what the loop's body does gather: one chunk of a group's tables
        assert f"tensor<{_G}x{cb}x{bs}x{H}x{Dh}xf32>" in text
        assert "stablehlo.while" in text
        eng.close()

    @pytest.mark.parametrize("n,slot", [(127, 2), (129, 0), (400, 1)])
    def test_int8_tier_reads_the_same_rows(self, n, slot):
        """The int8 tier is the same function over another pytree: on
        identical pools its row stays within the gate's tolerance of the
        fp step's, chunk edges or not."""
        eng = _long_engine(quantize="int8")
        prompt = _prompt(n, seed=n)
        _, fp = _one_decode_step(eng, eng._decode, eng._dp, prompt, slot)
        _, q = _one_decode_step(eng, eng._decode_q, eng._dp_q, prompt, slot)
        np.testing.assert_allclose(q, fp, rtol=eng.quantize_rtol,
                                   atol=eng.quantize_atol)
        toks = eng.generate(prompt, max_new_tokens=4)
        assert len(toks) == 4
        _zero_retraces(eng)
        assert len(eng.timings["lm_decode_int8"]) == 1
        eng.close()


#: live slot -> position, in a step of ``_WIDE`` slots: how many are live
#: (none, one, three, a group, a group and one, all), where the longest
#: sits, and a group whose members end in different chunks of its trips
_OCCUPANCY = {
    "none": {},
    "one": {13: 300},
    "three, longest last": {1: 40, 6: 130, 15: 390},
    "a group, each in its own chunk": {
        (3 + 5 * i) % _WIDE: 20 + 491 * i // max(_G - 1, 1)
        for i in range(_G)},
    "a group and one, longest in the middle": {
        (11 * i) % _WIDE: (61 * i + 7) % 512 for i in range(_G + 1)},
    "all": {s: (97 * s + 5) % 512 for s in range(_WIDE)},
}


@pytest.fixture(scope="module", params=["off", "int8"])
def wide_tier(request):
    eng = _wide_engine(quantize=request.param)
    yield eng
    eng.close()


class TestSlotGroups:
    @pytest.mark.parametrize("live", list(_OCCUPANCY), ids=list(_OCCUPANCY))
    def test_grouped_step_equals_the_all_slot_step(self, wide_tier, live,
                                                   monkeypatch):
        """The served step, its slots read in groups of ``_G``, against
        the same step built here with one group of every slot (what
        ``paged_attention`` read before the groups: every slot as far as
        the longest live context), on the same float32 pools: every live
        row's log-probs agree to 1e-6 and its token is the same, and the
        pools differ in the dump block alone."""
        eng = wide_tier
        fn, dp = _tier(eng)
        B = eng.max_batch
        monkeypatch.setattr(_lm, "_KV_GROUP", B)
        every_slot = jax.jit(_lm._choosing(_lm._pooled(_lm._build_decode_fn(
            eng.graph, eng.block_size, eng._max_blocks))))
        tokens, positions, tables, active, pools = _live_inputs(
            eng, _OCCUPANCY[live], seed=len(live))
        got = fn(dp, *map(jnp.asarray, pools), tokens, positions, tables,
                 active)
        want = every_slot(dp, *map(jnp.asarray, pools), tokens, positions,
                          tables, active)
        (tok, lp, got_pools, _), (tok_w, lp_w, want_pools, _) = (
            jax.tree_util.tree_map(np.asarray, got),
            jax.tree_util.tree_map(np.asarray, want))
        np.testing.assert_allclose(lp[active], lp_w[active], rtol=0,
                                   atol=1e-6)
        np.testing.assert_array_equal(tok[active], tok_w[active])
        for g, w in zip(got_pools, want_pools):
            assert np.isfinite(g).all()
            np.testing.assert_array_equal(np.delete(g, DUMP_BLOCK, axis=1),
                                          np.delete(w, DUMP_BLOCK, axis=1))
        assert np.isfinite(lp).all()

    def test_pools_stay_finite_and_one_program_serves_every_live_count(self):
        """Steps at 1 to ``_WIDE`` live slots, one after another on the
        same pools: the slots no trip reaches write the dump block, and it
        and every pool stay free of NaN; one ``lm_decode`` program serves
        every count, no retrace."""
        config.set_property("bigdl.analysis.retrace", "strict")
        eng = _wide_engine()
        B = eng.max_batch
        tokens, _, tables, _, pools = _live_inputs(eng, {}, seed=1)
        eng.cache.pools = tuple(map(jnp.asarray, pools))
        rng = np.random.default_rng(1)
        for live in range(1, B + 1):
            active = np.zeros((B,), bool)
            active[rng.permutation(B)[:live]] = True
            positions = rng.integers(0, 512, size=B).astype(np.int32)
            tok, lp, eng.cache.pools, _ = eng._decode(
                eng._dp, *eng.cache.pools, tokens, positions, tables, active)
            assert np.isfinite(np.asarray(lp)).all(), live
            for p in eng.cache.pools:
                assert np.isfinite(np.asarray(p)).all(), live
            tokens = np.asarray(tok)
        _zero_retraces(eng)
        assert len(eng.timings["lm_decode"]) == 1
        eng.close()


# ---------------------------------------------------------------------------
# the steps choose their token on the device; the scheduler pulls only that
# ---------------------------------------------------------------------------

def _tier(eng):
    return ((eng._decode_q, eng._dp_q) if eng._dp_q is not None
            else (eng._decode, eng._dp))


class TestDeviceChoosesToken:
    @pytest.mark.parametrize("quantize", ["off", "int8"])
    def test_step_token_is_the_argmax_of_its_own_row(self, quantize):
        """A run of decode steps with live and idle slots side by side,
        each fed the step's own tokens: ``tok`` is ``argmax(lp) + 1`` of
        the ``lp`` the same call returns, bit for bit, in every row (an
        idle slot's junk row included), on the fp and the int8 tier."""
        eng = _engine(quantize=quantize)
        fn, dp = _tier(eng)
        B, MB = eng.max_batch, eng._max_blocks
        live = {0: _prompt(5, seed=1), 2: _prompt(11, seed=2)}
        tokens = np.ones((B, 1), np.int32)
        positions = np.zeros((B,), np.int32)
        tables = np.full((B, MB), DUMP_BLOCK, np.int32)
        active = np.zeros((B,), bool)
        for slot, prompt in live.items():
            eng.cache.allocate(-100 - slot, len(prompt) + 8)
            tok, row = eng._prefill_step_raw(-100 - slot, prompt)
            tokens[slot, 0], positions[slot] = tok, len(prompt)
            tables[slot], active[slot] = row, True
        seen = set()
        for step in range(7):
            if step == 4:
                active[2] = False         # a slot falls idle mid-run
            tok, lp, eng.cache.pools, aux = fn(
                dp, *eng.cache.pools, tokens, positions, tables, active)
            assert aux is None
            tok, lp = np.asarray(tok), np.asarray(lp)
            # a column: the shape of the step's own ``tokens`` argument
            assert tok.shape == (B, 1) and tok.dtype == np.int32
            assert lp.shape == (B, VOCAB) and lp.dtype == np.float32
            np.testing.assert_array_equal(tok[:, 0],
                                          np.argmax(lp, axis=-1) + 1)
            tokens[active] = tok[active]
            positions[active] += 1
            seen.update(tok[active, 0].tolist())
        assert len(seen) > 1                  # not one token all along
        for slot in live:
            eng.cache.free_seq(-100 - slot)
        _zero_retraces(eng)
        eng.close()

    def test_prefill_token_is_the_argmax_of_its_own_row(self):
        eng = _engine()
        prompt = _prompt(9, seed=4)
        eng.cache.allocate(-7, 10)
        padded = np.ones((1, eng._prefill_bucket(9)), np.int32)
        padded[0, :9] = prompt
        row = np.full((eng._max_blocks,), DUMP_BLOCK, np.int32)
        blocks = eng.cache.table(-7)
        row[:len(blocks)] = blocks
        tok, lp, eng.cache.pools, _ = eng._prefill(
            eng._dp, *eng.cache.pools, padded, np.int32(9), row)
        assert np.asarray(tok).shape == () and np.asarray(lp).shape == (VOCAB,)
        assert int(tok) == int(np.argmax(np.asarray(lp))) + 1
        eng.cache.free_seq(-7)
        eng.cache.allocate(-8, 10)
        assert eng._prefill_step_raw(-8, prompt)[0] == int(tok)
        eng.cache.free_seq(-8)
        eng.close()

    @pytest.mark.parametrize("rows", [
        [[0.0, -1.0, 0.5, 0.5, -2.0, 0.5]],               # three-way tie
        [[-3.0] * 6],                                     # all equal
        [[-np.inf, -np.inf, -1.0, -np.inf, -1.0, -np.inf],
         [-np.inf] * 6],                                  # ties at -inf
    ], ids=["tie", "flat", "minus_inf"])
    def test_first_index_wins_an_exact_tie(self, rows):
        """The one place every family's step gets its token, over a stub
        step: first index on a tie, as ``np.argmax`` on the host had it."""
        from bigdl_tpu.serving.lm import _choosing
        lp = np.asarray(rows, np.float32)

        def stub(dp, pool, x):
            return x, (pool,), None

        tok, out, pools, aux = jax.jit(_choosing(stub))(None, np.zeros(2),
                                                        lp)
        np.testing.assert_array_equal(np.asarray(tok)[:, 0],
                                      np.argmax(lp, axis=-1) + 1)
        assert np.asarray(tok).dtype == np.int32 and aux is None
        np.testing.assert_array_equal(np.asarray(out), lp)
        assert len(pools) == 1

    def test_scheduler_pulls_max_batch_integers_a_step(self):
        """One explicit pull a decode step and one a prefill, and what
        crosses in a decode step is ``4 x max_batch`` bytes: the counter,
        ``stats()`` and the span's ``bytes`` agree.  ``generate()`` is
        not the scheduler's and counts nothing."""
        from bigdl_tpu import telemetry
        from bigdl_tpu.analysis import hostsync
        with _engine() as eng:
            before = telemetry.counter("LM/decode_pulled_bytes").value
            d2h = telemetry.counter("LM/d2h_bytes").value
            eng.generate(_prompt(6), max_new_tokens=5)
            assert eng.stats()["decode_pulled_bytes"] == 0
            assert telemetry.counter("LM/decode_pulled_bytes").value == before
            # offline: a scalar from the prefill, max_batch int32 a step
            assert telemetry.counter("LM/d2h_bytes").value - d2h == \
                4 + 4 * 4 * eng.max_batch
            d2h = telemetry.counter("LM/d2h_bytes").value
            pulls = hostsync.STATS.explicit_pulls
            eng.start()
            streams = [eng.submit(_prompt(n, seed=n), max_new_tokens=7)
                       for n in (4, 9, 13)]
            for s in streams:
                s.result(timeout=60)
            eng.stop()
            stats = eng.stats()
            pulls = hostsync.STATS.explicit_pulls - pulls
        per_step = 4 * eng.max_batch
        assert stats["decode_steps"] > 0 and stats["prefills"] == 4
        assert stats["decode_pulled_bytes"] == stats["decode_steps"] * per_step
        assert telemetry.counter("LM/decode_pulled_bytes").value - before == \
            stats["decode_pulled_bytes"]
        assert pulls == stats["decode_steps"] + 3
        assert telemetry.counter("LM/d2h_bytes").value - d2h == \
            stats["decode_pulled_bytes"] + 3 * 4
        waits = [e for e in telemetry.events()
                 if e.get("name") == "lm/decode_wait"]
        assert len(waits) == stats["decode_steps"]
        assert {e["args"]["bytes"] for e in waits} == {per_step}

    @pytest.mark.parametrize("quantize", ["off", "int8"])
    def test_generate_returns_the_rows_only_when_asked(self, quantize):
        """(c), (d): the same tokens with and without ``return_logps``,
        from the scheduler too; each row is bit-equal to the whole
        ``(max_batch, vocab)`` pull of the same step made by hand (what
        ``generate()`` pulled on every step before), and the token is its
        arg-max."""
        eng = _engine(quantize=quantize)
        fn, dp = _tier(eng)
        prompt = _prompt(7, seed=9)
        toks = eng.generate(prompt, max_new_tokens=9)
        again, rows = eng.generate(prompt, max_new_tokens=9,
                                   return_logps=True)
        assert toks == again and len(rows) == 8
        assert [int(np.argmax(r)) + 1 for r in rows] == toks[1:]
        B, MB = eng.max_batch, eng._max_blocks
        seq = eng._offline_seq_id()
        eng.cache.allocate(seq, len(prompt) + 9)
        tok, row = eng._prefill_step_raw(seq, prompt)
        assert tok == toks[0]
        for i, want in enumerate(rows):
            tokens = np.ones((B, 1), np.int32)
            positions = np.zeros((B,), np.int32)
            tables = np.full((B, MB), DUMP_BLOCK, np.int32)
            active = np.zeros((B,), bool)
            tokens[0, 0], positions[0] = toks[i], len(prompt) + i
            tables[0], active[0] = row, True
            _, lp, eng.cache.pools, _ = fn(dp, *eng.cache.pools, tokens,
                                           positions, tables, active)
            np.testing.assert_array_equal(np.asarray(lp)[0], want)
        eng.cache.free_seq(seq)
        eng.start()
        assert eng.submit(prompt, max_new_tokens=9).result(timeout=60) == toks
        eng.close()
        _zero_retraces(eng)


# ---------------------------------------------------------------------------
# streaming + continuous batching
# ---------------------------------------------------------------------------

class TestStreamingScheduler:
    def test_stream_iterates_tokens_and_completes(self):
        with _engine() as eng:
            eng.start()
            s = eng.submit(_prompt(6), max_new_tokens=6)
            got = list(s)
            assert got == s.result(timeout=10) and len(got) == 6
            assert s.outcome == "completed"
            assert s.ttft_ms() > 0 and s.latency_ms() >= s.ttft_ms()
            stats = eng.stats()
        _assert_identity(stats)

    def test_eos_finishes_early(self):
        with _engine() as eng:
            eng.start()
            probe = eng.submit(_prompt(6, seed=2), max_new_tokens=8)
            toks = probe.result(timeout=10)
            s = eng.submit(_prompt(6, seed=2), max_new_tokens=8,
                           eos_id=toks[2])
            assert s.result(timeout=10) == toks[:3]
            assert s.outcome == "completed"

    def test_iteration_level_batching_shares_decode_steps(self):
        config.set_property("bigdl.analysis.retrace", "strict")
        with _engine() as eng:
            eng.start()
            streams = [eng.submit(_prompt(5, seed=i), max_new_tokens=8)
                       for i in range(8)]
            outs = [s.result(timeout=30) for s in streams]
            assert all(len(o) == 8 for o in outs)
            stats = eng.stats()
            # offline per-sequence decode would pay tokens - prefills
            # steps; continuous batching must share iterations
            decode_token_steps = stats["tokens_out"] - stats["prefills"]
            assert stats["decode_steps"] < decode_token_steps, stats
            # completions match the offline paged path bit-exactly
            _zero_retraces(eng)
        _assert_identity(stats)
        ref = _engine()
        for i, o in enumerate(outs):
            assert o == ref.generate(_prompt(5, seed=i), max_new_tokens=8)
        ref.close()

    @pytest.mark.parametrize("quantize", [None, "int8"])
    def test_clean_open_loop_completes_every_stream(self, quantize):
        """A Poisson open loop of mixed prompt and answer lengths with
        no fault planned: every stream completes with all its tokens,
        the identity is exact in the client's record and in the engine's
        stats, and no step retraces: on the fp tier and the int8 tier."""
        reqs = sample_lm_workload(12, VOCAB, seed=7,
                                  prompt_lens=(4, 8, 12),
                                  output_lens=(4, 6, 8))
        with _engine(quantize=quantize) as eng:
            eng.start()
            rec = run_lm_open_loop(eng, reqs, rate_hz=200.0, seed=11)
            stats = eng.stats()
        _assert_identity(rec)
        _assert_identity(stats)
        assert rec["completed"] == len(reqs), rec
        assert rec["tokens_total"] == sum(n for _, n in reqs)
        _zero_retraces(eng)

    def test_blocks_free_after_drain(self):
        with _engine() as eng:
            eng.start()
            for i in range(6):
                eng.submit(_prompt(4, seed=i), max_new_tokens=4)
            eng.stop()
            assert eng.cache.used_blocks == 0
            _assert_identity(eng.stats())

    def test_deadline_sheds_after_streamed_prefix(self):
        """Partially-streamed-then-failed is a first-class outcome: the
        deadline check runs AFTER the iteration's emit, so the client
        keeps the prefix and the terminal error is structured."""
        config.set_property("bigdl.chaos.hangDecodeAt", "2:0.6")
        chaos.install()
        with _engine() as eng:
            eng.start()
            s = eng.submit(_prompt(5), max_new_tokens=10, deadline_ms=250.0)
            got = []
            with pytest.raises(DeadlineExceeded):
                for tok in s:
                    got.append(tok)
            assert s.outcome == "shed"
            assert len(got) >= 1                 # the streamed prefix
            assert got == s.tokens()             # still readable
            stats = eng.stats()
        _assert_identity(stats)


# ---------------------------------------------------------------------------
# chaos trio + combined-plan identity
# ---------------------------------------------------------------------------

class TestLMChaos:
    def test_poison_prompt_quarantined_alone(self):
        config.set_property("bigdl.chaos.poisonPromptAt", "1")
        chaos.install()
        with _engine() as eng:
            eng.start()
            streams = [eng.submit(_prompt(4, seed=i), max_new_tokens=4)
                       for i in range(3)]
            assert len(streams[0].result(timeout=10)) == 4
            assert len(streams[2].result(timeout=10)) == 4
            with pytest.raises(ServingDataError, match="poison prompt"):
                streams[1].result(timeout=10)
            assert streams[1].outcome == "quarantined"
            stats = eng.stats()
        _assert_identity(stats)
        assert stats["completed"] == 2 and stats["quarantined"] == 1

    def test_evicted_block_sheds_one_sequence_retriably(self):
        config.set_property("bigdl.chaos.evictBlockAt", 2)
        chaos.install()
        with _engine() as eng:
            eng.start()
            a = eng.submit(_prompt(4, seed=0), max_new_tokens=6)
            b = eng.submit(_prompt(4, seed=1), max_new_tokens=6)
            outcomes = {}
            for s in (a, b):
                try:
                    s.result(timeout=10)
                except ServingInfraError as e:
                    assert "evicted" in str(e) and "retriable" in str(e)
                outcomes[s.index] = s.outcome
            assert sorted(outcomes.values()) == ["completed", "shed"]
            victim = a if a.outcome == "shed" else b
            assert len(victim.tokens()) >= 1     # prefix intact
            stats = eng.stats()
        _assert_identity(stats)

    def test_hung_decode_watchdog_aborts_and_cools_down(self):
        # 20x the ~1 ms decode EMA ≈ a 25 ms threshold: far above CI
        # scheduling jitter, still 100x under the injected 3 s wedge
        config.set_property("bigdl.lm.stallFactor", 20.0)
        config.set_property("bigdl.lm.warmupSteps", 2)
        config.set_property("bigdl.chaos.hangDecodeAt", "8:3.0")
        chaos.install()
        with _engine() as eng:
            eng.start()
            # decode steps 1..7 complete a clean stream and seed the EMA
            assert len(eng.submit(_prompt(4), max_new_tokens=8)
                       .result(timeout=30)) == 8
            t0 = time.monotonic()
            victim = eng.submit(_prompt(4, seed=1), max_new_tokens=8)
            with pytest.raises(HungDispatchError, match="wedged past"):
                victim.result(timeout=30)
            assert victim.outcome == "shed"
            assert time.monotonic() - t0 < 3.0, \
                "the abort must land well before the 3 s wedge expires"
            # cooldown clears once the backlog is empty; it re-serves
            deadline = time.monotonic() + 10
            while True:
                try:
                    h = eng.submit(_prompt(4, seed=2), max_new_tokens=4)
                    break
                except Overloaded as e:
                    assert e.reason == "cooldown", e
                    assert time.monotonic() < deadline
                    time.sleep(0.02)
            assert len(h.result(timeout=30)) == 4
            stats = eng.stats()
        _assert_identity(stats)

    def test_abort_mid_admission_cannot_strand_a_stream(self):
        """The watchdog abort is delivered asynchronously and can land
        while a stream sits in ``_admitting`` — popped from the queue,
        not yet slotted.  The shed sweep must account it (regression:
        a stranded stream held the accounting identity open forever)."""
        eng = _engine(warm=False)
        s = eng.submit(_prompt(4), max_new_tokens=4)
        eng._admitting = eng._q.get_nowait()
        assert eng._admitting is s
        eng._shed_active(HungDispatchError("injected mid-admission"),
                         "hung_decode")
        assert eng._admitting is None
        assert s.outcome == "shed"
        with pytest.raises(HungDispatchError):
            s.result(timeout=1)
        eng.close()
        _assert_identity(eng.stats())

    def test_combined_chaos_identity_exact(self):
        """The ISSUE-18 combined plan: poison prompt + hung decode +
        block eviction in ONE open-loop load.  Every submitted stream
        lands in exactly one outcome bucket — including sequences that
        streamed a prefix and then failed."""
        config.set_property("bigdl.lm.stallFactor", _COMBINED_STALL)
        config.set_property("bigdl.lm.warmupSteps", 2)
        config.set_property("bigdl.chaos.poisonPromptAt", "2")
        config.set_property("bigdl.chaos.evictBlockAt", 6)
        config.set_property("bigdl.chaos.hangDecodeAt", _COMBINED_HANG)
        chaos.install()
        reqs = sample_lm_workload(12, VOCAB, seed=9,
                                  prompt_lens=(4, 6, 8),
                                  output_lens=(4, 6, 8))
        with _engine() as eng:
            eng.start()
            rec = run_lm_open_loop(eng, reqs, rate_hz=200.0, seed=4)
            stats = eng.stats()
        _assert_identity(rec)
        _assert_identity(stats)
        assert rec["quarantined"] >= 1, rec
        assert rec["shed"] >= 1, rec
        # partially-streamed-then-failed: a shed stream keeps its prefix
        shed = [s for _, s in rec["streams"]
                if s is not None and s.outcome == "shed"]
        assert any(len(s.tokens()) >= 1 for s in shed), \
            "no shed stream retained a streamed prefix"
        _zero_retraces(eng)

    def test_combined_chaos_every_failure_explains_itself(self, tmp_path):
        """ISSUE-20 acceptance, end to end: under the ISSUE-18
        combined-chaos plan with request tracing armed, every
        non-completed request's trace id resolves to a causally-ordered
        span chain ending in its EXACT verdict; the tail-latency
        exemplar resolves to a real request; and each injected terminal
        fault writes exactly one schema-validated incident bundle whose
        event ring names the injection."""
        import json

        from bigdl_tpu import telemetry
        from bigdl_tpu.telemetry import incident, request_trace
        config.set_property("bigdl.lm.stallFactor", _COMBINED_STALL)
        config.set_property("bigdl.lm.warmupSteps", 2)
        config.set_property("bigdl.chaos.poisonPromptAt", "2")
        config.set_property("bigdl.chaos.evictBlockAt", 6)
        config.set_property("bigdl.chaos.hangDecodeAt", _COMBINED_HANG)
        config.set_property("bigdl.incident.dir", str(tmp_path))
        config.set_property("bigdl.incident.autoDump", True)
        request_trace.arm()
        chaos.install()
        reqs = sample_lm_workload(12, VOCAB, seed=9,
                                  prompt_lens=(4, 6, 8),
                                  output_lens=(4, 6, 8))
        try:
            with _engine() as eng:
                eng.start()
                rec = run_lm_open_loop(eng, reqs, rate_hz=200.0, seed=4)
                stats = eng.stats()
        finally:
            config.clear_property("bigdl.incident.dir")
        _assert_identity(rec)
        _assert_identity(stats)
        assert rec["quarantined"] >= 1 and rec["shed"] >= 1, rec

        # every request — admitted or rejected at the door — resolves
        # to a trace ending in its exact terminal verdict
        for key, s in rec["streams"]:
            if s is None:
                err = rec["errors"][key]
                tid = getattr(err, "trace_id", None)
                assert tid, "rejections carry their trace id on the error"
                assert request_trace.get(tid)["verdict"] == "rejected"
                continue
            tr = request_trace.get(s.trace_id)
            assert tr is not None, "every admitted stream is traced"
            assert tr["verdict"] == s.outcome, (key, s.outcome, tr)
            names = [sp["name"] for sp in tr["spans"]]
            assert names[0] == "request/queue_wait", names
            assert names[-1] == "request/verdict", names
            assert "request/admit" in names
            starts = [sp["t0_ns"] for sp in tr["spans"]]
            assert starts == sorted(starts), "span chain causally ordered"
            if s.outcome == "completed":
                assert "request/prefill" in names
                assert "request/decode_step" in names

        # exemplar round-trip: the tail of the latency histogram IS a
        # real request from this run
        ex = telemetry.histogram("LM/latency_ms").tail_exemplar()
        run_tids = {s.trace_id for _, s in rec["streams"] if s is not None}
        assert ex in run_tids
        assert request_trace.get(ex) is not None

        # one schema-validated bundle per injected terminal fault,
        # its ring naming the injection
        paths = incident.dumped()
        docs = []
        for p in paths:
            with open(p) as f:
                docs.append(json.load(f))
        reasons = [d["reason"] for d in docs]
        assert len(set(reasons)) == len(reasons), \
            "one bundle per fault slug, never duplicates"
        assert "lm/quarantine" in reasons
        assert "lm/hung_decode" in reasons
        ring_kinds = {e["kind"] for d in docs for e in d["events"]}
        assert "chaos/poison_prompt" in ring_kinds
        assert "chaos/hang_decode" in ring_kinds
        for d in docs:
            assert d["schema"] == "bigdl.incident/1"
            for k in ("reason", "written_ns", "events", "spans",
                      "metrics", "config", "threads", "trace_id"):
                assert k in d, k
        quarantine = docs[reasons.index("lm/quarantine")]
        assert quarantine["trace"] is not None
        assert quarantine["trace"]["verdict"] == "quarantined"
        _zero_retraces(eng)


# ---------------------------------------------------------------------------
# int8 decode tier
# ---------------------------------------------------------------------------

class TestInt8Tier:
    def test_gate_passes_and_serves(self):
        eng = _engine(quantize="int8")
        rep = eng.quantization_report
        assert rep["audit_ok"] and rep["allclose"], rep
        assert rep["max_abs_diff"] <= rep["atol"] + 1.0  # recorded, sane
        eng.start()
        s = eng.submit(_prompt(6), max_new_tokens=6)
        assert len(s.result(timeout=30)) == 6
        eng.close()
        _assert_identity(eng.stats())
        assert "lm_decode_int8" in eng.sentinels
        _zero_retraces(eng)

    def test_gate_refuses_on_drift(self):
        config.set_property("bigdl.lm.quantizeRtol", 0.0)
        config.set_property("bigdl.lm.quantizeAtol", 1e-9)
        with pytest.raises(QuantizationGateError, match="drifted past"):
            _engine(warm=False, quantize="int8")

    def test_unknown_tier_is_refused(self):
        with pytest.raises(ValueError, match="int8"):
            _engine(warm=False, quantize="int4")


# ---------------------------------------------------------------------------
# the width the engine holds its matrix weights and pools at
# ---------------------------------------------------------------------------

@pytest.fixture
def bf16_held(monkeypatch):
    """The engine holds bf16, as it does on a TPU at the default
    precision."""
    monkeypatch.setattr(_lm, "_held_dtype",
                        lambda backend, precision: jnp.dtype(jnp.bfloat16))


@pytest.fixture
def one_bf16_pass(monkeypatch):
    """Every product at one bf16 pass, the precision a TPU's default
    gives a float32 ``dot``: float32 operands rounded to the nearest
    bf16, float32 accumulation (the CPU's own float32 product rounds
    nothing).  Traces
    made before or after would not see it, so the jit caches are
    cleared on both sides."""
    from jax._src.lax import lax as lax_internal
    prim = lax_internal.dot_general_p
    bind = prim.bind

    def one_bf16_pass(x):
        return (x.astype(jnp.bfloat16).astype(x.dtype)
                if x.dtype == jnp.float32 else x)

    jax.clear_caches()
    monkeypatch.setattr(prim, "bind", lambda lhs, rhs, **kw: bind(
        one_bf16_pass(lhs), one_bf16_pass(rhs), **kw))
    yield
    monkeypatch.undo()
    jax.clear_caches()


def _matrix_weights(eng):
    return [e["w"] for l in eng._dp["layers"]
            for e in (*l["attn"].values(), *l["ffn"].values())] + [
        eng._dp["head"]["w"]]


class TestHeldWidth:
    @pytest.mark.parametrize("backend,precision,held", [
        ("tpu", None, "bfloat16"), ("tpu", "default", "bfloat16"),
        ("tpu", "bfloat16", "bfloat16"), ("tpu", "fastest", "bfloat16"),
        ("tpu", "highest", "float32"), ("tpu", "float32", "float32"),
        ("tpu", "tensorfloat32", "float32"), ("tpu", "high", "float32"),
        ("cpu", None, "float32"), ("cpu", "bfloat16", "float32"),
        ("gpu", None, "float32")])
    def test_rule_of_backend_and_precision(self, backend, precision, held):
        assert _lm._held_dtype(backend, precision) == jnp.dtype(held)

    def test_graph_asks_with_the_precision_in_force(self, monkeypatch):
        asked = []
        monkeypatch.setattr(_lm, "_held_dtype", lambda b, p: asked.append(
            (b, p)) or jnp.dtype(jnp.float32))
        m = _model()
        for precision in ("default", "highest"):
            with jax.default_matmul_precision(precision):
                _lm._LMGraph(m)
        assert asked == [("cpu", "default"), ("cpu", "highest")]

    def test_cpu_holds_the_modules_own_float32_arrays(self):
        m = _model()
        eng = _engine(m, warm=False)
        graph = _lm._LMGraph(m)
        assert eng.cache.k.dtype == eng.cache.v.dtype == jnp.float32
        assert eng._dp["head"]["w"] is graph.head.params["weight"]
        assert all(w.dtype == jnp.float32 for w in _matrix_weights(eng))
        eng.close()

    @pytest.mark.parametrize("long,n,new", [(False, 9, 12),
                                            (True, 125, 5)],
                             ids=["one_chunk", "first_edge"])
    def test_bf16_steps_are_the_float32_steps_at_one_bf16_pass(
            self, one_bf16_pass, monkeypatch, long, n, new):
        """Prefill and decode (``generate``) and the full step
        (``generate_sequential``): the same tokens as the float32 engine
        whose products read bf16 operands, log-probs within 1e-5 (so
        nothing else was rounded: not the residual, the softmax or the
        log-probs), the pools bf16 after the steps and zero outside the
        dump block once the sequence is freed."""
        m, kw = (_model(max_len=512), _LONG) if long else (_model(), {})
        ref = _engine(m, **kw)
        monkeypatch.setattr(_lm, "_held_dtype",
                            lambda b, p: jnp.dtype(jnp.bfloat16))
        eng = _engine(m, **kw)
        assert ref.cache.k.dtype == jnp.float32
        assert all(w.dtype == jnp.bfloat16 for w in _matrix_weights(eng))
        assert eng._dp["embed"].dtype == jnp.float32
        for ask in ("generate", "generate_sequential"):
            want_t, want_lp = getattr(ref, ask)(
                _prompt(n, seed=5), max_new_tokens=new, return_logps=True)
            got_t, got_lp = getattr(eng, ask)(
                _prompt(n, seed=5), max_new_tokens=new, return_logps=True)
            assert got_t == want_t, ask
            np.testing.assert_allclose(np.asarray(got_lp),
                                       np.asarray(want_lp), rtol=0,
                                       atol=1e-5)
        assert eng.cache.k.dtype == eng.cache.v.dtype == jnp.bfloat16
        assert _pools_zero_outside_dump(eng.cache)
        ref.close()
        eng.close()

    def test_bf16_halves_the_pools_and_the_weights_products_read(self):
        m = _model()
        f32 = _engine(m, warm=False)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(_lm, "_held_dtype",
                       lambda b, p: jnp.dtype(jnp.bfloat16))
            bf16 = _engine(m, warm=False)
        n = sum(w.size for w in _matrix_weights(f32))
        a, b = f32.stats(), bf16.stats()
        assert a["dot_weight_bytes"] == 4 * n
        assert b["dot_weight_bytes"] == 2 * n
        assert b["cache_bytes_per_token"] * 2 == a["cache_bytes_per_token"]
        assert sum(b["pool_bytes"].values()) * 2 == sum(
            a["pool_bytes"].values())
        # what LM/weight_bytes counts loses half of every matrix weight
        assert a["weight_bytes"] - b["weight_bytes"] == 2 * n
        f32.close()
        bf16.close()

    def test_int8_tier_quantizes_the_modules_float32_weights(self,
                                                             bf16_held):
        m = _model()
        eng = _engine(m, warm=False, quantize="int8")
        assert eng.quantization_report["allclose"]
        want = _lm._quantize_params(_lm._extract_params(_lm._LMGraph(m)))
        got_leaves, got_tree = jax.tree_util.tree_flatten(eng._dp_q)
        want_leaves, want_tree = jax.tree_util.tree_flatten(want)
        assert got_tree == want_tree
        for g, w in zip(got_leaves, want_leaves):
            assert g.dtype == w.dtype and np.array_equal(np.asarray(g),
                                                         np.asarray(w))
        n = sum(w.size for w in _matrix_weights(eng))
        # the fp steps' bf16 weights and the int8 tier's bytes, each once
        assert eng.stats()["dot_weight_bytes"] == 2 * n + n
        eng.close()


# ---------------------------------------------------------------------------
# donated KV pools: every step that takes the pools consumes them
# ---------------------------------------------------------------------------

def _pools_zero_outside_dump(cache):
    return bool((np.asarray(cache.k)[:, 1:] == 0).all() and
                (np.asarray(cache.v)[:, 1:] == 0).all())


class TestDonatedPools:
    @pytest.mark.parametrize("owner,step", [("engine", "_decode"),
                                            ("engine", "_prefill"),
                                            ("cache", "scrub_step")])
    def test_step_consumes_the_pools_it_is_given(self, owner, step):
        """One ``generate()`` runs a prefill, decode steps and the frees'
        scrubs: every pool array handed to the named step is dead once
        the call returns, and what the cache holds afterwards is live."""
        eng = _engine()
        obj = eng if owner == "engine" else eng.cache
        real, seen = getattr(obj, step), []
        at = 0 if owner == "cache" else 1       # (dp, k, v, ...) vs (k, v, ids)

        def spy(*args):
            seen.extend(args[at:at + 2])
            return real(*args)

        setattr(obj, step, spy)
        first = (eng.cache.k, eng.cache.v)
        toks = eng.generate(_prompt(6, seed=1), max_new_tokens=3)
        assert len(toks) == 3 and len(seen) >= 2
        assert all(a.is_deleted() for a in seen), step
        assert all(a.is_deleted() for a in first)
        assert not (eng.cache.k.is_deleted() or eng.cache.v.is_deleted())
        # the in-place scrub is still the bit-exact one
        assert _pools_zero_outside_dump(eng.cache)
        eng.close()

    @pytest.mark.parametrize("quantize", ["off", "int8"])
    def test_warmup_reports_the_pools_aliased(self, quantize, caplog):
        """(b), (f): XLA took the donation in every step — the gauge
        reads the pools' own size, warmup has nothing to warn about, and
        the int8 gate (two steps on one pool) leaves live pools."""
        import logging

        from bigdl_tpu import telemetry
        with caplog.at_level(logging.WARNING, logger="bigdl_tpu"):
            eng = _engine(quantize=quantize)
        stats = eng.stats()
        assert stats["kv_aliased_bytes"] == eng.cache.pool_nbytes
        assert stats["kv_pool_rebuilds"] == 0
        assert telemetry.REGISTRY.gauge("LM/kv_aliased_bytes").value == \
            eng.cache.pool_nbytes
        assert not [r for r in caplog.records if "alias" in r.getMessage()]
        assert not (eng.cache.k.is_deleted() or eng.cache.v.is_deleted())
        assert eng.cache.used_blocks == 0
        assert _pools_zero_outside_dump(eng.cache)
        if quantize == "int8":
            assert eng.quantization_report["allclose"]
        eng.close()

    def test_warmup_warns_when_a_step_copies(self, caplog):
        import logging
        eng = _engine(warm=False)
        eng.cache.note_executable(object())      # cannot answer: counts as 0
        with caplog.at_level(logging.WARNING, logger="bigdl_tpu"):
            eng.warmup()
        assert eng.stats()["kv_aliased_bytes"] == 0
        assert [r for r in caplog.records if "declined the donation"
                in r.getMessage()]
        eng.close()

    @pytest.mark.parametrize("how", ["deleted_by_hand",
                                     "raise_after_dispatch"])
    def test_consumed_pool_is_rebuilt_and_the_engine_serves_on(self, how):
        """(d): a dispatch that consumed the pools and never wrote them
        back must not take the engine down — the shed sweep finds the
        dead pool, rebuilds zeros, and the next request completes."""
        eng = _engine()
        if how == "deleted_by_hand":
            s = eng.submit(_prompt(4), max_new_tokens=4)
            eng._admitting = eng._q.get_nowait()
            eng.cache.allocate(s.seq_id, 8)
            eng.cache.k.delete()
            eng._shed_active(ServingInfraError("injected"), "infra")
            eng.start()
        else:
            real, calls = eng._decode, []

            def consume_then_raise(*args):
                out = real(*args)
                if not calls:
                    calls.append(1)
                    raise RuntimeError("injected after dispatch")
                return out

            eng._decode = consume_then_raise
            eng.start()
            s = eng.submit(_prompt(4), max_new_tokens=4)
        with pytest.raises(ServingInfraError):
            s.result(timeout=30)
        assert s.outcome == "shed"
        fresh = eng.submit(_prompt(5, seed=3), max_new_tokens=5)
        assert len(fresh.result(timeout=30)) == 5
        eng.stop()
        stats = eng.stats()
        assert stats["kv_pool_rebuilds"] == 1
        assert not (eng.cache.k.is_deleted() or eng.cache.v.is_deleted())
        assert eng.cache._tables == {} and eng.cache.used_blocks == 0
        assert _pools_zero_outside_dump(eng.cache)
        _assert_identity(stats)
        ref = _engine()
        assert fresh.result() == ref.generate(_prompt(5, seed=3),
                                              max_new_tokens=5)
        ref.close()

    def test_failed_offline_decode_leaves_a_live_pool(self):
        eng = _engine()
        real = eng._decode

        def consume_then_raise(*args):
            real(*args)
            raise RuntimeError("injected after dispatch")

        eng._decode = consume_then_raise
        with pytest.raises(RuntimeError, match="injected"):
            eng.generate(_prompt(4), max_new_tokens=4)
        eng._decode = real
        assert eng.stats()["kv_pool_rebuilds"] == 1
        assert eng.cache.used_blocks == 0
        assert eng.generate(_prompt(4), max_new_tokens=4) == \
            eng.generate_sequential(_prompt(4), max_new_tokens=4)
        eng.close()

    def test_dead_pool_under_a_held_table_is_an_error_not_a_rebuild(self):
        cache = PagedKVCache.kv(2, 2, 8, n_blocks=5, block_size=4)
        cache.allocate(1, 4)
        cache.allocate(2, 4)
        assert cache.ensure_live() is False          # live: nothing to do
        cache.v.delete()
        with pytest.raises(ServingInfraError, match=r"\[1\] still hold"):
            cache.free_seq(2)
        assert cache.rebuilds == 0
        assert cache.ensure_live(releasing=[1]) is True
        assert cache.rebuilds == 1
        assert cache.k.sharding == cache.v.sharding
        assert (np.asarray(cache.k) == 0).all()
        assert (np.asarray(cache.v) == 0).all()

    def test_cache_entry_of_a_copying_scrub_is_not_served(self, tmp_path):
        """(g): aliasing is in the lowered text, so an entry compiled
        without donation has another key; one compiled with it is loaded
        and still reports what it aliases."""
        from bigdl_tpu.analysis.program_contracts import lm_kv_scrub_contract
        from bigdl_tpu.serving import kv_cache
        from bigdl_tpu.utils.compile_cache import tracked_jit
        config.set_property("bigdl.compile.cacheDir", str(tmp_path / "cc"))
        try:
            cache = PagedKVCache.kv(2, 2, 8, n_blocks=5, block_size=4)
            pool = jax.ShapeDtypeStruct(cache.k.shape, cache.k.dtype)
            ids = jax.ShapeDtypeStruct((kv_cache._SCRUB_CHUNK,), np.int32)
            old = tracked_jit(kv_cache._scrub_fn, "lm_kv_scrub",
                              contract=lm_kv_scrub_contract())
            old.warmup(pool, pool, ids)
            assert old.compiles == 1                 # stored, no donation
            cache.warmup()
            assert cache.scrub_step.cache_hits == 0
            assert cache.scrub_step.compiles == 1
            assert cache.aliased_bytes == cache.pool_nbytes
            again = PagedKVCache.kv(2, 2, 8, n_blocks=5, block_size=4)
            again.warmup()
            assert again.scrub_step.cache_hits == 1
            assert again.scrub_step.compiles == 0
            assert again.aliased_bytes == again.pool_nbytes
            before = (again.k, again.v)
            again.allocate(1, 8)
            again.free_seq(1)                        # the loaded one runs
            assert all(a.is_deleted() for a in before)
            assert (np.asarray(again.k) == 0).all()
        finally:
            config.clear_property("bigdl.compile.cacheDir")


# ---------------------------------------------------------------------------
# one decode step in flight (ISSUE 32): step n+1 is dispatched with step
# n's tokens still on the device, and emitted one pass later
# ---------------------------------------------------------------------------

def _pass(eng):
    """One pass of ``_scheduler_loop`` that has work, made by hand on an
    engine that was not started: the schedule is then the test's, not the
    thread's."""
    if eng._pending or not eng._q.empty():
        eng._admit_waiting(None)
    if eng._any_active():
        eng._decode_iteration(None)


def _serve_by_hand(eng, arrivals, limit=400):
    """Submit ``arrivals[pass number]`` (lists of ``submit()`` keywords)
    before that pass and run passes until nothing is left; the streams in
    order of arrival."""
    streams = []
    for n in range(limit):
        for kw in arrivals.get(n, ()):
            streams.append(eng.submit(**kw))
        if (n > max(arrivals) and eng._q.empty() and not eng._pending
                and not eng._any_active()):
            return streams
        _pass(eng)
    raise AssertionError(f"still busy after {limit} passes")


def _fresh_at(toks, lo):
    """First index >= ``lo`` whose token the sequence has not held before:
    as an ``eos_id`` it ends the sequence exactly there."""
    return next(i for i in range(lo, len(toks)) if toks[i] not in toks[:i])


class TestStepInFlight:
    def test_streams_served_ahead_are_generates(self):
        """(a): admissions in mid-run, ends by length (one at its prefill),
        ends on ``eos_id``: every stream is ``generate()``'s, token for
        token, no step retraces and the freed blocks are zero."""
        config.set_property("bigdl.analysis.retrace", "strict")
        ref = _engine()
        plan = [(0, 5, 12, None), (0, 9, 3, None), (4, 7, 10, 3),
                (5, 6, 1, None), (9, 4, 2, None), (9, 11, 9, 5),
                (30, 8, 6, None)]
        arrivals, want = {}, []
        for at, n, new, eos_from in plan:
            prompt = _prompt(n, seed=100 + n)
            toks = ref.generate(prompt, max_new_tokens=new)
            eos = (toks[_fresh_at(toks, eos_from)]
                   if eos_from is not None else None)
            want.append(ref.generate(prompt, max_new_tokens=new,
                                     eos_id=eos))
            arrivals.setdefault(at, []).append(
                dict(prompt=prompt, max_new_tokens=new, eos_id=eos))
        assert len(want[2]) < 10 and len(want[5]) < 9     # eos did end them
        eng = _engine()
        streams = _serve_by_hand(eng, arrivals)
        assert [s.result(timeout=1) for s in streams] == want
        assert all(s.outcome == "completed" for s in streams)
        stats = eng.stats()
        _assert_identity(stats)
        _zero_retraces(eng)
        assert eng._inflight is None and eng.cache.used_blocks == 0
        assert _pools_zero_outside_dump(eng.cache)
        # most steps ran ahead; each eos end left its row in the step in
        # flight, and that row reached nobody
        assert stats["decode_steps_ahead"] > stats["decode_steps"] // 2
        assert stats["decode_rows_wasted"] == 2
        assert stats["tokens_out"] == sum(len(w) for w in want)
        ref.close()
        eng.close()

    def test_threaded_streams_with_late_arrivals_are_generates(self):
        """(a) through the scheduler thread: a second wave is submitted
        while the first decodes, and one stream ends on its ``eos_id``."""
        config.set_property("bigdl.analysis.retrace", "strict")
        ref = _engine()
        probe = ref.generate(_prompt(6, seed=2), max_new_tokens=14)
        eos = probe[_fresh_at(probe, 4)]
        with _engine() as eng:
            eng.start()
            first = [eng.submit(_prompt(6, seed=2), max_new_tokens=14,
                                eos_id=eos),
                     eng.submit(_prompt(10, seed=3), max_new_tokens=16)]
            it = iter(first[1])
            head = [next(it) for _ in range(3)]        # it is decoding now
            late = [eng.submit(_prompt(n, seed=n), max_new_tokens=new)
                    for n, new in ((4, 9), (12, 2), (7, 12))]
            outs = [s.result(timeout=60) for s in first + late]
            stats = eng.stats()
            _zero_retraces(eng)
        assert outs[1][:3] == head
        assert outs[0] == ref.generate(_prompt(6, seed=2), max_new_tokens=14,
                                       eos_id=eos)
        assert outs[1] == ref.generate(_prompt(10, seed=3), max_new_tokens=16)
        for (n, new), o in zip(((4, 9), (12, 2), (7, 12)), outs[2:]):
            assert o == ref.generate(_prompt(n, seed=n), max_new_tokens=new)
        _assert_identity(stats)
        assert stats["decode_steps_ahead"] > 0
        ref.close()

    def test_row_of_an_ended_sequence_never_reaches_the_slots_next_stream(
            self):
        """(b): one slot.  A ends on its ``eos_id`` at the emission of step
        n-1, with step n (A's row live) in flight; B takes the slot at the
        next pass.  Step n's row is dropped, not handed to B; the counter
        says one row was wasted; A's blocks, written once more by step n
        and scrubbed after it, read zero."""
        ref = _engine(max_batch=1)
        pa, pb = _prompt(5, seed=41), _prompt(8, seed=42)
        toks = ref.generate(pa, max_new_tokens=12)
        at = _fresh_at(toks, 3)
        eng = _engine(max_batch=1)
        a = eng.submit(pa, max_new_tokens=12, eos_id=toks[at])
        b = eng.submit(pb, max_new_tokens=6)
        for _ in range(40):
            _pass(eng)
            if a.done():
                break
        assert a.result(timeout=1) == toks[:at + 1]
        # A is gone, B still waits, and the step dispatched before A's end
        # was found is in flight with A's row
        flight = eng._inflight
        assert flight is not None and flight.rows == [(0, a)]
        assert eng._slots == [None] and eng.cache.used_blocks == 0
        assert _pools_zero_outside_dump(eng.cache)    # scrubbed AFTER step n
        assert not b.tokens()
        _pass(eng)                    # admits B into the slot, retires step n
        assert eng._slots[0].stream is b and len(b.tokens()) == 1
        assert eng.stats()["decode_rows_wasted"] == 1
        for _ in range(40):
            if b.done():
                break
            _pass(eng)
        assert b.result(timeout=1) == ref.generate(pb, max_new_tokens=6)
        stats = eng.stats()
        assert stats["decode_rows_wasted"] == 1
        assert stats["tokens_out"] == at + 1 + 6
        _assert_identity(stats)
        assert eng._inflight is None and eng.cache.used_blocks == 0
        assert _pools_zero_outside_dump(eng.cache)
        ref.close()
        eng.close()

    @pytest.mark.parametrize("fail_at", [2, 3])
    def test_failed_step_dispatched_ahead_sheds_every_stream_once(self,
                                                                  fail_at):
        """(c): a dispatch that consumed the pools and raised, with the
        previous step still in flight: every live stream is shed once,
        nothing stays in flight, the dead pool is rebuilt, the identity
        holds and the next request completes."""
        eng = _engine()
        real, calls = eng._decode, []

        def consume_then_raise(*args):
            out = real(*args)
            calls.append(eng._inflight is not None)
            if len(calls) == fail_at:
                raise RuntimeError("injected after dispatch")
            return out

        eng._decode = consume_then_raise
        eng.start()
        streams = [eng.submit(_prompt(4, seed=i), max_new_tokens=8)
                   for i in range(2)]
        for s in streams:
            with pytest.raises(ServingInfraError, match="decode failed"):
                s.result(timeout=30)
            assert s.outcome == "shed"
        assert calls[fail_at - 1] is True      # it was dispatched ahead
        fresh = eng.submit(_prompt(5, seed=3), max_new_tokens=5)
        assert len(fresh.result(timeout=30)) == 5
        eng.stop()
        stats = eng.stats()
        assert stats["shed"] == 2 and stats["completed"] == 1
        assert stats["kv_pool_rebuilds"] == 1 and eng._inflight is None
        assert eng.cache._tables == {} and eng.cache.used_blocks == 0
        assert _pools_zero_outside_dump(eng.cache)
        _assert_identity(stats)
        ref = _engine()
        assert fresh.result() == ref.generate(_prompt(5, seed=3),
                                              max_new_tokens=5)
        ref.close()

    def test_counters_say_which_steps_ran_ahead(self):
        """(d): a lone two-token request has one decode step and nothing
        ahead of it.  In a long run every step runs ahead but the first
        after an idle engine and the first after an admission pass that
        left a sequence to decode (one that ended at its prefill changes
        nothing); ``stats()`` and the registry agree."""
        from bigdl_tpu import telemetry
        config.set_property("bigdl.analysis.retrace", "strict")
        eng = _engine()
        ahead = telemetry.counter("LM/decode_steps_ahead").value
        wasted = telemetry.counter("LM/decode_rows_wasted").value
        _serve_by_hand(eng, {0: [dict(prompt=_prompt(5),
                                      max_new_tokens=2)]})
        stats = eng.stats()
        assert stats["decode_steps"] == 1 and stats["decode_steps_ahead"] == 0
        # idle, then: two arrive together (one restart), one mid-run that
        # decodes (one more), one mid-run that ends at its prefill (none),
        # and after the engine ran empty a last one (one more)
        streams = _serve_by_hand(eng, {
            0: [dict(prompt=_prompt(5, seed=1), max_new_tokens=20),
                dict(prompt=_prompt(7, seed=2), max_new_tokens=9)],
            6: [dict(prompt=_prompt(4, seed=3), max_new_tokens=7)],
            11: [dict(prompt=_prompt(6, seed=4), max_new_tokens=1)],
            40: [dict(prompt=_prompt(9, seed=5), max_new_tokens=5)]})
        assert [len(s.result(timeout=1)) for s in streams] == [20, 9, 7, 1, 5]
        stats = eng.stats()
        steps = stats["decode_steps"] - 1
        assert steps == 19 + 4          # the longest stream, then the last
        assert stats["decode_steps_ahead"] == steps - 3
        assert stats["decode_rows_wasted"] == 0
        assert telemetry.counter("LM/decode_steps_ahead").value - ahead == \
            stats["decode_steps_ahead"]
        assert telemetry.counter("LM/decode_rows_wasted").value == wasted
        _zero_retraces(eng)
        _assert_identity(stats)
        eng.close()

    def test_stop_with_a_step_in_flight_drains(self):
        """(d): ``stop()`` while steps run ahead lets the sequences finish
        inside the grace period and leaves nothing in flight."""
        eng = _engine()
        eng.start()
        s = eng.submit(_prompt(6, seed=8), max_new_tokens=24)
        it = iter(s)
        head = [next(it) for _ in range(3)]
        eng.stop(grace=20.0)
        assert s.outcome == "completed" and len(s.result(timeout=1)) == 24
        assert s.result()[:3] == head
        stats = eng.stats()
        assert eng._inflight is None and not eng.scheduler_alive()
        assert stats["decode_steps_ahead"] >= 20
        assert eng.cache.used_blocks == 0
        assert _pools_zero_outside_dump(eng.cache)
        _assert_identity(stats)

    def test_evicted_row_in_flight_is_dropped(self):
        """``chaos.evict_block`` sheds a stream whose row is in the step
        in flight: the row is wasted, every other stream is generate()'s."""
        config.set_property("bigdl.chaos.evictBlockAt", "4")
        chaos.install()
        ref = _engine()
        eng = _engine()
        streams = _serve_by_hand(eng, {0: [
            dict(prompt=_prompt(5, seed=i), max_new_tokens=10)
            for i in range(3)]})
        with pytest.raises(ServingInfraError, match="evicted"):
            streams[0].result(timeout=1)
        assert len(streams[0].tokens()) == 3     # prefill + steps 1 and 2
        for i in (1, 2):
            assert streams[i].result(timeout=1) == ref.generate(
                _prompt(5, seed=i), max_new_tokens=10)
        stats = eng.stats()
        assert stats["decode_rows_wasted"] == 1
        _assert_identity(stats)
        assert _pools_zero_outside_dump(eng.cache)
        ref.close()
        eng.close()


# ---------------------------------------------------------------------------
# lint rule: unbounded-decode-loop
# ---------------------------------------------------------------------------

class TestUnboundedDecodeLoopRule:
    def _lint(self, tmp_path, body):
        from bigdl_tpu.analysis.lint import lint_paths
        d = tmp_path / "serving"
        d.mkdir(exist_ok=True)
        (d / "lm.py").write_text(body, encoding="utf-8")
        return [f for f in lint_paths([str(tmp_path)])
                if f.rule == "unbounded-decode-loop"]

    def test_flags_while_true_on_the_decode_path(self, tmp_path):
        found = self._lint(tmp_path,
                           "def decode():\n"
                           "    while True:\n"
                           "        step()\n")
        assert len(found) == 1 and found[0].line == 2

    def test_flags_unbounded_condition_name(self, tmp_path):
        found = self._lint(tmp_path,
                           "def decode(running):\n"
                           "    while running:\n"
                           "        step()\n")
        assert len(found) == 1

    def test_accepts_deadline_and_terminal_bounds(self, tmp_path):
        assert self._lint(tmp_path,
                          "def decode(self, deadline):\n"
                          "    while now() < deadline:\n"
                          "        step()\n"
                          "    while not self._terminal:\n"
                          "        step()\n"
                          "    for _ in range(max_new):\n"
                          "        step()\n") == []

    def test_production_lm_file_is_clean(self):
        from bigdl_tpu.analysis.lint import lint_paths
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        target = os.path.join(repo, "bigdl_tpu", "serving", "lm.py")
        assert [f for f in lint_paths([target])
                if f.rule == "unbounded-decode-loop"] == []


# ---------------------------------------------------------------------------
# docs drift guard: bigdl.lm.* keys
# ---------------------------------------------------------------------------

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestLMDocDrift:
    """Every ``bigdl.lm.*`` key the code registers must have a row in
    docs/configuration.md — and vice versa (same guard as the fleet,
    chaos, and ingest key families)."""

    _KEY = re.compile(r"bigdl\.lm\.[A-Za-z0-9]+(?:\.[A-Za-z0-9]+)*")

    def _keys_in(self, *parts):
        with open(os.path.join(_REPO, *parts), encoding="utf-8") as f:
            return set(self._KEY.findall(f.read()))

    def test_config_defaults_match_docs_both_ways(self):
        code = self._keys_in("bigdl_tpu", "utils", "config.py")
        docs = self._keys_in("docs", "configuration.md")
        assert code - docs == set(), \
            f"lm keys missing a docs row: {sorted(code - docs)}"
        assert docs - code == set(), \
            f"documented lm keys unknown to config.py: " \
            f"{sorted(docs - code)}"

    def test_lm_module_reads_registered_keys_only(self):
        registered = self._keys_in("bigdl_tpu", "utils", "config.py")
        used = (self._keys_in("bigdl_tpu", "serving", "lm.py") |
                self._keys_in("bigdl_tpu", "serving", "kv_cache.py"))
        assert used - registered == set(), \
            f"lm serving reads unregistered keys: " \
            f"{sorted(used - registered)}"
