"""Paged block-table KV cache for autoregressive decode.

vLLM-style paging brought to the tracked-jit world: fixed
device-resident pools of blocks shaped ``(layer, block, block_size,
*row)`` (keys and values of ``(head, head_dim)`` rows by default; a
latent-attention model names its own kinds), a HOST-side free-list, and a
per-sequence block table mapping token positions to pool blocks.  Heterogeneous
sequence lengths share the same device memory with fragmentation bounded
by the block granularity — a sequence wastes at most ``block_size - 1``
slots, never a max-context reservation.

Three invariants the serving tests bit-assert:

- **Block 0 is the dump block.**  It is never allocated: padded prefill
  positions and inactive decode slots scatter their (junk) k/v there, so
  the fused step keeps ONE fixed shape regardless of occupancy and a
  stray write can never land in another sequence's block.
- **Freed blocks are zero-scrubbed** before they re-enter the free-list:
  a reused block carries no residue of the previous request's tokens
  (no cross-request leakage, asserted bit-exactly by reading the pool).
- **Exhaustion is structured.**  An allocation the free-list cannot
  satisfy raises the serving taxonomy's retriable
  :class:`~bigdl_tpu.serving.engine.Overloaded` — the pool is sized ONCE
  at construction (gated by the HBM preflight budget,
  :func:`bigdl_tpu.resources.device.preflight_pool`), so running out of
  blocks is an admission-control answer, never a device OOM.

The pool arrays are DONATED: the compiled prefill, decode and scrub
steps consume the pools they are given (XLA aliases each to its output
and scatters in place; the array that went in reports ``is_deleted()``
once the step is dispatched) and return the updated pools, which the
caller writes back to :attr:`k` / :attr:`v` in the statement that makes
the call.  A dispatch that consumed the pools and never wrote them back
(the step raised, or the watchdog's abort landed between the two) leaves
dead handles: :meth:`PagedKVCache.ensure_live` replaces them by zeros,
which is the right content exactly when no sequence holds a block.
``LM/kv_aliased_bytes`` says whether XLA took the donation.  The
free-list and tables are plain host state under a lock (allocation is
scheduler-thread work, microseconds).
"""

from __future__ import annotations

import math
from typing import Dict, Iterable, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from bigdl_tpu import analysis, telemetry
from bigdl_tpu.serving.engine import Overloaded, ServingInfraError
from bigdl_tpu.telemetry import incident

#: block id every padded / inactive-slot scatter targets — reserved at
#: construction, never handed out by the free-list
DUMP_BLOCK = 0

#: freed block ids are zero-scrubbed in fixed-size batches (padded with
#: the dump block) so the scrub step has ONE signature instead of one
#: per distinct free-list length
_SCRUB_CHUNK = 8


def _scrub_fn(*pools_and_ids):
    """``(pool..., ids) -> (pool...)``: the blocks ``ids`` of every pool,
    all layers, set to zero."""
    *pools, ids = pools_and_ids
    return tuple(
        p.at[:, ids].set(jnp.zeros((p.shape[0], ids.shape[0]) + p.shape[2:],
                                   p.dtype))
        for p in pools)


class PagedKVCache:
    """Fixed device pools of ``(layer, block, block_size, *row)`` blocks +
    ONE host free-list + per-sequence block tables.

    ``kinds`` names the pools, ``{name: (layers, row shape)}``:
    :meth:`kv` gives the usual two, ``k`` and ``v``, of ``(n_head,
    head_dim)`` rows for each layer; a latent-attention model has a
    ``latent`` row for every layer and an ``index`` key for the layers
    that have an indexer.  :attr:`pools` holds them in that order, and is
    what the steps take, donate and return."""

    @classmethod
    def kv(cls, n_layers: int, n_head: int, head_dim: int, n_blocks: int,
           block_size: int, **kw) -> "PagedKVCache":
        row = (int(n_head), int(head_dim))
        return cls({"k": (n_layers, row), "v": (n_layers, row)}, n_blocks,
                   block_size, **kw)

    def __init__(self, kinds: Dict[str, Tuple[int, Tuple[int, ...]]],
                 n_blocks: int, block_size: int, dtype=jnp.float32,
                 label: str = "lm_kv_cache"):
        if n_blocks < 2:
            raise ValueError(
                f"paged KV cache needs >= 2 blocks (block {DUMP_BLOCK} is "
                f"the reserved dump block), got n_blocks={n_blocks}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.n_blocks = int(n_blocks)
        self.block_size = int(block_size)
        self.dtype = jnp.dtype(dtype)
        self.label = label
        self.kinds = tuple(kinds)
        shapes = [(int(layers), self.n_blocks, self.block_size)
                  + tuple(int(n) for n in row)
                  for layers, row in kinds.values()]
        #: bytes of each pool, by kind
        self.kind_nbytes = {
            kind: int(np.prod(shape)) * self.dtype.itemsize
            for kind, shape in zip(self.kinds, shapes)}
        self.pool_nbytes = sum(self.kind_nbytes.values())
        #: bytes the pools hold for one token position, all layers
        self.bytes_per_token = self.pool_nbytes // (
            self.n_blocks * self.block_size)
        # gate BEFORE the buffers exist: an over-budget pool is a plan
        # error answered while device state is still untouched
        from bigdl_tpu.resources.device import preflight_pool
        preflight_pool(self.pool_nbytes, label)
        self.pools: Tuple = tuple(jnp.zeros(shape, self.dtype)
                                  for shape in shapes)
        #: bytes of the pools that the compiled steps alias input to
        #: output, the smallest over every executable seen so far:
        #: ``pool_nbytes`` when donation engaged everywhere, 0 when XLA
        #: declined it somewhere, None before the first compile
        self.aliased_bytes: Optional[int] = None
        #: dead pools replaced by zeros (see :meth:`ensure_live`)
        self.rebuilds = 0
        self._aliased_gauge = telemetry.gauge(
            "LM/kv_aliased_bytes",
            help="KV-pool bytes the compiled steps update in place "
                 "(smallest over the steps)")
        self._rebuild_counter = telemetry.counter(
            "LM/kv_pool_rebuilds",
            help="dead (donated, never written back) KV pools replaced "
                 "by zeros")
        # the scrub is a compiled step like prefill and decode: an eager
        # ``.at[].set`` dispatches op by op and compiles each op at the
        # FIRST free — on the decode clock, after warmup
        from bigdl_tpu.analysis.program_contracts import lm_kv_scrub_contract
        from bigdl_tpu.utils.compile_cache import tracked_jit
        self.scrub_step = tracked_jit(_scrub_fn, "lm_kv_scrub",
                                       contract=lm_kv_scrub_contract(),
                                       donate_argnums=tuple(
                                           range(len(self.pools))))
        self.scrub_step.on_executable = self.note_executable
        self._free: List[int] = list(range(self.n_blocks - 1, 0, -1))
        self._tables: Dict[int, List[int]] = {}
        self._lock = analysis.make_lock("lm.kv_cache")
        self._occupancy = telemetry.gauge(
            "LM/block_occupancy",
            help="allocated KV-cache blocks / allocatable blocks")
        self._occupancy.set(0.0)

    # -- the pools by name ------------------------------------------------

    def pool(self, kind: str):
        return self.pools[self.kinds.index(kind)]

    @property
    def k(self):
        return self.pool("k")

    @property
    def v(self):
        return self.pool("v")

    # -- capacity ---------------------------------------------------------

    @property
    def allocatable_blocks(self) -> int:
        """Total blocks the free-list can ever hand out (pool minus the
        dump block)."""
        return self.n_blocks - 1

    @property
    def free_blocks(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def used_blocks(self) -> int:
        return self.allocatable_blocks - self.free_blocks

    def blocks_for(self, n_tokens: int) -> int:
        """Blocks a sequence of ``n_tokens`` total positions occupies."""
        return max(1, math.ceil(n_tokens / self.block_size))

    def can_allocate(self, n_tokens: int) -> bool:
        with self._lock:
            return self.blocks_for(n_tokens) <= len(self._free)

    # -- allocation -------------------------------------------------------

    def allocate(self, seq_id: int, n_tokens: int) -> List[int]:
        """Reserve the blocks for a sequence of up to ``n_tokens`` total
        positions, or raise a structured retriable
        :class:`Overloaded` — the free-list is the bound; exhaustion is
        an admission answer, never an allocation attempt on device."""
        need = self.blocks_for(n_tokens)
        with self._lock:
            if seq_id in self._tables:
                raise ValueError(f"sequence {seq_id} already holds "
                                 f"{len(self._tables[seq_id])} block(s)")
            if need > len(self._free):
                err = Overloaded(
                    "kv blocks exhausted",
                    queue_depth=self.allocatable_blocks - len(self._free),
                    max_depth=self.allocatable_blocks)
                err.blocks_needed = need
                err.blocks_free = len(self._free)
                raise err
            blocks = [self._free.pop() for _ in range(need)]
            self._tables[seq_id] = blocks
        self._publish_occupancy()
        return list(blocks)

    def table(self, seq_id: int) -> List[int]:
        with self._lock:
            return list(self._tables[seq_id])

    def free_seq(self, seq_id: int) -> int:
        """Release a sequence's blocks back to the free-list, ZEROING
        them on device first — a later allocation of the same block ids
        starts bit-clean (the no-cross-request-leakage proof reads the
        pool and asserts exactly this).  Returns the block count (0 when
        the sequence holds nothing — idempotent)."""
        with self._lock:
            blocks = self._tables.pop(seq_id, None)
            if not blocks:
                return 0
        self._scrub(blocks)
        with self._lock:
            self._free.extend(blocks)
        self._publish_occupancy()
        return len(blocks)

    def warmup(self) -> None:
        """AOT-compile the scrub step, so the first free after warmup
        pays no compile."""
        import jax
        self.scrub_step.warmup(
            *(jax.ShapeDtypeStruct(p.shape, p.dtype) for p in self.pools),
            jax.ShapeDtypeStruct((_SCRUB_CHUNK,), jnp.int32))

    # -- donation -------------------------------------------------------

    def note_executable(self, exe) -> None:
        """``CachedStep.on_executable`` of every step that takes the
        pools: keep the smallest aliased size any of them reports and
        publish it.  An executable that cannot answer counts as 0 — a
        step that may be copying is what the gauge exists to show."""
        try:
            aliased = int(exe.memory_analysis().alias_size_in_bytes)
        except Exception:  # noqa: BLE001 — a backend without the analysis
            aliased = 0
        if self.aliased_bytes is None or aliased < self.aliased_bytes:
            self.aliased_bytes = aliased
        self._aliased_gauge.set(self.aliased_bytes)

    def ensure_live(self, releasing: Iterable[int] = ()) -> bool:
        """Replace pools that a dispatch consumed and nobody wrote back.

        The steps donate, so a call that raised after dispatch — or a
        watchdog abort that landed before the write-back — leaves
        :attr:`pools` deleted, and the next step would fail on
        them.  All are then rebuilt as zeros of the same shape, dtype
        and placement, counted (``LM/kv_pool_rebuilds``) and recorded as
        an incident.  Zeros are the right content only when no sequence
        holds a block (freed blocks are zero by the second invariant):
        a table held by anything but ``releasing`` — the sequences the
        caller is about to free — has lost its context, which is a
        :class:`ServingInfraError` and never a silent rebuild.  Returns
        whether it rebuilt."""
        if not any(p.is_deleted() for p in self.pools):
            return False
        with self._lock:
            held = sorted(set(self._tables) - set(releasing))
        if held:
            raise ServingInfraError(
                f"KV pool was consumed by a failed dispatch while "
                f"sequence(s) {held} still hold blocks — their context "
                "is lost; shed them and retry")
        # a deleted array keeps its shape, dtype and sharding
        self.pools = tuple(jnp.zeros(a.shape, a.dtype, device=a.sharding)
                           for a in self.pools)
        self.rebuilds += 1
        self._rebuild_counter.inc()
        incident.record("lm/kv_pool_rebuild", label=self.label,
                        nbytes=self.pool_nbytes)
        return True

    def _scrub(self, blocks: List[int]) -> None:
        """Zero the named blocks across all layers, in place (the step
        donates every pool).  Ids are padded to ``_SCRUB_CHUNK`` with
        the dump block (re-zeroing junk is free), so the scrub step
        keeps its one fixed shape for every free."""
        self.ensure_live()
        for at in range(0, len(blocks), _SCRUB_CHUNK):
            chunk = blocks[at:at + _SCRUB_CHUNK]
            ids = np.full((_SCRUB_CHUNK,), DUMP_BLOCK, np.int32)
            ids[:len(chunk)] = chunk
            self.pools = tuple(self.scrub_step(*self.pools, ids))

    def _publish_occupancy(self) -> None:
        denom = max(1, self.allocatable_blocks)
        self._occupancy.set(self.used_blocks / denom)
