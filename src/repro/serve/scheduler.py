"""Slot-based continuous-batching decode scheduler.

The paper's thesis applied to serving: decisions that depend on data —
a sequence hitting EOS, a slot running out of budget — are made
**inside the runtime**, not by returning to the client. The engine owns
a fixed pool of ``n_slots`` decode slots. Each slot is one row of a
shared KV/SSM cache plus per-slot registers (``cur_len``,
``n_emitted``, ``budget``, ``active``, ``done``, ``request_id``, PRNG
key). Three layers:

1. **In-graph step function** (``_step``): one ``core.while_loop``
   whose body decodes *all* slots one token (vector ``cur_len`` — every
   slot sits at a different depth), emits into per-slot output rows,
   and retires slots **data-dependently** (EOS or budget exhausted →
   ``active=False, done=True``). Retirement also calls
   ``KVCache.free`` *in-graph*: with the paged cache the slot's blocks
   return to the free-list inside the loop body, so they are reusable
   by the very next admission. The loop predicate is
   ``any(active) & (idle_slots < want)`` where the host passes
   ``want = min(admit_threshold, len(queue))`` (or ``n_slots + 1``
   with an empty queue, reducing the predicate to ``any(active)`` so
   the drain tail never pauses): the device keeps stepping at full
   occupancy and returns to the host exactly when enough slots have
   freed for a scheduling decision to be worth making.

2a. **Chunked prefill, interleaved with decode**
   (``prefill="chunked"``, attention-decoder families —
   dense/moe/vlm): admission becomes *assign slot + alloc blocks* — a cheap
   register/table scatter with NO model forward — and the prompt
   itself is prefilled **inside the decode loop**: every loop
   iteration advances each prefilling slot by at most ``chunk_tokens``
   stream positions (``engine.prefill_chunk`` — K/V written through
   the cache view at per-slot offsets, attention against prior chunks
   through the block table when the Pallas path is on) *and* decodes
   every running slot one token. A long prompt therefore never stalls
   running slots for its full length: the inter-token gap of a
   decoding slot is bounded by one decode step plus one
   ``chunk_tokens`` chunk, whatever arrives (the vLLM/Sarathi
   scheduling argument, and the paper's §3.3 non-strict execution:
   independent subcomputations overlap instead of serializing).
   Slots gain a third in-graph state: FREE → **PREFILLING**
   (``prefilling``, per-slot progress vector ``pf_pos``) → RUNNING →
   DONE. The chunk whose window covers a slot's last real stream
   position samples its first token and flips it to RUNNING in the
   same iteration. One compiled step serves every prompt length —
   chunked mode needs no prefill buckets at all.

2b. **Batched prefill-into-slot** (``_admit``, ``prefill="oneshot"``):
   all queued prompts with
   a free slot are prefilled together as one ``n_slots``-wide batch.
   Admission first calls ``KVCache.free`` + ``KVCache.alloc`` for the
   filled rows (no-ops for the dense cache; block-table assignment for
   the paged one — sized by each request's OWN ``max_new``, which is
   why the paged pool is bounded by tokens in flight rather than
   ``n_slots × max_len``), then ``engine.prefill`` writes attention
   K/V straight into the pool rows while SSM / audio-cross state is
   spliced along its batch axis. The row mapping is a *permutation* of
   slot indices — admitted requests land in free slots, every other
   slot rewrites its own values — so admission never moves or re-pads
   running sequences, and one admission call costs one prefill
   regardless of how many requests it admits.

   **Bucketed prefill** (pure-attention families — dense/vlm/audio):
   variable prompt lengths are right-padded to the next power-of-two
   bucket (capped at ``prompt_len``), so mixed prompt traffic reuses
   at most ``log2(prompt_len)+1`` compiled prefill shapes instead of
   one per length. Right padding is exact there: causal attention
   means real tokens never see the pad lanes, the first sampled token
   is read from each row's own last real position, and the pad K/V
   beyond a row's true length is overwritten by decode before
   ``cur_len`` ever exposes it. SSM/hybrid prefills keep updating
   their recurrent state through a pad tail and MoE capacity routing
   lets pads displace real tokens, so those families require
   exact-length prompts (``submit`` rejects anything else).

3. **Host driver** (``DecodeScheduler``): keeps a FIFO queue, admits
   between device segments, harvests finished requests. Admission
   policy is greedy FIFO: every free slot is filled before the next
   device segment — for the paged cache, only while the request's
   blocks fit the free-list (head-of-line blocking keeps FIFO order;
   the host mirrors the free-block count so the gate never reads the
   device). Host-side busy mirrors avoid device round-trips on the
   scheduling path.

Per-request greedy outputs are **bit-identical** to the
batch-synchronous ``engine.generate_batch_sync`` path — and identical
between ``kv="dense"`` and ``kv="paged"`` (the paged gather
reconstructs the dense K/V layout lane-for-lane; see
``repro.serve.kv_cache``). Exception: MoE decode regroups the pool
into one routing group (``models.moe.moe_mlp``), whose capacity
couples rows — that coupling already exists inside a batch-synchronous
batch, so it is a property of the family, not of this scheduler.

Sharding: the slot pool is just a batch — ``pool_shardings`` maps the
slot axis onto the data mesh axes via the ``SLOT`` logical axis and
the paged block pool via ``BLOCK`` (``repro.dist.sharding``), so an
8-way pool runs 1-slot-per-data-shard with the same rules table the
training batch uses.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
import time
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import core
from ..dist import sharding as sh
from . import engine, kv_cache as kvc, sampling as sampling_lib
from . import speculative as spec_lib


# "no per-segment iteration cap": large enough that the free-slot
# predicate always fires first (int32-safe — steps deltas stay below it)
_NO_STEP_CAP = np.int32(2**31 - 1)


# =========================== pool state =====================================

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SlotPool:
    """Device-resident scheduler state; all leaves are arrays.

    Slot lifecycle: FREE (``~active & ~prefilling & ~done``) →
    [chunked mode: PREFILLING (``prefilling``, assigned by ``_assign``,
    prompt advanced ``chunk_tokens``/iteration in-graph) →]
    RUNNING (``active``) → DONE (``done``, retired in-graph on
    EOS/budget, cache rows freed in-graph) → FREE (host harvest clears
    ``done``). One-shot mode enters RUNNING directly via ``_admit``
    and the prefill fields ride along empty (``prompt`` is
    zero-width).
    """

    cache: Any               # engine.make_cache(cfg, n_slots, max_len, ...)
    next_token: jax.Array    # (n,) int32 — token to feed the next step
    cur_len: jax.Array       # (n,) int32 — valid cache positions + 1
    n_emitted: jax.Array     # (n,) int32 — tokens emitted so far
    budget: jax.Array        # (n,) int32 — per-request max_new
    active: jax.Array        # (n,) bool
    done: jax.Array          # (n,) bool — retired, awaiting harvest
    request_id: jax.Array    # (n,) int32
    keys: jax.Array          # (n, 2) uint32 — per-request PRNG keys
    out: jax.Array           # (n, max_new_cap) int32 — emissions
    steps: jax.Array         # scalar int32 — loop iterations run
                             # (chunked: incl. prefill-only ones)
    slot_steps: jax.Array    # scalar int32 — Σ active slots per iteration
                             # (in-graph occupancy accounting)
    prompt: jax.Array        # (n, prompt_len | 0) int32 — resident
                             # prompt tokens (chunked mode)
    plen: jax.Array          # (n,) int32 — total prefill stream length
                             # (prefix + true prompt length)
    pf_pos: jax.Array        # (n,) int32 — prefill progress (stream
                             # positions already written)
    prefilling: jax.Array    # (n,) bool — slot mid-prefill
    prefix: Any = None       # (n, prefix_len, d) patch prefix embeds
                             # (chunked VLM pools; else None)
    draft: Any = None        # draft model's own cache (speculative
                             # pools with drafter="model"; else None)
    slot_accepted: Any = None  # (n,) int32 — Σ extra tokens emitted
                             # beyond 1/iteration (speculative pools)
    slot_windows: Any = None   # (n,) int32 — Σ verify windows run
    priority: Any = None     # (n,) int32 — request priority class
                             # (lower = more urgent; SLO layer)
    deadline: Any = None     # (n,) float32 — request deadline (host
                             # clock seconds; +inf = none)
    slot_layers: Any = None  # (n,) int32 — Σ decoder blocks applied
                             # across this request's decode steps
                             # (adaptive depth; == L·decodes otherwise)
    slot_decodes: Any = None  # (n,) int32 — Σ decode tokens the depth
                             # sum covers (mean depth = layers/decodes)
    chunk_iters: Any = None  # scalar int32 — loop iterations whose chunk
                             # branch ran (monotonic: reset_stats
                             # leaves it, as it leaves chunk_rows)
    chunk_rows: Any = None   # scalar int32 — Σ prefilling rows at those
                             # iterations (the chunk forward runs all n)
    decode_iters: Any = None  # scalar int32 — loop iterations whose
                             # decode branch ran (monotonic, as above)
    decode_pages: Any = None  # scalar int32 — Σ over the n rows of
                             # ceil(cur_len / kv_block) at those
                             # iterations: the pages the decode kernel
                             # reads per layer (0 for a dense cache)

    def tree_flatten(self):
        return (self.cache, self.next_token, self.cur_len, self.n_emitted,
                self.budget, self.active, self.done, self.request_id,
                self.keys, self.out, self.steps, self.slot_steps,
                self.prompt, self.plen, self.pf_pos, self.prefilling,
                self.prefix, self.draft, self.slot_accepted,
                self.slot_windows, self.priority, self.deadline,
                self.slot_layers, self.slot_decodes, self.chunk_iters,
                self.chunk_rows, self.decode_iters, self.decode_pages), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@dataclasses.dataclass
class FinishedRequest:
    request_id: int
    tokens: np.ndarray       # (length,) — EOS included when hit
    length: int              # emitted tokens, EOS included
    text_length: int         # tokens before EOS
    hit_eos: bool
    mean_depth: float = 0.0  # mean decoder blocks applied per decode
                             # token (== cfg.n_layers unless adaptive
                             # depth exited early / routed around)


@dataclasses.dataclass
class _Queued:
    request_id: int
    prompt: Any              # (1, L) int32, 1 <= L <= prompt_len
    max_new: int
    key: Any                 # (2,) uint32 or None (derive from rid)
    prefix_embeds: Any = None
    frames: Any = None
    priority: int = 0        # lower = more urgent (SLO layer)
    deadline: float = float("inf")
    admitted_wall: Optional[float] = None   # time.monotonic() when
                             # _admit_queued first gave it a slot


@dataclasses.dataclass
class PreemptedRequest:
    """A resident request evicted by ``preempt_slots``: everything
    needed to re-queue it for recompute-from-prompt, plus the host-side
    snapshot of what it had already emitted (a streaming front-end must
    not re-deliver those tokens; a replay's regenerated prefix must
    MATCH them bit-for-bit — greedy decode and the emission-index PRNG
    keying both guarantee it)."""

    request_id: int
    prompt: np.ndarray       # (1, L) int32 — the original prompt
    max_new: int             # the original budget (full recompute)
    key: Any                 # the original explicit key (None = derived
                             # from request_id, so a replay re-derives
                             # the identical key)
    tokens: np.ndarray       # (n_emitted,) — snapshot at preemption
    priority: int = 0
    deadline: float = float("inf")
    prefix_embeds: Any = None
    frames: Any = None


# =========================== prefix index ===================================

@dataclasses.dataclass
class _PrefixEntry:
    """One cached full prompt block. ``block_id`` is the physical pool
    block (filled from the device table right after the registering
    admission); ``ready`` flips once the registering slot has finished
    prefilling it (only READY entries are matchable — a half-written
    block must never be shared); ``row_refs`` counts resident rows
    referencing the entry (the registering row included), so eviction
    can't pull a block out from under a live table."""

    block_id: int = -1
    ready: bool = False
    row_refs: int = 0


class _PrefixIndex:
    """Host-side content-addressed index over READY prompt blocks.

    Keys are **chain hashes**: block ``i``'s key digests block
    ``i-1``'s key plus block ``i``'s token ids (VLM streams seed the
    chain with a digest of the request's patch embeds), so a key
    match proves the ENTIRE prefix up to and including that block is
    identical — matching is a per-block dict probe, not a token
    comparison. Hashes are computed host-side at admission: the token
    ids are already on the host (they arrived in ``submit``), the
    index is host state anyway (the device has no dict), and hashing
    ~plen/block small byte strings is noise next to a prefill — doing
    it in-graph would buy nothing and cost a device round-trip per
    probe. Entries are kept in LRU order (an :class:`OrderedDict`);
    eviction picks the least-recently-used READY entry with no
    resident references.
    """

    def __init__(self, block: int):
        self.block = int(block)
        self.entries: "collections.OrderedDict[bytes, _PrefixEntry]" = \
            collections.OrderedDict()

    @staticmethod
    def seed(prefix_embeds) -> bytes:
        """Chain seed for a request: VLM patch embeds digest (distinct
        images diverge at block 0), empty otherwise."""
        if prefix_embeds is None:
            return b""
        return hashlib.blake2b(np.ascontiguousarray(
            np.asarray(prefix_embeds)).tobytes(),
            digest_size=16).digest()

    def hashes(self, tokens: np.ndarray, prefix_len: int,
               seed: bytes) -> List[bytes]:
        """Chain hash of every FULL stream block of a prompt (stream =
        ``prefix_len`` patch positions then the tokens)."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        plen = prefix_len + len(tokens)
        h = hashlib.blake2b(seed, digest_size=16).digest()
        out = []
        for jb in range(plen // self.block):
            lo = max(0, jb * self.block - prefix_len)
            hi = (jb + 1) * self.block - prefix_len
            seg = tokens[lo:hi] if hi > 0 else tokens[:0]
            h = hashlib.blake2b(h + seg.tobytes(),
                                digest_size=16).digest()
            out.append(h)
        return out

    def lookup(self, hs: List[bytes], cap: int, dead=frozenset()):
        """Longest READY prefix run of ``hs``, at most ``cap`` blocks
        (pure: no LRU/ref mutation — admission planning must be able
        to back out). ``dead`` holds keys already planned for eviction
        this round: their pins are released before alloc runs, so
        mapping them would race the fresh-block allocator. Returns
        (keys, block_ids)."""
        keys: List[bytes] = []
        ids: List[int] = []
        for h in hs[:cap]:
            e = self.entries.get(h)
            if e is None or not e.ready or e.block_id < 0 or h in dead:
                break
            keys.append(h)
            ids.append(e.block_id)
        return keys, ids

    def pick_victim(self, reserved) -> Optional[bytes]:
        """LRU READY entry with no resident references (and not
        reserved by the admission round being planned)."""
        for h, e in self.entries.items():
            if h in reserved or not e.ready or e.row_refs > 0 \
                    or e.block_id < 0:
                continue
            return h
        return None

    def evict(self, h: bytes) -> int:
        """Drop an entry; returns its block id (whose pin the device
        releases in the same admission call)."""
        return self.entries.pop(h).block_id

    def register(self, h: bytes) -> _PrefixEntry:
        """Add a PENDING entry owned by the registering row."""
        e = _PrefixEntry(row_refs=1)
        self.entries[h] = e
        return e

    def touch(self, h: bytes) -> None:
        self.entries.move_to_end(h)

    def __len__(self) -> int:
        return len(self.entries)


# =========================== shardings ======================================

def pool_shardings(cfg, n_slots: int, max_len: int, max_new_cap: int,
                   rules, mesh=None, *, kv: str = "dense",
                   kv_block: int = 16, kv_blocks: Optional[int] = None,
                   prompt_len: int = 0, prefix_len: int = 0,
                   draft_cfg=None):
    """NamedShardings for a ``SlotPool`` under ``rules``.

    Per-slot registers, dense cache rows, and the chunked-mode prompt
    buffers shard over the ``SLOT`` logical axis (→ the data mesh
    axes); a paged cache's block pool shards over ``BLOCK`` instead
    (``KVCache.shardings``). ``prompt_len``/``prefix_len`` size the
    chunked-prefill buffers (0 = one-shot pool, zero-width buffer /
    no prefix leaf). Non-dividing counts fall back to replicated via
    the dims-aware spec.
    """
    abs_cache = engine.make_cache(cfg, n_slots, max_len, mode="abstract",
                                  kv_impl=kv, kv_block=kv_block,
                                  kv_blocks=kv_blocks)
    cache_sh = engine.cache_shardings(cfg, rules, mesh, cache=abs_cache,
                                      row_axis=sh.SLOT)
    vec = rules.sharding((sh.SLOT,), mesh, dims=(n_slots,))
    rep = rules.sharding((), mesh)
    return SlotPool(
        cache=cache_sh, next_token=vec, cur_len=vec, n_emitted=vec,
        budget=vec, active=vec, done=vec, request_id=vec,
        keys=rules.sharding((sh.SLOT, None), mesh, dims=(n_slots, 2)),
        out=rules.sharding((sh.SLOT, None), mesh,
                           dims=(n_slots, max_new_cap)),
        steps=rep, slot_steps=rep,
        prompt=rules.sharding((sh.SLOT, None), mesh,
                              dims=(n_slots, prompt_len)),
        plen=vec, pf_pos=vec, prefilling=vec,
        prefix=(rules.sharding((sh.SLOT, None, None), mesh,
                               dims=(n_slots, prefix_len, cfg.d_model))
                if prefix_len else None),
        draft=(engine.cache_shardings(
            draft_cfg, rules, mesh,
            cache=engine.make_cache(draft_cfg, n_slots, max_len,
                                    mode="abstract"),
            row_axis=sh.SLOT) if draft_cfg is not None else None),
        slot_accepted=vec, slot_windows=vec,
        priority=vec, deadline=vec,
        slot_layers=vec, slot_decodes=vec,
        chunk_iters=rep, chunk_rows=rep, decode_iters=rep, decode_pages=rep)


def _under_mesh(fn, mesh):
    """``fn`` called with ``mesh`` as the abstract context mesh when the
    pool spans several devices, so the Pallas kernels traced inside can
    wrap themselves for it (``kernels.per_device``: the TPU compiler
    cannot partition them). Only the abstract mesh is set: a concrete
    one would also bind every ``device_put`` traced inside to the mesh's
    devices. Unchanged on one device."""
    if mesh is None or mesh.size == 1:
        return fn

    def call(*args):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return fn(*args)
    return call


# =========================== scheduler ======================================

class DecodeScheduler:
    """Continuous-batching driver over a fixed slot pool.

    Args:
      params, cfg: model.
      n_slots: decode slots (cache row count).
      prompt_len: MAXIMUM prompt length; for pure-attention families
        (dense/vlm/audio) submitted prompts may be any length in
        ``[1, prompt_len]`` and are right-padded to power-of-two
        buckets at admission (≤ log2(prompt_len)+1 compiled prefill
        shapes). SSM/hybrid/MoE prompts must be exactly this long
        (right padding is not exact for recurrent state / expert
        capacity).
      max_new_cap: per-slot output buffer capacity; per-request
        ``max_new`` must not exceed it. ``max_len`` is
        ``prompt_len + prefix_len + max_new_cap + 1`` — identical to
        the batch-synchronous sizing, so logits match bitwise.
      eos_id: retirement token.
      sampling: ``SamplingParams`` (greedy default).
      rules / mesh: optional sharding; the pool is placed with
        ``pool_shardings`` when a mesh is available.
      prefix_len: VLM patch-prefix length (0 otherwise).
      seed: base PRNG seed; request r's key is
        ``fold_in(PRNGKey(seed), r)`` (derived in-graph at admission)
        unless ``submit`` is given an explicit key.
      admit_threshold: free slots worth pausing a segment for.
      kv: self-attention cache layout, "dense" | "paged".
      kv_block: paged block size (tokens per block).
      kv_blocks: paged pool capacity in blocks. ``None`` = dense-
        equivalent (``n_slots * ceil(max_len / kv_block)``); serving
        pools pass less and admit MORE slots at equal cache memory,
        because each request only holds
        ``ceil((true_prompt + prefix + max_new + 1) / kv_block)``
        blocks instead of a full ``max_len`` column.
      prefill: "oneshot" (admission runs one monolithic batched
        prefill, stalling running slots for the whole prompt) or
        "chunked" (admission just assigns the slot and allocs blocks;
        the prompt prefills INSIDE the decode loop, ``chunk_tokens``
        stream positions per iteration, interleaved with one decode
        token for every running slot — bounded per-step work, so a
        long prompt cannot stall the pool). Chunked requires an
        attention-decoder family (dense/moe/vlm): SSM/hybrid fold
        recurrent state through a full-prompt forward and audio needs
        its encoder run up front. Greedy outputs are bit-identical
        between the two modes (tests pin it across chunk sizes).
      chunk_tokens: chunked-mode prefill chunk size (stream positions
        advanced per in-graph iteration per prefilling slot). Smaller
        = tighter inter-token latency bound for running slots, more
        iterations per prompt; the compiled step count does NOT depend
        on it (one trace serves every prompt length — no buckets).
      speculative: a ``speculative.SpecConfig`` turns every decode
        iteration into draft-k/verify-once (DESIGN.md §8.4): a cheap
        proposer drafts k candidates per running slot, ONE target
        forward scores all k+1 window positions through the block
        table (``engine.verify_step``), and a data-dependent prefix is
        accepted in-graph — ``cur_len`` advances by ``accepted + 1``
        and up to k+1 tokens are emitted per iteration. Greedy outputs
        stay BITWISE identical to the non-speculative pool; sampled
        outputs draw the identical per-emission key stream. Requires
        ``prefill="chunked"`` (the drafter reads the resident prompt;
        verification rides the chunked write path).
      draft_params / draft_cfg: the draft model for
        ``speculative.drafter == "model"`` — a small zoo LM with the
        TARGET's vocab (e.g. smollm-135m drafting for qwen2-7b). It
        keeps its own per-slot cache in the pool (dense layout: the
        draft is small by construction, so its cache is not worth
        block-accounting) and prefills the prompt alongside the target
        inside the same chunked iterations.
      prefill_only: run this pool as the PREFILL TIER of a
        disaggregated deployment (``serve/disagg.py``): a slot whose
        prompt finishes prefilling retires (``done``) instead of
        flipping to RUNNING — it never decodes. The host harvests it
        with ``harvest_prefilled`` (first sampled token + resident KV
        blocks), ships the blocks to a decode-tier pool
        (``KVCache.export_rows`` → ``splice_requests``), and frees the
        slot with ``release_slots``. Requires ``prefill='chunked'``
        (the tier IS the chunked admission path) and ``kv='paged'``
        (shipping is block-granular).
    """

    def __init__(self, params, cfg, *, n_slots: int, prompt_len: int,
                 max_new_cap: int, eos_id: int = 1,
                 sampling: sampling_lib.SamplingParams =
                 sampling_lib.SamplingParams(),
                 rules=None, mesh=None, prefix_len: int = 0, seed: int = 0,
                 admit_threshold: int = 1, kv: str = "dense",
                 kv_block: int = 16, kv_blocks: Optional[int] = None,
                 prefill: str = "oneshot", chunk_tokens: int = 16,
                 prefix_cache: bool = False,
                 speculative: Optional[spec_lib.SpecConfig] = None,
                 draft_params=None, draft_cfg=None,
                 prefill_only: bool = False):
        if n_slots < 1 or max_new_cap < 1:
            raise ValueError("need n_slots >= 1 and max_new_cap >= 1")
        if not 1 <= admit_threshold <= n_slots:
            raise ValueError("admit_threshold must be in [1, n_slots]")
        if kv not in ("dense", "paged"):
            raise ValueError(f"kv must be 'dense' or 'paged'; got {kv!r}")
        if prefill not in ("oneshot", "chunked"):
            raise ValueError(f"prefill must be 'oneshot' or 'chunked'; "
                             f"got {prefill!r}")
        if prefill == "chunked":
            if cfg.family not in ("dense", "moe", "vlm"):
                raise ValueError(
                    f"prefill='chunked' requires an attention-decoder "
                    f"family (dense/moe/vlm); family {cfg.family!r} "
                    f"prefills through a full-prompt forward")
            if chunk_tokens < 1:
                raise ValueError("chunk_tokens must be >= 1")
        if prefix_cache and (prefill != "chunked" or kv != "paged"):
            raise ValueError(
                "prefix_cache=True requires prefill='chunked' (a hit "
                "starts prefilling at its first uncached block, which "
                "only the chunked path's per-row offsets support) and "
                "kv='paged' (sharing is a block-table mapping); got "
                f"prefill={prefill!r}, kv={kv!r}")
        if prefill_only:
            if prefill != "chunked" or kv != "paged":
                raise ValueError(
                    "prefill_only=True (disaggregated prefill tier) "
                    "requires prefill='chunked' and kv='paged': the "
                    "tier exists to run chunked admission and ship "
                    f"block-granular KV; got prefill={prefill!r}, "
                    f"kv={kv!r}")
            if speculative is not None:
                raise ValueError(
                    "a prefill-only tier never decodes; speculative "
                    "decoding belongs on the decode tier")
        if speculative is not None:
            spec_lib.validate(speculative, cfg, prefill, draft_cfg,
                              draft_params, prefix_len)
        elif draft_params is not None or draft_cfg is not None:
            raise ValueError("draft_params/draft_cfg need "
                             "speculative=SpecConfig(drafter='model')")
        if prefix_len and (cfg.family != "vlm"
                           or prefix_len != cfg.n_patches):
            # The in-graph admission derives the patch prefix from
            # cfg.n_patches; a diverging prefix_len would let the host
            # block-accounting and the device alloc disagree.
            raise ValueError(
                f"prefix_len must be 0, or cfg.n_patches "
                f"({getattr(cfg, 'n_patches', 'n/a')}) on a vlm config; "
                f"got {prefix_len} for family {cfg.family!r}")
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.prompt_len = prompt_len
        self.max_new_cap = max_new_cap
        self.eos_id = int(eos_id)
        self.sampling = sampling
        self.rules = rules
        self.mesh = mesh if mesh is not None else getattr(rules, "mesh",
                                                          None)
        self.prefix_len = prefix_len
        self.admit_threshold = admit_threshold
        self.max_len = prompt_len + prefix_len + max_new_cap + 1
        self.prefill = prefill
        self.chunk_tokens = int(chunk_tokens)
        self.kv = kv
        self.kv_block = kv_block
        self.kv_blocks = (n_slots * kvc.blocks_needed(self.max_len,
                                                      kv_block)
                          if kv_blocks is None else int(kv_blocks))
        self._kv_key = engine.kv_key(cfg)
        self.prefill_only = bool(prefill_only)
        self.speculative = speculative
        self.draft_cfg = draft_cfg
        self._draft_params = draft_params
        # Right padding is EXACT only for pure-attention prefills
        # (causal masking keeps real tokens blind to pad lanes). An SSM
        # recurrence keeps updating its conv/h state through the pad
        # tail, and MoE capacity-limited routing lets pad tokens
        # displace real ones from expert slots — both would silently
        # break the bit-identical guarantee, so those families require
        # exact-length prompts (one prefill shape, as before). Chunked
        # mode keeps the same per-family rule: a ragged final chunk
        # puts its garbage tail inside the row's own routing group, so
        # MoE stays exact-length there too.
        if prefill == "chunked":
            self._bucketed = cfg.family in ("dense", "vlm")
        else:
            self._bucketed = cfg.family in ("dense", "vlm", "audio")
        self._base_key = jax.random.PRNGKey(seed)
        self._next_rid = 0
        self.queue: List[_Queued] = []
        # host mirrors of slot occupancy and (paged) free blocks, kept
        # in lockstep with the device flags so the scheduling path
        # never blocks on a device→host read
        self._busy = np.zeros(n_slots, bool)
        self._slot_blocks = np.zeros(n_slots, np.int64)
        self._free_blocks = self.kv_blocks
        # host copy of each resident slot's request (set at admission,
        # cleared at harvest/preemption): preemption re-queues from it
        # and the SLO layer reads slot→priority without a device sync
        self._slot_req: List[Optional[_Queued]] = [None] * n_slots
        # prefix cache: host-side content-addressed index + per-slot
        # bookkeeping of matched (hit) and registered entry keys
        self.prefix_cache = bool(prefix_cache)
        self._prefix_index = (_PrefixIndex(kv_block) if prefix_cache
                              else None)
        self._slot_hits: List[List[bytes]] = [[] for _ in range(n_slots)]
        self._slot_regs: List[List[bytes]] = [[] for _ in range(n_slots)]
        self.prefix_hit_blocks = 0    # Σ blocks mapped instead of prefilled
        self.prefix_evictions = 0
        # driver stats (busy_slot_steps lives in-graph: pool.slot_steps)
        self.total_steps = 0          # loop iterations across segments
        self.tokens_emitted = 0
        self.peak_resident = 0        # max co-resident requests, sampled
        #                               post-admission (a whole admitted
        #                               batch can retire within one
        #                               segment, so post-harvest
        #                               active_count misses it)
        self.preemptions = 0          # preempt_slots victims (SLO layer)
        # adaptive-depth run totals, accumulated host-side at harvest
        # (slot counters recycle with their slot)
        self.depth_layers = 0         # Σ decoder blocks applied
        self.depth_tokens = 0         # Σ decode tokens they cover

        self.pool = self._init_pool()
        on_mesh = functools.partial(_under_mesh, mesh=self.mesh)
        # chunked admission runs NO model forward: assign registers +
        # alloc blocks, and let the in-graph step do the prefilling
        self._admit_fn = on_mesh(jax.jit(self._build_assign()
                                         if prefill == "chunked"
                                         else self._build_admit()))
        self._step_fn = on_mesh(jax.jit(self._build_step()))
        self._preempt_fn = on_mesh(jax.jit(self._build_preempt()))
        # disaggregated decode-tier admission (register + alloc +
        # import shipped blocks); only meaningful for paged chunked
        # pools that DO decode
        self._splice_fn = (on_mesh(jax.jit(self._build_splice()))
                           if prefill == "chunked" and kv == "paged"
                           and not prefill_only else None)

    # ---------------- pool construction ----------------

    def _init_pool(self) -> SlotPool:
        n, cap = self.n_slots, self.max_new_cap
        chunked = self.prefill == "chunked"
        pbuf = self.prompt_len if chunked else 0
        pfx = self.prefix_len if chunked else 0
        pool = SlotPool(
            cache=engine.make_cache(self.cfg, n, self.max_len,
                                    kv_impl=self.kv, kv_block=self.kv_block,
                                    kv_blocks=self.kv_blocks),
            next_token=jnp.zeros((n,), jnp.int32),
            cur_len=jnp.ones((n,), jnp.int32),
            n_emitted=jnp.zeros((n,), jnp.int32),
            budget=jnp.zeros((n,), jnp.int32),
            active=jnp.zeros((n,), bool),
            done=jnp.zeros((n,), bool),
            request_id=jnp.full((n,), -1, jnp.int32),
            keys=jnp.zeros((n, 2), jnp.uint32),
            out=jnp.zeros((n, cap), jnp.int32),
            steps=jnp.asarray(0, jnp.int32),
            slot_steps=jnp.asarray(0, jnp.int32),
            prompt=jnp.zeros((n, pbuf), jnp.int32),
            plen=jnp.zeros((n,), jnp.int32),
            pf_pos=jnp.zeros((n,), jnp.int32),
            prefilling=jnp.zeros((n,), bool),
            prefix=(jnp.zeros((n, pfx, self.cfg.d_model),
                              self.cfg.dtype("compute"))
                    if pfx else None),
            # the draft model's cache rides the pool as a dense column
            # layout: the draft is small by construction, so its bytes
            # are noise next to the target's pool and not worth block
            # accounting (alloc/free are no-ops; stale rows past a
            # retired request are causally invisible, same as dense kv)
            draft=(engine.make_cache(self.draft_cfg, n, self.max_len)
                   if self.draft_cfg is not None else None),
            slot_accepted=jnp.zeros((n,), jnp.int32),
            slot_windows=jnp.zeros((n,), jnp.int32),
            priority=jnp.zeros((n,), jnp.int32),
            deadline=jnp.full((n,), jnp.inf, jnp.float32),
            slot_layers=jnp.zeros((n,), jnp.int32),
            slot_decodes=jnp.zeros((n,), jnp.int32),
            chunk_iters=jnp.asarray(0, jnp.int32),
            chunk_rows=jnp.asarray(0, jnp.int32),
            decode_iters=jnp.asarray(0, jnp.int32),
            decode_pages=jnp.asarray(0, jnp.int32))
        if self.rules is not None and self.mesh is not None \
                and self.mesh.size > 1:
            shd = pool_shardings(self.cfg, n, self.max_len, cap,
                                 self.rules, self.mesh, kv=self.kv,
                                 kv_block=self.kv_block,
                                 kv_blocks=self.kv_blocks,
                                 prompt_len=pbuf, prefix_len=pfx,
                                 draft_cfg=self.draft_cfg)
            pool = jax.tree.map(jax.device_put, pool, shd)
        return pool

    def cache_bytes(self) -> int:
        """Device bytes held by the pool's cache (all entries)."""
        return sum(a.size * a.dtype.itemsize
                   for a in jax.tree.leaves(self.pool.cache))

    # ---------------- in-graph admission (batched prefill) ------------

    def _build_admit(self):
        cfg, rules, sp = self.cfg, self.rules, self.sampling
        n, kv_key = self.n_slots, self._kv_key
        base_key = self._base_key

        def admit(params, pool: SlotPool, prompts, true_lens, slots, rids,
                  max_news, keys, derive, mask, prios, deadlines,
                  prefix_embeds, frames) -> SlotPool:
            """Admit up to n requests in one prefill.

            prompts (n, Sb) right-padded to the bucket width Sb;
            true_lens (n,) real prompt lengths; slots (n,) a
            PERMUTATION of range(n) whose masked rows are the free
            slots being filled; mask (n,) bool; derive (n,) bool —
            fold the request key from ``rids`` (else use ``keys`` as
            given). Unmasked rows are untouched (attention K/V) or
            rewrite their own slot's current values (spliced parts),
            so the call is exact for any admitted count.
            """
            prefix = 0
            if cfg.family == "vlm" and prefix_embeds is not None:
                prefix = cfg.n_patches
            cache = pool.cache
            if kv_key is not None:
                # Lifecycle first: release whatever the freed slot last
                # held, then reserve this request's own budget — the
                # paged pool recycles retired blocks immediately.
                node = cache[kv_key].free(slots, mask=mask)
                node = node.alloc(
                    slots, true_lens + prefix + max_news + 1, mask=mask)
                cache = {**cache, kv_key: node}
            logits, cacheB = engine.prefill(
                params, cfg, prompts, cache, rules,
                prefix_embeds=prefix_embeds, frames=frames,
                rows=slots, mask=mask)
            rkeys = jnp.where(
                derive[:, None],
                jax.vmap(lambda r: jax.random.fold_in(base_key, r))(rids),
                keys)
            # Token at emission index 0 comes from each row's LAST REAL
            # position of the prefill logits (bucketed rows are
            # right-padded, so [:, -1] would read a pad lane).
            last = prefix + true_lens - 1
            k0 = sampling_lib.step_keys(rkeys, jnp.zeros((n,), jnp.int32))
            tok0 = sampling_lib.sample_slots(
                logits[jnp.arange(n), last], k0, sp)
            cur0 = true_lens + prefix + 1

            def splice(full, new):
                # spliced leaves carry the slot dim at axis 1
                return kvc.set_rows(full.swapaxes(0, 1), slots,
                                    new.swapaxes(0, 1), mask).swapaxes(0, 1)

            def sreg(vec, new):
                return kvc.set_rows(vec, slots, new, mask)

            # Attention KVCache entries were written in-pool by prefill
            # (rows/mask-aware); SSM and audio-cross state comes back
            # prompt-batch-wide and splices along its batch axis.
            new_cache = {}
            for key in cacheB:
                if isinstance(cacheB[key], kvc.KVCache):
                    new_cache[key] = cacheB[key]
                else:
                    new_cache[key] = jax.tree.map(splice, pool.cache[key],
                                                  cacheB[key])

            return dataclasses.replace(
                pool, cache=new_cache,
                next_token=sreg(pool.next_token, tok0),
                cur_len=sreg(pool.cur_len, cur0.astype(jnp.int32)),
                n_emitted=sreg(pool.n_emitted, jnp.zeros((n,), jnp.int32)),
                budget=sreg(pool.budget, max_news),
                active=sreg(pool.active, jnp.ones((n,), bool)),
                done=sreg(pool.done, jnp.zeros((n,), bool)),
                request_id=sreg(pool.request_id, rids),
                keys=sreg(pool.keys, rkeys),
                out=sreg(pool.out, jnp.zeros_like(pool.out)),
                priority=sreg(pool.priority, prios),
                deadline=sreg(pool.deadline, deadlines),
                slot_layers=sreg(pool.slot_layers,
                                 jnp.zeros((n,), jnp.int32)),
                slot_decodes=sreg(pool.slot_decodes,
                                  jnp.zeros((n,), jnp.int32)))

        return admit

    # ---------------- in-graph admission (chunked: assign only) -------

    def _build_assign(self):
        """Chunked-mode admission: assign slot + alloc blocks, NO model
        forward — the prompt rides into the pool's resident buffers and
        the in-graph step prefills it ``chunk_tokens`` positions per
        iteration, interleaved with decode. Admission cost is a
        register/table scatter however long the prompt is.
        """
        n, kv_key = self.n_slots, self._kv_key
        base_key = self._base_key

        def assign(params, pool: SlotPool, prompts, plens, slots, rids,
                   max_news, keys, derive, mask, prios, deadlines,
                   prefix, shared, pin, pf0, evict) -> SlotPool:
            """Assign up to n requests into free slots.

            prompts (n, prompt_len) right-padded token buffers; plens
            (n,) total prefill STREAM lengths (prefix + true prompt
            length); slots/mask/rids/max_news/keys/derive as in
            ``_admit``; prefix (n, prefix_len, d) patch embeds or
            None. ``params`` is unused (signature kept parallel to
            ``_admit`` so the host driver is mode-agnostic).

            Prefix-cache extras (None / zeros when disabled): shared
            (n, bpr) physical block ids to MAP into each row's leading
            table columns (a hit's cached prefix), pin (n, bpr) bool
            columns taking an extra index-pin reference, pf0 (n,)
            initial prefill offsets (a hit starts at its first
            uncached block), evict (kv_blocks,) block ids whose index
            pins are released THIS call, before allocating — one
            device dispatch covers evict + free + alloc.
            """
            del params
            cache = pool.cache
            # Lifecycle exactly as one-shot admission: release the
            # freed slot's previous blocks, reserve this request's own
            # budget. The blocks are reserved BEFORE any prefill runs,
            # so chunk writes always have somewhere to land.
            node = cache[kv_key]
            if evict is not None:
                node = node.release(evict)
            node = node.free(slots, mask=mask)
            node = node.alloc(slots, plens + max_news + 1, mask=mask,
                              shared=shared, pin=pin)
            cache = {**cache, kv_key: node}
            rkeys = jnp.where(
                derive[:, None],
                jax.vmap(lambda r: jax.random.fold_in(base_key, r))(rids),
                keys)

            def sreg(vec, new):
                return kvc.set_rows(vec, slots, new, mask)

            return dataclasses.replace(
                pool, cache=cache,
                next_token=sreg(pool.next_token, jnp.zeros((n,), jnp.int32)),
                cur_len=sreg(pool.cur_len, jnp.ones((n,), jnp.int32)),
                n_emitted=sreg(pool.n_emitted, jnp.zeros((n,), jnp.int32)),
                budget=sreg(pool.budget, max_news),
                active=sreg(pool.active, jnp.zeros((n,), bool)),
                done=sreg(pool.done, jnp.zeros((n,), bool)),
                request_id=sreg(pool.request_id, rids),
                keys=sreg(pool.keys, rkeys),
                out=sreg(pool.out, jnp.zeros_like(pool.out)),
                prompt=sreg(pool.prompt, prompts),
                plen=sreg(pool.plen, plens),
                pf_pos=sreg(pool.pf_pos, pf0),
                prefilling=sreg(pool.prefilling, jnp.ones((n,), bool)),
                prefix=(pool.prefix if prefix is None
                        else sreg(pool.prefix, prefix)),
                priority=sreg(pool.priority, prios),
                deadline=sreg(pool.deadline, deadlines),
                slot_layers=sreg(pool.slot_layers,
                                 jnp.zeros((n,), jnp.int32)),
                slot_decodes=sreg(pool.slot_decodes,
                                  jnp.zeros((n,), jnp.int32)))

        return assign

    # ---------------- in-graph preemption -----------------------------

    def _build_preempt(self):
        """Victim eviction: free the masked slots' cache rows (the
        refcounted ``free`` — blocks shared with other rows or pinned
        by the prefix index survive) and return their registers to
        FREE, all in one device dispatch. The host snapshots emitted
        tokens BEFORE calling this and re-queues the request for
        recompute-from-prompt; nothing is swapped out — with prefix
        caching the replayed prompt usually maps straight back to the
        still-pinned blocks, which is why recompute wins (DESIGN.md
        §8.5)."""
        kv_key = self._kv_key

        def preempt(pool: SlotPool, mask, evict) -> SlotPool:
            """mask (n,) bool — victim slots; evict (kv_blocks,) int32
            block ids whose index pins are released in the same call
            (a mid-prefill victim's PENDING registrations are
            half-written and must leave the index), or None when the
            prefix cache is off."""
            cache = pool.cache
            if kv_key is not None:
                node = cache[kv_key]
                if evict is not None:
                    node = node.release(evict)
                node = node.free(mask=mask)
                cache = {**cache, kv_key: node}
            keep = ~mask
            return dataclasses.replace(
                pool, cache=cache,
                active=pool.active & keep,
                prefilling=pool.prefilling & keep,
                done=pool.done & keep,
                request_id=jnp.where(mask, -1, pool.request_id),
                budget=jnp.where(mask, 0, pool.budget),
                n_emitted=jnp.where(mask, 0, pool.n_emitted),
                cur_len=jnp.where(mask, 1, pool.cur_len),
                pf_pos=jnp.where(mask, 0, pool.pf_pos),
                plen=jnp.where(mask, 0, pool.plen),
                slot_layers=jnp.where(mask, 0, pool.slot_layers),
                slot_decodes=jnp.where(mask, 0, pool.slot_decodes))

        return preempt

    # ---------------- in-graph splice admission (disagg decode tier) --

    def _build_splice(self):
        """Disaggregated decode-tier admission: register + alloc +
        IMPORT shipped blocks. The spliced request arrives with its
        prompt KV already computed (on the prefill slice) and its
        first token already sampled there: this fn allocates fresh
        blocks for the full residency, scatters the shipped block
        buffer into the row's leading table columns
        (``PagedKVCache.import_rows``) and registers the slot directly
        in the RUNNING state — ``cur_len = plen + 1`` with position
        ``plen`` still unwritten, exactly the state a colocated slot
        is in the instant its final chunk flips it PREFILLING→RUNNING
        (the first decode step appends token 0's K/V at ``cur_len - 1``
        on both paths, and request keys are rid-derived on both tiers,
        which is what makes disaggregated decode bit-identical)."""
        n, kv_key = self.n_slots, self._kv_key
        base_key = self._base_key

        def splice(pool: SlotPool, prompts, plens, slots, rids,
                   max_news, keys, derive, mask, prios, deadlines, t0,
                   k_data, v_data) -> SlotPool:
            """slots/mask/rids/... as in ``_assign``; t0 (n,) int32 —
            each spliced request's prefill-sampled first token;
            k_data/v_data (L, k, n_cols, KV, block, hd) — the shipped
            block buffers for the k masked rows (already placed in
            this pool's sharding by the caller's ``device_put``)."""
            k = k_data.shape[1]
            cache = pool.cache
            node = cache[kv_key]
            node = node.free(slots, mask=mask)
            node = node.alloc(slots, plens + max_news + 1, mask=mask)
            node = node.import_rows(slots[:k], k_data, v_data,
                                    mask=mask[:k])
            cache = {**cache, kv_key: node}
            rkeys = jnp.where(
                derive[:, None],
                jax.vmap(lambda r: jax.random.fold_in(base_key, r))(rids),
                keys)

            def sreg(vec, new):
                return kvc.set_rows(vec, slots, new, mask)

            return dataclasses.replace(
                pool, cache=cache,
                next_token=sreg(pool.next_token, t0),
                cur_len=sreg(pool.cur_len,
                             (plens + 1).astype(jnp.int32)),
                n_emitted=sreg(pool.n_emitted,
                               jnp.zeros((n,), jnp.int32)),
                budget=sreg(pool.budget, max_news),
                active=sreg(pool.active, jnp.ones((n,), bool)),
                done=sreg(pool.done, jnp.zeros((n,), bool)),
                request_id=sreg(pool.request_id, rids),
                keys=sreg(pool.keys, rkeys),
                out=sreg(pool.out, jnp.zeros_like(pool.out)),
                prompt=sreg(pool.prompt, prompts),
                plen=sreg(pool.plen, plens),
                pf_pos=sreg(pool.pf_pos, plens),
                prefilling=sreg(pool.prefilling, jnp.zeros((n,), bool)),
                priority=sreg(pool.priority, prios),
                deadline=sreg(pool.deadline, deadlines),
                slot_layers=sreg(pool.slot_layers,
                                 jnp.zeros((n,), jnp.int32)),
                slot_decodes=sreg(pool.slot_decodes,
                                  jnp.zeros((n,), jnp.int32)))

        return splice

    # ---------------- in-graph decode segment -------------------------

    def _build_step(self):
        cfg, rules, sp = self.cfg, self.rules, self.sampling
        eos_id, cap, n = self.eos_id, self.max_new_cap, self.n_slots
        kv_key = self._kv_key
        chunked = self.prefill == "chunked"
        prefill_only = self.prefill_only
        C = self.chunk_tokens
        spec = self.speculative
        d_cfg = self.draft_cfg
        prefix_len = self.prefix_len
        page = self.kv_block if self.kv == "paged" else 0
        if chunked:
            stream = self.prompt_len + self.prefix_len
            # A valid bound for the speculative path too: every verify
            # window emits AT LEAST one token (acceptance only adds).
            max_iters = cap + -(-stream // C) + 1
        else:
            max_iters = cap

        def chunk_fn(params, dparams, p: SlotPool) -> SlotPool:
            """Advance every PREFILLING slot by one <=C-token chunk.

            ``engine.prefill_chunk`` writes the chunk's K/V at each
            row's own ``pf_pos`` offset (masked: only prefilling rows
            write) and attends against prior chunks through the cache
            view. A slot whose window covers its last real stream
            position samples its first token from that position's
            logits — exactly the lane the one-shot admission samples —
            and flips PREFILLING → RUNNING, so it decodes in this very
            iteration. A prefill-ONLY tier flips it PREFILLING → DONE
            instead: the first token and the resident KV blocks wait
            for the host to ship them to the decode tier
            (``harvest_prefilled``), and the decode branch of the loop
            never fires.
            """
            logits, cache = engine.prefill_chunk(
                params, cfg, p.prompt, p.cache, p.pf_pos, rules,
                chunk=C, mask=p.prefilling, prefix_embeds=p.prefix)
            draft = p.draft
            if d_cfg is not None:
                # The draft model prefills the same prompt stream into
                # ITS cache, riding the same pf_pos window (its logits
                # are discarded — the first token comes from the
                # target). Cost scales with the draft's size, which is
                # small by construction.
                _, draft = engine.prefill_chunk(
                    dparams, d_cfg, p.prompt, draft, p.pf_pos, rules,
                    chunk=C, mask=p.prefilling)
            fin = p.prefilling & (p.pf_pos + C >= p.plen)
            last = jnp.clip(p.plen - 1 - p.pf_pos, 0, C - 1)
            k0 = sampling_lib.step_keys(p.keys, jnp.zeros((n,), jnp.int32))
            t0 = sampling_lib.sample_slots(
                logits[jnp.arange(n), last], k0, sp)
            return dataclasses.replace(
                p, cache=cache, draft=draft,
                next_token=jnp.where(fin, t0, p.next_token),
                cur_len=jnp.where(fin, p.plen + 1, p.cur_len),
                pf_pos=jnp.where(p.prefilling, p.pf_pos + C, p.pf_pos),
                prefilling=p.prefilling & ~fin,
                active=(p.active if prefill_only else p.active | fin),
                done=(p.done | fin if prefill_only else p.done))

        def decode_fn(params, p: SlotPool) -> SlotPool:
            tok = p.next_token                           # (n,)
            emit = p.active
            idx = jnp.clip(p.n_emitted, 0, cap - 1)
            # a select on each row's own columns, not a scatter (see
            # kv_cache.set_rows): ``out`` is sharded by slot rows
            put = emit[:, None] & (jnp.arange(cap)[None, :] == idx[:, None])
            out = jnp.where(put, tok[:, None].astype(p.out.dtype), p.out)
            n_emitted = p.n_emitted + emit
            finished = emit & ((tok == eos_id)
                               | (n_emitted >= p.budget))
            active = emit & ~finished
            # Slot retirement frees the cache row IN-GRAPH: a paged
            # slot's blocks return to the free-list here, inside
            # the decode loop (dense: no-op). The retired row's
            # subsequent garbage appends route to the drop index,
            # so recycled blocks are never corrupted.
            cache = p.cache
            if kv_key is not None:
                cache = {**cache,
                         kv_key: cache[kv_key].free(mask=finished)}
            # Decode all slots (inactive rows compute garbage that
            # is masked). One-shot mode can let inactive rows write
            # garbage (their rows are rewritten at admission / their
            # freed tables drop it); chunked mode must NOT — a
            # mid-prefill slot's stale cur_len points INTO its
            # already-written prompt — so the append is gated.
            # Adaptive early exit: only emitting rows keep the dynamic
            # layer loop alive (retired / mid-prefill slots start
            # halted and pay no block FLOPs). `depth` feeds the
            # per-slot mean-depth stats either way (== n_layers for
            # static-depth pools).
            logits, cache, depth = engine.decode_step(
                params, cfg, tok[:, None], cache, p.cur_len, rules,
                write_mask=emit if chunked else None,
                live=emit if cfg.early_exit else None, with_depth=True)
            keys = sampling_lib.step_keys(p.keys, n_emitted)
            nxt = sampling_lib.sample_slots(logits[:, 0], keys, sp)
            return dataclasses.replace(
                p, cache=cache,
                next_token=jnp.where(active, nxt, tok),
                cur_len=p.cur_len + active,
                n_emitted=n_emitted,
                active=active,
                done=p.done | finished,
                out=out,
                slot_steps=p.slot_steps
                + jnp.sum(emit).astype(jnp.int32),
                slot_layers=p.slot_layers
                + jnp.where(emit, depth, 0).astype(jnp.int32),
                slot_decodes=p.slot_decodes + emit.astype(jnp.int32))

        def spec_decode_fn(params, dparams, p: SlotPool) -> SlotPool:
            """One draft-k/verify-once iteration for every running slot.

            Window = ``[pending, d_1..d_k]``. ONE target forward
            (``engine.verify_step``) writes the window's K/V at
            ``cur_len - 1`` through the chunk path and scores all k+1
            positions; the accepted prefix (greedy match / rejection
            sampling — ``speculative.accept``) is emitted in-graph and
            ``cur_len`` advances by ``accepted + 1``. Rejected drafts
            are NOT physically rolled back: the stale lanes sit at
            positions >= the new ``cur_len - 1``, inside the region the
            NEXT window rewrites before attending (k+1 writes cover at
            most k stale lanes), and a paged row's over-budget lanes
            route to the drop index. A slot whose accepted prefix
            contains EOS emits only up to it, retires, and frees its
            blocks in-graph THIS iteration — rejected drafts past EOS
            never burn a phantom iteration.
            """
            k = spec.k
            emit = p.active
            row = jnp.arange(n)
            t0 = p.next_token
            if d_cfg is None:
                drafts = spec_lib.draft_ngram(
                    p.prompt, p.plen - prefix_len, p.out, p.n_emitted,
                    t0, k=k, ngram=spec.ngram)
                draft = p.draft
            else:
                # k+1 cheap draft decode steps: feed the window
                # sequentially so the draft cache's valid prefix ends
                # exactly at the window end — next iteration's window
                # re-feeds (and rewrites) everything past the accept
                # point, keeping draft and target caches aligned
                # without rollback.
                # A draft with cfg.early_exit set drafts at adaptive
                # (shallow) depth — the natural cheap drafter — while
                # the target verify below stays full-depth exact.
                draft, toks, tok = p.draft, [], t0
                for j in range(k + 1):
                    dl, draft = engine.decode_step(
                        dparams, d_cfg, tok[:, None], draft,
                        p.cur_len + j, rules, write_mask=emit,
                        live=emit if d_cfg.early_exit else None)
                    tok = jnp.argmax(dl[:, 0], axis=-1).astype(jnp.int32)
                    if j < k:
                        toks.append(tok)
                drafts = jnp.stack(toks, axis=1)
            window = jnp.concatenate([t0[:, None], drafts], axis=1)
            logits, cache = engine.verify_step(
                params, cfg, window, p.cache, p.cur_len, rules,
                write_mask=emit)
            # keys for emission indices n_emitted+1 .. n_emitted+k+1:
            # the candidates' own indices plus the post-window pending
            # token's (greedy ignores them)
            wkeys = sampling_lib.window_keys(p.keys, p.n_emitted + 1,
                                             k + 1)
            acc, nxt = spec_lib.accept(logits, drafts, wkeys, sp)
            # Emit min(accepted+1, room, up to first EOS) tokens.
            jw = jnp.arange(k + 1, dtype=jnp.int32)
            room = p.budget - p.n_emitted
            eos_pos = jnp.min(jnp.where((window == eos_id)
                                        & (jw[None] <= acc[:, None]),
                                        jw[None], k + 1), axis=1)
            m = jnp.minimum(acc + 1, jnp.minimum(room, eos_pos + 1))
            m = jnp.where(emit, m, 0)
            put = emit[:, None] & (jw[None] < m[:, None])
            idx = jnp.where(put, p.n_emitted[:, None] + jw[None], cap)
            # (n, k+1, cap): window lane j lands in column idx[:, j]; a
            # select per row as in decode_fn, not a scatter
            hit = idx[:, :, None] == jnp.arange(cap)[None, None, :]
            out = jnp.where(hit.any(axis=1),
                            jnp.sum(jnp.where(hit, window[:, :, None], 0),
                                    axis=1).astype(p.out.dtype), p.out)
            n_emitted = p.n_emitted + m
            last_tok = window[row, jnp.maximum(m - 1, 0)]
            finished = emit & ((last_tok == eos_id)
                               | (n_emitted >= p.budget))
            active = emit & ~finished
            if kv_key is not None:
                cache = {**cache,
                         kv_key: cache[kv_key].free(mask=finished)}
            return dataclasses.replace(
                p, cache=cache, draft=draft,
                next_token=jnp.where(active, nxt, t0),
                cur_len=p.cur_len + m,
                n_emitted=n_emitted,
                active=active,
                done=p.done | finished,
                out=out,
                slot_steps=p.slot_steps
                + jnp.sum(emit).astype(jnp.int32),
                slot_accepted=p.slot_accepted
                + jnp.where(emit, m - 1, 0).astype(jnp.int32),
                slot_windows=p.slot_windows + emit.astype(jnp.int32),
                # verify_step always runs the TARGET at full depth (the
                # exactness anchor: adaptive shallow exits belong on
                # the DRAFT side, via draft_cfg.early_exit), so every
                # emitted token here cost n_layers target blocks.
                slot_layers=p.slot_layers
                + jnp.where(emit, m * cfg.n_layers, 0).astype(jnp.int32),
                slot_decodes=p.slot_decodes + m.astype(jnp.int32))

        def step(params, dparams, pool: SlotPool, want,
                 max_steps) -> SlotPool:
            """One device segment.

            ``want`` (traced scalar) is the number of free slots worth
            returning to the host for: the loop runs while any slot is
            busy (active or prefilling) AND fewer than ``want`` slots
            are idle. The host passes
            ``min(admit_threshold, len(queue))``, or ``n_slots + 1``
            with an empty queue — then the predicate reduces to
            ``any(busy)`` and the whole drain tail costs one dispatch
            (a freed slot has no successor, so retirement is no reason
            to pause; outputs wait for harvest).

            ``max_steps`` (traced scalar) additionally bounds this
            segment's iteration count: a streaming driver needs tokens
            surfaced (and preemption decisions re-made) every few
            iterations even when no slot frees — the host passes
            ``2**31 - 1`` to keep the classic free-slot-only pauses.

            Chunked mode interleaves inside each iteration: at most
            one ``chunk_tokens`` prefill chunk for every prefilling
            slot (skipped at runtime when none is — steady-state
            decode pays nothing) and one decode token for every
            running slot. Per-iteration work is bounded whatever
            prompt is being admitted — the inter-token latency bound
            the one-shot admission can't give.
            """
            s0 = pool.steps

            def cond_fn(p: SlotPool):
                busy = p.active | p.prefilling
                idle = n - jnp.sum(busy).astype(jnp.int32)
                return jnp.any(busy) & (idle < want) \
                    & (p.steps - s0 < max_steps)

            # Entering a segment implies the host harvested the previous
            # one: clear `done` here (free, in-graph) instead of paying
            # a host-side dispatch per harvest.
            pool = dataclasses.replace(pool,
                                       done=jnp.zeros_like(pool.done))

            def count_decode(p: SlotPool) -> SlotPool:
                # the live pages the decode attention reads per layer,
                # taken from the cur_len it is handed
                on = jnp.any(p.active)
                pages = (jnp.sum(-(-p.cur_len // page)).astype(jnp.int32)
                         if page else 0)
                return dataclasses.replace(
                    p, decode_iters=p.decode_iters + on.astype(jnp.int32),
                    decode_pages=p.decode_pages + jnp.where(on, pages, 0))

            def body_fn(p: SlotPool) -> SlotPool:
                # the scopes name each branch's ops in the compiled
                # program's metadata, and so in a device trace
                if chunked:
                    rows = jnp.sum(p.prefilling).astype(jnp.int32)
                    with jax.named_scope("serve.prefill_chunk"):
                        p = jax.lax.cond(rows > 0,
                                         lambda q: chunk_fn(params, dparams,
                                                            q),
                                         lambda q: q, p)
                    # decode only when someone is actually running
                    # (pure-prefill iterations skip the dispatch; a
                    # slot that just finished its chunk decodes NOW)
                    dec = (spec_decode_fn if spec is not None
                           else lambda pp, dd, q: decode_fn(pp, q))
                    p = count_decode(p)
                    with jax.named_scope("serve.decode"):
                        p = jax.lax.cond(jnp.any(p.active),
                                         lambda q: dec(params, dparams, q),
                                         lambda q: q, p)
                    # how many of the chunk forward's n rows did work
                    p = dataclasses.replace(
                        p, chunk_iters=p.chunk_iters
                        + (rows > 0).astype(jnp.int32),
                        chunk_rows=p.chunk_rows + rows)
                else:
                    p = count_decode(p)
                    with jax.named_scope("serve.decode"):
                        p = decode_fn(params, p)
                # steps counts LOOP iterations — including chunked
                # mode's prefill-only ones, so per-iteration wall
                # derivations and occupancy denominators stay honest
                return dataclasses.replace(p, steps=p.steps + 1)

            return core.while_loop(cond_fn, body_fn, pool,
                                   max_iters=max_iters, name="serve_step")

        return step

    # ---------------- host driver -------------------------------------

    def warmup(self) -> None:
        """Compile admission + both step variants with no-op calls.

        An all-False admission mask touches no slot state and an idle
        pool makes both while_loop variants exit immediately, so state
        is unchanged while every trace the serving loop needs is
        compiled outside the timed path. (Bucketed prompt widths still
        compile on first use per bucket.)
        """
        if self._busy.any() or self.queue:
            raise RuntimeError("warmup() must run on an idle scheduler")
        n, L = self.n_slots, self.prompt_len
        # dummy extras matching the pool's family, so the trace warmed
        # here is the one real admissions will hit
        cdt = self.cfg.dtype("compute")
        prefix_embeds = (jnp.zeros((n, self.prefix_len,
                                    self.cfg.d_model), cdt)
                         if self.prefix_len > 0 else None)
        prios = np.zeros(n, np.int32)
        deadlines = np.full(n, np.inf, np.float32)
        if self.prefill == "chunked":
            shared, pin, evict = self._no_prefix_args()
            pool = self._admit_fn(
                self.params, self.pool, np.zeros((n, L), np.int32),
                np.full(n, L + self.prefix_len, np.int32),
                np.arange(n, dtype=np.int32), np.full(n, -1, np.int32),
                np.zeros(n, np.int32), np.zeros((n, 2), np.uint32),
                np.zeros(n, bool), np.zeros(n, bool), prios, deadlines,
                prefix_embeds, shared, pin, np.zeros(n, np.int32), evict)
        else:
            frames = (jnp.zeros((n, self.cfg.n_frames, self.cfg.d_model),
                                cdt)
                      if self.cfg.family == "audio" else None)
            pool = self._admit_fn(
                self.params, self.pool, np.zeros((n, L), np.int32),
                np.full(n, L, np.int32), np.arange(n, dtype=np.int32),
                np.full(n, -1, np.int32), np.zeros(n, np.int32),
                np.zeros((n, 2), np.uint32), np.zeros(n, bool),
                np.zeros(n, bool), prios, deadlines, prefix_embeds,
                frames)
        pool = self._step_fn(self.params, self._draft_params, pool,
                             np.int32(self.n_slots + 1), _NO_STEP_CAP)
        jax.block_until_ready(pool.next_token)
        self.pool = pool

    @property
    def free_slots(self) -> int:
        return int(self.n_slots - self._busy.sum())

    @property
    def free_blocks(self) -> int:
        """Host mirror of the paged free-list (pool capacity for dense)."""
        return int(self._free_blocks)

    def blocks_for(self, true_len: int, max_new: int) -> int:
        """Blocks a request holds while resident (0 for dense)."""
        if self.kv != "paged":
            return 0
        # Must agree with the device-side alloc in _build_admit, which
        # reserves `true_len + prefix + max_new + 1` token positions.
        return int(kvc.blocks_needed(
            true_len + self.prefix_len + max_new + 1, self.kv_block))

    def _no_prefix_args(self):
        """(shared, pin, evict) admission extras with nothing shared,
        nothing pinned, nothing evicted — None when the prefix cache
        is off (the jitted assign then skips those paths entirely)."""
        if not self.prefix_cache:
            return None, None, None
        n = self.n_slots
        bpr = int(kvc.blocks_needed(self.max_len, self.kv_block))
        return (np.full((n, bpr), -1, np.int32),
                np.zeros((n, bpr), bool),
                np.full(self.kv_blocks, -1, np.int32))

    @property
    def active_count(self) -> int:
        return int(self._busy.sum())

    @property
    def pending(self) -> int:
        """Requests not yet finished (queued + in slots)."""
        return len(self.queue) + int(self._busy.sum())

    def submit(self, prompt, *, max_new: int, request_id: Optional[int] =
               None, key=None, prefix_embeds=None, frames=None,
               priority: int = 0,
               deadline: float = float("inf")) -> int:
        """Queue one request. prompt: (1, L) int32, 1 <= L <= prompt_len.

        ``priority`` (lower = more urgent) and ``deadline`` (host-clock
        seconds) ride into the slot pool as carry fields; the base
        FIFO driver ignores them — the SLO layer
        (``repro.serve.slo``) orders and preempts by them."""
        prompt = np.asarray(prompt)
        if prompt.ndim != 2 or prompt.shape[0] != 1 or \
                not 1 <= prompt.shape[1] <= self.prompt_len:
            raise ValueError(f"prompt must be (1, L) with 1 <= L <= "
                             f"{self.prompt_len}; got {prompt.shape}")
        if not self._bucketed and prompt.shape[1] != self.prompt_len:
            raise ValueError(
                f"family {self.cfg.family!r} requires exact-length "
                f"prompts (1, {self.prompt_len}): right-padding is not "
                f"exact for SSM state / MoE routing; got {prompt.shape}")
        if not 1 <= max_new <= self.max_new_cap:
            raise ValueError(f"max_new must be in [1, {self.max_new_cap}]")
        need = self.blocks_for(prompt.shape[1], max_new)
        if need > self.kv_blocks:
            # Reject up front: a request that can NEVER fit the paged
            # pool would otherwise wedge the FIFO head forever.
            raise ValueError(
                f"request needs {need} cache blocks but the paged pool "
                f"only has kv_blocks={self.kv_blocks}; raise kv_blocks "
                f"or lower max_new/prompt length")
        # prefix/frames presence must be uniform across the pool: one
        # admission batch shares a single prefill call, so a bare
        # request co-admitted with a prefixed one would silently get a
        # zeros prefix and a shifted cur_len. A pool built with
        # prefix_len > 0 therefore REQUIRES prefix_embeds on every
        # request (and an audio pool requires frames); max_len was
        # sized with prefix_len, so a mismatch would also let late K/V
        # writes clip silently at the cache boundary.
        if self.prefix_len > 0:
            pe = np.shape(prefix_embeds) if prefix_embeds is not None \
                else None
            if self.cfg.family != "vlm" or pe is None or \
                    pe[:2] != (1, self.prefix_len):
                raise ValueError(
                    f"this pool was built with prefix_len="
                    f"{self.prefix_len}: every request needs "
                    f"prefix_embeds (1, {self.prefix_len}, d); got {pe}")
        elif prefix_embeds is not None:
            raise ValueError("prefix_embeds on a pool built with "
                             "prefix_len=0; pass prefix_len at "
                             "construction")
        if self.cfg.family == "audio":
            if frames is None or np.shape(frames)[:2] != \
                    (1, self.cfg.n_frames):
                raise ValueError(
                    f"audio pool: every request needs frames "
                    f"(1, {self.cfg.n_frames}, ...); got "
                    f"{None if frames is None else np.shape(frames)}")
        elif frames is not None:
            raise ValueError(f"frames invalid for family "
                             f"{self.cfg.family!r}")
        if not self.queue and not self._busy.any():
            # first submission of a fresh run on a drained scheduler:
            # counters describe runs, not scheduler lifetimes
            self.reset_stats()
        rid = self._next_rid if request_id is None else int(request_id)
        self._next_rid = max(self._next_rid, rid) + 1
        self.queue.append(_Queued(rid, prompt, int(max_new), key,
                                  prefix_embeds, frames, int(priority),
                                  float(deadline)))
        return rid

    def _bucket(self, length: int) -> int:
        """Power-of-two prefill bucket for a prompt length."""
        if not self._bucketed:
            return self.prompt_len
        b = 1
        while b < length:
            b <<= 1
        return min(b, self.prompt_len)

    def _refresh_ready(self) -> None:
        """Flip PENDING index entries READY for slots that have left
        prefill (reads ``pool.prefilling`` — one device sync, paid only
        when some busy slot still has pending registrations)."""
        if not self.prefix_cache:
            return
        idx = self._prefix_index
        pend = [s for s in range(self.n_slots)
                if self._busy[s] and any(
                    h in idx.entries and not idx.entries[h].ready
                    for h in self._slot_regs[s])]
        if not pend:
            return
        prefilling = np.asarray(self.pool.prefilling)
        for s in pend:
            if not prefilling[s]:
                for h in self._slot_regs[s]:
                    e = idx.entries.get(h)
                    if e is not None:
                        e.ready = True

    @functools.partial(jax.profiler.annotate_function, name="serve.admit")
    def _admit_queued(self) -> int:
        """Fill free slots from the queue in ONE batched prefill.

        ``admit_threshold > 1`` coalesces admissions: an admission call
        costs one fixed-size prefill dispatch however many requests it
        carries, so waiting for a couple of free slots trades a little
        occupancy for fewer prefill dispatches (throughput knob for
        small models / fast steps; keep 1 for latency). For the paged
        cache, a request is only admitted while its blocks fit the
        free-list (FIFO head-of-line blocking — order is preserved, a
        huge request waits rather than being overtaken).
        """
        if not self.queue or self.free_slots == 0:
            return 0
        self._refresh_ready()
        # ---- planning pass: PURE index reads (lookup / pick_victim
        # mutate nothing), so coalescing or head-of-line blocking can
        # abandon the whole plan without unwinding anything.
        idx = self._prefix_index
        batch: List[_Queued] = []
        plans: List[Optional[dict]] = []
        victims: List[bytes] = []        # keys planned for eviction
        reserved: set = set()            # keys this round must not evict
        blocks_free = self._free_blocks
        while self.queue and len(batch) < self.free_slots:
            q = self.queue[0]
            need = self.blocks_for(q.prompt.shape[1], q.max_new)
            plan = None
            if self.prefix_cache:
                plen = self.prefix_len + q.prompt.shape[1]
                hs = idx.hashes(q.prompt[0], self.prefix_len,
                                idx.seed(q.prefix_embeds))
                # Sharing cap: at least one stream position must
                # prefill in-row (it produces the first token's
                # logits), and — since writes start at the first
                # uncached position — every written block is fresh,
                # so the scheduler path never triggers CoW.
                hit_keys, hit_ids = idx.lookup(
                    hs, (plen - 1) // self.kv_block, set(victims))
                need -= len(hit_keys)
                # evict LRU unreferenced entries until the fresh
                # blocks fit (each frees exactly one pinned block)
                while need > blocks_free:
                    v = idx.pick_victim(reserved | set(hit_keys))
                    if v is None:
                        break
                    victims.append(v)
                    reserved.add(v)
                    blocks_free += 1
                plan = {"hs": hs, "hit_keys": hit_keys,
                        "hit_ids": hit_ids}
            if need > blocks_free:
                break
            blocks_free -= need
            if plan is not None:
                reserved.update(plan["hit_keys"])
            plans.append(plan)
            batch.append(self.queue.pop(0))
        k = len(batch)
        if k == 0:
            return 0
        if k < min(self.admit_threshold, k + len(self.queue)) \
                and self._busy.any():
            self.queue[:0] = batch   # coalesce: admit on a later round
            return 0
        n = self.n_slots
        chunked = self.prefill == "chunked"
        L = (self.prompt_len if chunked
             else max(self._bucket(q.prompt.shape[1]) for q in batch))
        free = np.nonzero(~self._busy)[0]
        busy = np.nonzero(self._busy)[0]
        slots = np.concatenate([free, busy]).astype(np.int32)  # permutation
        mask = np.zeros(n, bool)
        mask[:k] = True
        prompts = np.zeros((n, L), np.int32)
        true_lens = np.full(n, L, np.int32)
        rids = np.full(n, -1, np.int32)
        max_news = np.zeros(n, np.int32)
        keys = np.zeros((n, 2), np.uint32)
        derive = np.zeros(n, bool)
        prios = np.zeros(n, np.int32)
        deadlines = np.full(n, np.inf, np.float32)
        for i, q in enumerate(batch):
            tl = q.prompt.shape[1]
            prompts[i, :tl] = q.prompt[0]
            true_lens[i] = tl
            rids[i] = q.request_id
            max_news[i] = q.max_new
            prios[i] = q.priority
            deadlines[i] = q.deadline
            if q.key is None:
                derive[i] = True
            else:
                keys[i] = np.asarray(q.key, np.uint32)
        prefix_embeds = frames = None
        if any(q.prefix_embeds is not None for q in batch):
            pe0 = next(q.prefix_embeds for q in batch
                       if q.prefix_embeds is not None)
            prefix_embeds = np.zeros((n,) + tuple(pe0.shape[1:]),
                                     np.asarray(pe0).dtype)
            for i, q in enumerate(batch):
                if q.prefix_embeds is not None:
                    prefix_embeds[i] = np.asarray(q.prefix_embeds)[0]
        if any(q.frames is not None for q in batch):
            f0 = next(q.frames for q in batch if q.frames is not None)
            frames = np.zeros((n,) + tuple(f0.shape[1:]),
                              np.asarray(f0).dtype)
            for i, q in enumerate(batch):
                if q.frames is not None:
                    frames[i] = np.asarray(q.frames)[0]
        if chunked:
            # assign-only admission: registers + block tables, no
            # prefill — the in-graph step does the prompt work
            plens = true_lens + np.int32(self.prefix_len)
            shared, pin, evict = self._no_prefix_args()
            pf0 = np.zeros(n, np.int32)
            regs: List[List[tuple]] = [[] for _ in range(k)]
            if self.prefix_cache:
                # ---- commit pass: the plan is final, mutate the index
                for j, key_ in enumerate(victims):
                    evict[j] = idx.evict(key_)
                self._free_blocks += len(victims)
                self.prefix_evictions += len(victims)
                for i, plan in enumerate(plans):
                    hit_keys, hit_ids = plan["hit_keys"], plan["hit_ids"]
                    for h in hit_keys:
                        idx.entries[h].row_refs += 1
                        idx.touch(h)
                    shared[i, :len(hit_ids)] = hit_ids
                    pf0[i] = len(hit_ids) * self.kv_block
                    self.prefix_hit_blocks += len(hit_ids)
                    # register every full prompt block past the hit run
                    # (pin takes a +1 index reference at alloc; the
                    # entry turns READY once this slot leaves prefill)
                    for c in range(len(hit_keys), len(plan["hs"])):
                        h = plan["hs"][c]
                        if h in idx.entries:
                            continue   # in-flight twin registered it
                        idx.register(h)
                        pin[i, c] = True
                        regs[i].append((h, c))
            self.pool = self._admit_fn(self.params, self.pool, prompts,
                                       plens, slots, rids, max_news,
                                       keys, derive, mask, prios,
                                       deadlines, prefix_embeds,
                                       shared, pin, pf0, evict)
            if self.prefix_cache and any(regs):
                # fill registered entries' physical ids from the device
                # table (one sync per admission that registers blocks)
                tbl = np.asarray(self.pool.cache[self._kv_key].table)
                for i in range(k):
                    slot = int(free[i])
                    kept = []
                    for h, c in regs[i]:
                        bid = int(tbl[slot, c])
                        if bid >= 0:
                            idx.entries[h].block_id = bid
                            kept.append((h, c))
                        else:       # defensive: row alloc failed
                            idx.entries.pop(h, None)
                    regs[i] = kept
        else:
            self.pool = self._admit_fn(self.params, self.pool, prompts,
                                       true_lens, slots, rids, max_news,
                                       keys, derive, mask, prios,
                                       deadlines, prefix_embeds, frames)
        now = time.monotonic()
        for i, q in enumerate(batch):
            if q.admitted_wall is None:
                q.admitted_wall = now
            slot = int(free[i])
            self._busy[slot] = True
            self._slot_req[slot] = q
            need = self.blocks_for(q.prompt.shape[1], q.max_new)
            if self.prefix_cache and chunked:
                need -= len(plans[i]["hit_keys"])     # fresh blocks only
                self._slot_hits[slot] = list(plans[i]["hit_keys"])
                self._slot_regs[slot] = [h for h, _ in regs[i]]
                # registered blocks stay pinned by the index after this
                # slot retires; only the rest return at harvest
                self._slot_blocks[slot] = need - len(regs[i])
            else:
                self._slot_blocks[slot] = need
            self._free_blocks -= need
        # Residency peaks right after admission, whoever drove it: a
        # whole admitted batch can retire within one segment, and bench
        # drivers call _admit_queued directly without going through
        # step() — sampling here (the common admission round) is what
        # makes every mode report peak_resident.
        self.peak_resident = max(self.peak_resident, self.active_count)
        return k

    @functools.partial(jax.profiler.annotate_function,
                       name="serve.harvest")
    def _harvest(self) -> List[FinishedRequest]:
        done = np.asarray(self.pool.done)
        if not done.any():
            return []
        out = np.asarray(self.pool.out)
        n_emitted = np.asarray(self.pool.n_emitted)
        rids = np.asarray(self.pool.request_id)
        slayers = np.asarray(self.pool.slot_layers)
        sdecodes = np.asarray(self.pool.slot_decodes)
        got = []
        for slot in np.nonzero(done)[0]:
            length = int(n_emitted[slot])
            toks = out[slot, :length].copy()
            hit_eos = length > 0 and int(toks[-1]) == self.eos_id
            dl, dt = int(slayers[slot]), int(sdecodes[slot])
            got.append(FinishedRequest(
                request_id=int(rids[slot]), tokens=toks, length=length,
                text_length=length - int(hit_eos), hit_eos=hit_eos,
                mean_depth=dl / dt if dt else 0.0))
            self.depth_layers += dl
            self.depth_tokens += dt
            self.tokens_emitted += length
            self._busy[slot] = False
            self._slot_req[slot] = None
            # the device freed these blocks in-graph at retirement; the
            # host mirror learns at harvest, before the next admission
            self._free_blocks += int(self._slot_blocks[slot])
            self._slot_blocks[slot] = 0
            if self.prefix_cache:
                # a done slot finished its prefill long ago: its
                # registrations are READY, and it no longer references
                # any entry (its table rows were freed in-graph)
                idx = self._prefix_index
                for h in self._slot_regs[slot]:
                    e = idx.entries.get(h)
                    if e is not None:
                        e.ready = True
                        e.row_refs -= 1
                for h in self._slot_hits[slot]:
                    e = idx.entries.get(h)
                    if e is not None:
                        e.row_refs -= 1
                self._slot_regs[slot] = []
                self._slot_hits[slot] = []
        # `done` is cleared in-graph at the next segment's entry (the
        # host has harvested by construction), so no dispatch here.
        # Results are RETURNED, not archived: a long-running server
        # must not accumulate every historical token array.
        return got

    def dispatch_segment(self, expect_arrivals: bool = False,
                         max_steps: Optional[int] = None) -> bool:
        """Admit + LAUNCH one device segment without waiting on it.

        The async half of ``step``: the jitted segment is dispatched
        and the call returns while the device works. The disaggregated
        driver (``serve/disagg.py``) uses this to overlap its two
        submeshes — the prefill slice's segment is launched before the
        decode slice's round blocks on its own harvest, so the slices
        compute concurrently (the paper's non-strict overlap argument
        applied across device sets). Returns False when there was
        nothing to run (idle pool, nothing admitted)."""
        self._admit_queued()
        if self.active_count == 0:
            return False
        if not self.queue and not expect_arrivals:
            want = self.n_slots + 1          # drain: never pause
        else:
            # Return once enough slots have freed *beyond those already
            # idle at entry* (idle slots the queue couldn't fill don't
            # count — an absolute threshold would exit without decoding)
            fresh = (min(self.admit_threshold, len(self.queue))
                     if self.queue else self.admit_threshold)
            want = self.free_slots + fresh
        cap = _NO_STEP_CAP if max_steps is None else np.int32(max_steps)
        with jax.profiler.TraceAnnotation("serve.dispatch"):
            self.pool = self._step_fn(self.params, self._draft_params,
                                      self.pool, np.int32(want), cap)
        return True

    def step(self, expect_arrivals: bool = False,
             max_steps: Optional[int] = None) -> List[FinishedRequest]:
        """One scheduling round: admit → device segment → harvest.

        Returns the requests that finished this round. A round with an
        empty queue and an idle pool is a no-op. With an empty queue
        the segment runs in *drain* mode: retirements don't pause the
        loop (there is nothing to admit), so the whole tail costs one
        device dispatch — UNLESS ``expect_arrivals`` is set: a driver
        that knows more requests are coming (an open request queue)
        passes True so the segment still returns on freed slots and a
        request arriving mid-drain isn't stuck behind the whole tail.

        ``max_steps`` additionally caps this round's in-graph iteration
        count: a streaming/SLO driver needs control back every few
        iterations to surface tokens and revisit preemption decisions
        even while every slot stays busy. ``None`` keeps the classic
        free-slot-only pauses.

        A prefill-only tier returns [] always: its finished rows carry
        shippable KV, not emissions — collect them with
        ``harvest_prefilled`` and free them with ``release_slots``.
        """
        if not self.dispatch_segment(expect_arrivals, max_steps):
            return []
        # one post-segment sync (needed before harvest anyway); busy
        # slot-steps accumulate in-graph next to `steps`
        with jax.profiler.TraceAnnotation("serve.segment"):
            self.total_steps = int(self.pool.steps)
        if self.prefill_only:
            return []
        return self._harvest()

    # ---------------- disaggregation hooks (serve/disagg.py) ----------

    def harvest_prefilled(self) -> List[dict]:
        """Prefill-only tier: collect rows whose prompt just finished.

        Returns one record per finished row — ``slot``, the host-side
        request ``req``, the first sampled token ``t0`` (the lane the
        colocated path samples at its PREFILLING→RUNNING flip) and the
        prefilled stream length ``plen`` — WITHOUT freeing anything:
        the slot stays resident so its blocks keep backing the KV the
        caller is about to export/ship. Call ``release_slots`` once
        the export is dispatched — and before the next segment, whose
        entry clears ``done`` in-graph."""
        if not self.prefill_only:
            raise RuntimeError("harvest_prefilled() requires a "
                               "prefill_only=True scheduler")
        self.total_steps = int(self.pool.steps)
        done = np.asarray(self.pool.done)
        if not done.any():
            return []
        t0 = np.asarray(self.pool.next_token)
        plen = np.asarray(self.pool.plen)
        return [{"slot": int(s), "req": self._slot_req[int(s)],
                 "t0": int(t0[s]), "plen": int(plen[s])}
                for s in np.nonzero(done)[0]]

    def release_slots(self, slots) -> None:
        """Free harvested-prefill rows (blocks + registers) once their
        KV has been exported — the prefill-tier half of a block
        shipment, one jitted dispatch (reuses the preemption fn:
        refcounted free + register clear). The export buffer is fresh
        (``export_rows`` gathers), so an in-flight ``device_put`` of
        it is unaffected by the blocks being recycled here.
        Prefix-index registrations flip READY and keep their pins,
        exactly as at normal retirement — later warm hits on the
        shipped prompt still map them."""
        slots = sorted({int(s) for s in np.atleast_1d(
            np.asarray(slots, np.int64))})
        if not slots:
            return
        for s in slots:
            if not 0 <= s < self.n_slots or not self._busy[s]:
                raise ValueError(f"slot {s} is not resident")
        mask = np.zeros(self.n_slots, bool)
        mask[slots] = True
        self.pool = self._preempt_fn(self.pool, mask, None)
        for s in slots:
            self._busy[s] = False
            self._slot_req[s] = None
            self._free_blocks += int(self._slot_blocks[s])
            self._slot_blocks[s] = 0
            if self.prefix_cache:
                idx = self._prefix_index
                for h in self._slot_regs[s]:
                    e = idx.entries.get(h)
                    if e is not None:
                        e.ready = True
                        e.row_refs -= 1
                for h in self._slot_hits[s]:
                    e = idx.entries.get(h)
                    if e is not None:
                        e.row_refs -= 1
                self._slot_regs[s] = []
                self._slot_hits[s] = []

    def splice_requests(self, reqs, t0s, plens, k_data,
                        v_data) -> List[int]:
        """Admit already-prefilled requests into free slots — the
        decode-tier half of a block shipment (disaggregated serving).

        ``reqs`` are the ``_Queued`` records harvested from the
        prefill tier, ``t0s`` their prefill-sampled first tokens,
        ``plens`` their prefilled stream lengths, and
        ``k_data``/``v_data`` the shipped ``(L, len(reqs), n_cols,
        KV, block, hd)`` block buffers — ideally already
        ``device_put`` into this pool's sharding, so an async transfer
        overlaps host work and the jitted splice simply consumes it
        when the bits land. The caller gates on ``free_slots`` /
        ``free_blocks`` (the same head-of-line discipline as
        ``_admit_queued``). Returns the slots filled."""
        if self._splice_fn is None:
            raise RuntimeError(
                "splice_requests needs prefill='chunked', kv='paged' "
                "and prefill_only=False (the disagg decode tier)")
        k = len(reqs)
        if k == 0:
            return []
        if k_data.shape[1] != k or k > self.free_slots:
            raise RuntimeError(
                f"splice of {k} requests needs {k} free slots and a "
                f"matching shipment; free={self.free_slots}, "
                f"shipment rows={k_data.shape[1]}")
        needs = [int(kvc.blocks_needed(int(plens[i]) + reqs[i].max_new
                                       + 1, self.kv_block))
                 for i in range(k)]
        if sum(needs) > self._free_blocks:
            raise RuntimeError(
                f"splice needs {sum(needs)} blocks; free="
                f"{self._free_blocks} (caller must gate admission)")
        n = self.n_slots
        free = np.nonzero(~self._busy)[0]
        busy = np.nonzero(self._busy)[0]
        slots = np.concatenate([free, busy]).astype(np.int32)
        mask = np.zeros(n, bool)
        mask[:k] = True
        prompts = np.zeros((n, self.prompt_len), np.int32)
        plens_a = np.zeros(n, np.int32)
        rids = np.full(n, -1, np.int32)
        max_news = np.zeros(n, np.int32)
        keys = np.zeros((n, 2), np.uint32)
        derive = np.zeros(n, bool)
        prios = np.zeros(n, np.int32)
        deadlines = np.full(n, np.inf, np.float32)
        t0v = np.zeros(n, np.int32)
        for i, q in enumerate(reqs):
            tl = q.prompt.shape[1]
            prompts[i, :tl] = q.prompt[0]
            plens_a[i] = int(plens[i])
            rids[i] = q.request_id
            max_news[i] = q.max_new
            prios[i] = q.priority
            deadlines[i] = q.deadline
            t0v[i] = int(t0s[i])
            if q.key is None:
                derive[i] = True
            else:
                keys[i] = np.asarray(q.key, np.uint32)
        self.pool = self._splice_fn(self.pool, prompts, plens_a, slots,
                                    rids, max_news, keys, derive, mask,
                                    prios, deadlines, t0v, k_data,
                                    v_data)
        filled = []
        for i, q in enumerate(reqs):
            slot = int(free[i])
            self._busy[slot] = True
            self._slot_req[slot] = q
            self._slot_blocks[slot] = needs[i]
            self._free_blocks -= needs[i]
            filled.append(slot)
        self.peak_resident = max(self.peak_resident, self.active_count)
        return filled

    # ---------------- preemption (SLO layer) --------------------------

    def preempt_slots(self, slots) -> List[PreemptedRequest]:
        """Evict resident requests from ``slots``, freeing their blocks.

        The victims' emitted tokens are snapshotted host-side and each
        request is returned as a :class:`PreemptedRequest` — re-queue
        it (``resubmit``) for recompute-from-prompt: the same
        rid-derived (or explicit) key plus emission-index PRNG keying
        regenerates the IDENTICAL token stream, and the prefix cache
        usually maps the replayed prompt straight back onto its
        still-pinned blocks. Prefix-index bookkeeping: READY
        registrations stay pinned (their cached content is valid —
        exactly what makes the replay cheap); PENDING ones are
        half-written and leave the index, their pins released in the
        same device dispatch that frees the rows.

        Must run between device segments (it is a host scheduling
        action, like admission). Harvest first: ``done`` slots already
        freed their blocks in-graph, so preempting one would
        double-free.
        """
        slots = sorted({int(s) for s in np.atleast_1d(
            np.asarray(slots, np.int64))})
        if not slots:
            return []
        for s in slots:
            if not 0 <= s < self.n_slots or not self._busy[s]:
                raise ValueError(f"slot {s} is not resident")
            if self._slot_req[s] is None:
                raise ValueError(f"slot {s} has no host request record")
        done = np.asarray(self.pool.done)
        if done[slots].any():
            raise RuntimeError("preempting a done (unharvested) slot "
                               "would double-free its blocks; harvest "
                               "first")
        self._refresh_ready()
        out = np.asarray(self.pool.out)
        n_emitted = np.asarray(self.pool.n_emitted)
        evict = None
        if self.prefix_cache:
            idx = self._prefix_index
            evicted: List[int] = []
            for s in slots:
                for h in self._slot_regs[s]:
                    e = idx.entries.get(h)
                    if e is None:
                        continue
                    if e.ready:
                        # valid cached content: keep it pinned so the
                        # replay (and everyone else) hits it
                        e.row_refs -= 1
                    else:
                        # mid-prefill: the block is half-written —
                        # nobody may ever match it
                        evicted.append(idx.evict(h))
                        self.prefix_evictions += 1
                for h in self._slot_hits[s]:
                    e = idx.entries.get(h)
                    if e is not None:
                        e.row_refs -= 1
                self._slot_regs[s] = []
                self._slot_hits[s] = []
            evict = np.full(self.kv_blocks, -1, np.int32)
            evict[:len(evicted)] = evicted
            # each evicted pin was the block's last extra reference on
            # top of the row's own (freed below): fully free again
            self._free_blocks += len(evicted)
        mask = np.zeros(self.n_slots, bool)
        mask[slots] = True
        self.pool = self._preempt_fn(self.pool, mask, evict)
        got: List[PreemptedRequest] = []
        for s in slots:
            q = self._slot_req[s]
            got.append(PreemptedRequest(
                request_id=q.request_id, prompt=q.prompt,
                max_new=q.max_new, key=q.key,
                tokens=out[s, :int(n_emitted[s])].copy(),
                priority=q.priority, deadline=q.deadline,
                prefix_embeds=q.prefix_embeds, frames=q.frames))
            self._busy[s] = False
            self._slot_req[s] = None
            self._free_blocks += int(self._slot_blocks[s])
            self._slot_blocks[s] = 0
        self.preemptions += len(slots)
        return got

    def resubmit(self, p: PreemptedRequest) -> None:
        """Re-queue a preempted request at the FRONT of the queue with
        its original rid/key/priority/deadline — the replay regenerates
        the identical stream from scratch (recompute-from-prompt)."""
        self.queue.insert(0, _Queued(p.request_id, p.prompt, p.max_new,
                                     p.key, p.prefix_embeds, p.frames,
                                     p.priority, p.deadline))

    # ---------------- stats lifecycle ---------------------------------

    def reset_stats(self) -> None:
        """Zero every run counter — host mirrors AND the in-graph
        accumulators (``steps``/``slot_steps``/``slot_accepted``/
        ``slot_windows``, zeroed by multiply — preserves device
        placement and sharding without re-initialising the pool). A
        reused scheduler's stats then describe one run, not the sum of
        its history.

        Called automatically when work is submitted to a fully idle,
        fully drained scheduler — i.e. at the start of each new run —
        so back-to-back ``run_until_drained`` calls (or ``generate``
        wrappers) each report their own counters without the caller
        doing anything. Manual ``step()`` driving mid-run is
        unaffected: the scheduler is not idle then. The lifetime
        counters of ``counters()`` are left as they are."""
        self.total_steps = 0
        self.tokens_emitted = 0
        self.peak_resident = 0
        self.prefix_hit_blocks = 0
        self.prefix_evictions = 0
        self.preemptions = 0
        self.depth_layers = 0
        self.depth_tokens = 0

        def z(a):
            return None if a is None else a * 0

        self.pool = dataclasses.replace(
            self.pool,
            steps=self.pool.steps * 0,
            slot_steps=self.pool.slot_steps * 0,
            slot_accepted=z(self.pool.slot_accepted),
            slot_windows=z(self.pool.slot_windows),
            slot_layers=z(self.pool.slot_layers),
            slot_decodes=z(self.pool.slot_decodes))

    def counters(self) -> dict:
        """Lifetime counters, which ``reset_stats()`` never zeroes:
        ``chunk_iters`` (loop iterations that ran the chunk forward),
        ``chunk_rows`` (Σ prefilling rows at those iterations, of the
        ``n_slots`` the forward computes), ``decode_iters`` (loop
        iterations that ran the decode branch) and ``decode_pages`` (Σ
        over the ``n_slots`` rows of ``ceil(cur_len / kv_block)`` at
        those iterations: the pages the decode kernel reads per layer;
        0 for a dense cache). Read from the device in one transfer, so
        only when asked. They are int32 in-graph: take differences
        modulo 2**32."""
        names = ("chunk_iters", "chunk_rows", "decode_iters",
                 "decode_pages")
        vals = jax.device_get(tuple(getattr(self.pool, k) for k in names))
        return {k: int(v) for k, v in zip(names, vals)}

    def run_until_drained(self) -> List[FinishedRequest]:
        """Drive until queue and pool are empty; returns all finished."""
        results: List[FinishedRequest] = []
        while self.pending:
            before = self.pending
            results.extend(self.step())
            if self.pending == before:   # no progress: defensive guard
                raise RuntimeError("scheduler made no progress")
        return results

    @property
    def attn_impl(self) -> str:
        """Decode-attention path this pool's steps actually run
        (``engine.resolved_attn_impl``) — e.g. "pallas-paged:interpret"
        on CPU, so benchmark output can't be misread as TPU numbers."""
        return engine.resolved_attn_impl(self.cfg, self.kv)

    @property
    def prefill_impl(self) -> str:
        """PREFILL-attention path admissions actually run
        (``engine.resolved_prefill_impl``): "dense-bucketed" (one-shot
        monolithic prefill), "flash-paged:compiled|interpret" (chunked
        through the block-table kernel), or "xla-chunked" — so
        interleaved-mode CPU interpret numbers can't be misread as TPU
        numbers either."""
        return engine.resolved_prefill_impl(self.cfg, self.kv,
                                            self.prefill)

    @property
    def transfer_impl(self) -> str:
        """How prefilled KV reaches the decode attention kernel. A
        single-tier scheduler prefills into the very pool it decodes
        from — no transfer at all — reported as "colocated" so
        disaggregated runs ("device_put:ics"/"device_put:dcn", see
        ``serve/disagg.py``) can't be confused with it."""
        return "colocated"

    @property
    def busy_slot_steps(self) -> int:
        """Σ over loop iterations of the active-slot count (device
        counter, accumulated in-graph; prefill-only iterations add
        zero)."""
        return int(self.pool.slot_steps)

    @property
    def occupancy(self) -> float:
        """Mean fraction of slots decoding over all loop iterations
        so far (chunked mode: prefill-only iterations count as idle
        decode capacity — the honest denominator)."""
        if self.total_steps == 0:
            return 0.0
        return self.busy_slot_steps / (self.total_steps * self.n_slots)

    # ---------------- speculative-decoding stats -----------------------
    # Accounting is EMISSION-weighted: a window's accepted count is the
    # extra tokens it actually emitted beyond the 1/iteration baseline
    # (post EOS/budget clamp) — the number that explains the measured
    # speedup, not the optimistic raw greedy-match length.

    @property
    def spec_windows(self) -> int:
        """Verify windows run (Σ over slots; 0 for non-spec pools)."""
        if self.speculative is None:
            return 0
        return int(np.asarray(self.pool.slot_windows).sum())

    @property
    def accepted_tokens(self) -> int:
        """Σ extra tokens emitted beyond one per verify window."""
        if self.speculative is None:
            return 0
        return int(np.asarray(self.pool.slot_accepted).sum())

    @property
    def drafted_tokens(self) -> int:
        """Σ drafted candidates (k per verify window)."""
        return self.spec_windows * (self.speculative.k
                                    if self.speculative else 0)

    @property
    def accept_rate(self) -> float:
        """accepted_tokens / drafted_tokens (0.0 when nothing drafted)."""
        d = self.drafted_tokens
        return self.accepted_tokens / d if d else 0.0

    @property
    def mean_accept_len(self) -> float:
        """Mean accepted drafts per verify window (tokens/iteration is
        this + 1)."""
        w = self.spec_windows
        return self.accepted_tokens / w if w else 0.0

    def slot_accept_len(self) -> np.ndarray:
        """Per-slot mean accept length over that slot's windows."""
        if self.speculative is None:
            return np.zeros(self.n_slots)
        a = np.asarray(self.pool.slot_accepted, np.float64)
        w = np.asarray(self.pool.slot_windows, np.float64)
        return a / np.maximum(w, 1.0)

    # ---------------- adaptive-depth stats ------------------------------

    @property
    def mean_depth(self) -> float:
        """Mean decoder blocks applied per decode token across the run:
        harvested requests' totals plus the still-resident slots'
        counters. == cfg.n_layers for static-depth pools; < n_layers
        when adaptive early exit / mixture-of-depths skipped blocks
        (``models.adaptive``)."""
        dl = self.depth_layers + int(np.asarray(self.pool.slot_layers,
                                                np.int64).sum())
        dt = self.depth_tokens + int(np.asarray(self.pool.slot_decodes,
                                                np.int64).sum())
        return dl / dt if dt else 0.0

    def slot_mean_depth(self) -> np.ndarray:
        """Per-slot mean depth over that slot's CURRENT residency
        (counters recycle at admission; harvested totals live in
        ``depth_layers``/``depth_tokens``)."""
        a = np.asarray(self.pool.slot_layers, np.float64)
        d = np.asarray(self.pool.slot_decodes, np.float64)
        return a / np.maximum(d, 1.0)
