"""Prefill/decode disaggregation: two-tier routing with async KV-block
shipping across mesh slices.

The paper's §3 argument is that the branches and loop bodies of one
logical computation can be *partitioned across sets of devices*, with
non-strict execution overlapping one partition's compute with the
communication feeding the next. Applied to serving: chunked prefill
and paged decode are different computations with different resource
shapes — prefill is compute-bound and bursty, decode is
latency-critical and steady — and colocating them makes every
long-prompt admission steal inter-token latency from running slots
(bounded by the chunk size, but never zero). This module splits them
onto **disjoint submeshes** of one device fleet:

- a **prefill slice**: a :class:`~repro.serve.scheduler.DecodeScheduler`
  built with ``prefill_only=True`` — chunked ``flash_prefill``
  admission into a paged pool; a slot whose prompt completes retires
  holding its KV blocks and its first sampled token instead of
  decoding;
- a **decode slice**: a second scheduler that never prefills — it
  admits *already-prefilled* requests through ``splice_requests``
  (alloc + ``PagedKVCache.import_rows`` + register straight into the
  RUNNING state) and runs the paged-attention decode kernel.

Between them, finished KV blocks ship slice-to-slice asynchronously:
``export_rows`` gathers the row's blocks into a fresh wire buffer
``(L, 1, n_cols, KV, block, hd)`` on the prefill slice,
``jax.device_put`` dispatches the transfer into the decode pool's
sharding (``dist.sharding.transfer_sharding``) without blocking the
host, and the shipment rides an in-transit queue for one full round
before the jitted splice consumes it — so request *i*'s transfer hides
under request *i+1*'s prefill chunks and the decode slice's own
segment (the paper's overlap argument, double-buffered). JAX's data
dependency makes the splice wait on the transfer with no explicit
synchronization.

The host FIFO driver becomes a **two-tier router**: submit → backlog
(priority/deadline-sorted) → prefill-slice admission; harvest-KV →
ship → splice-into-decode-slot. The SLO layer's logic composes
unchanged on the decode tier: when the most urgent shipment cannot be
spliced, strictly-lower-priority decode residents are evicted through
the same ``preempt_slots`` machinery (victims by priority /
reclaimable blocks / replay cost), re-queued for recompute-from-prompt
through the prefill tier, and their replayed streams are verified
bit-identical against the preemption snapshot
(``replay_mismatches`` must stay 0).

Why transfer rather than recompute: recompute-from-prompt is the right
call for *preemption* (DESIGN.md §8.5 — rare, and prefix caching makes
the replay nearly free), but here every request would pay it on every
admission, exactly doubling the prefill FLOPs the split exists to get
off the decode slice. A prompt's KV blocks are
``plen * kv * hd * 2 * L`` bytes — at serving shapes, milliseconds of
ICI/DCN for seconds of saved prefill — and the shipment overlaps work
on both slices, so transfer wins whenever the interconnect is not
pathologically slow.

Greedy decode through the disaggregated path is bit-identical to the
colocated scheduler: the splice registers exactly the state a
colocated slot holds the instant its last chunk flips it
PREFILLING→RUNNING (``cur_len = plen + 1``, first token sampled from
the final chunk's logits with emission-index key 0), and both tiers
derive request keys from the same seed, so the decode-tier stream is
tier-invariant (tests pin this across dense/moe/vlm, with prefix cache
and preemption enabled).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from ..dist import sharding as sh
from . import kv_cache as kvc
from . import sampling as sampling_lib
from . import scheduler as sched_lib

__all__ = ["DisaggScheduler"]


def _slice_rules(cfg, mesh):
    """ShardingRules for one slice mesh (None off-mesh)."""
    if mesh is None:
        return None
    return sh.resolve_rules(
        mesh, d_model=cfg.d_model, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads,
        head_dim=getattr(cfg, "head_dim", 0), d_ff=cfg.d_ff,
        vocab=getattr(cfg, "padded_vocab", 0),
        n_experts=getattr(cfg, "n_experts", 0))


def _replicate(params, mesh):
    """Place one tier's parameter copy on its slice (replicated).

    Each slice holds its own replica: the split is between *phases*,
    not a sharding of one model, and a slice must never read weights
    off the other slice mid-segment."""
    if mesh is None:
        return params
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.device_put(params, NamedSharding(mesh, PartitionSpec()))


def _segment_done(arr) -> bool:
    """Non-blocking readiness poll of a dispatched segment's result.

    ``jax.Array.is_ready`` answers without synchronizing."""
    return bool(arr.is_ready())


@dataclasses.dataclass
class _Shipment:
    """One request's KV blocks in flight between the slices."""

    req: sched_lib._Queued   # original record (real max_new restored)
    t0: int                  # first token, sampled on the prefill slice
    plen: int                # prefilled stream length (prompt + prefix)
    k: Any                   # (L, 1, n_cols, KV, block, hd) wire buffers,
    v: Any                   # device_put toward the decode slice
    round: int               # dispatch round — spliced strictly later


class DisaggScheduler:
    """Two-tier router over a prefill slice and a decode slice.

    Args:
      params, cfg: model (replicated onto each slice's mesh).
      n_prefill_slots / n_decode_slots: per-tier slot-pool sizes. The
        prefill tier turns slots over once per prompt, so it runs much
        smaller than the decode tier at equal throughput.
      prompt_len, max_new_cap, eos_id, sampling, prefix_len, seed: as
        the colocated scheduler — ``seed`` MUST be shared across tiers
        (both derive request keys from it; that is half of the
        bit-identity argument).
      prefill_mesh / decode_mesh: disjoint submeshes
        (``dist.sharding.carve_slices`` + ``slice_mesh``); None runs
        the tier on the default device (CI fallback, still exercising
        the full ship/splice path).
      kv_block, chunk_tokens: block and chunk geometry (shared — the
        wire format is block-granular).
      prefill_kv_blocks / decode_kv_blocks: per-tier pool capacities.
      prefix_cache: warm-prompt block reuse ON THE PREFILL TIER (that
        is where prompts are; the decode tier always receives private
        fresh-alloc'd copies, so CoW never crosses the wire).
      speculative / draft_params / draft_cfg: decode-tier speculative
        decoding. The prefill tier refuses it by construction; a
        model drafter would also need its dense draft cache shipped,
        so only the n-gram drafter composes with disaggregation.
      segment_steps: decode-segment iteration cap per round while the
        prefill pipeline is live — the splice/preemption revisit
        granularity (the SLO layer's bounded-segment idea).
      prefill_segment_steps: chunk-iteration cap per PREFILL-slice
        segment (default: ``segment_steps``). Bounding the launched
        segment keeps a long prompt from monopolizing its slice in
        one dispatch: each round advances it a bounded number of
        chunks and hands the host a harvest opportunity — and on
        fleets whose "slices" contend for the same silicon (CI's
        virtual host devices; oversubscribed CPU) it also bounds the
        per-round interference the in-flight segment can impose on
        the decode slice's wall clock.
    """

    def __init__(self, params, cfg, *, n_prefill_slots: int,
                 n_decode_slots: int, prompt_len: int, max_new_cap: int,
                 eos_id: int = 1,
                 sampling: sampling_lib.SamplingParams =
                 sampling_lib.SamplingParams(),
                 prefill_mesh=None, decode_mesh=None, prefix_len: int = 0,
                 seed: int = 0, kv_block: int = 16,
                 prefill_kv_blocks: Optional[int] = None,
                 decode_kv_blocks: Optional[int] = None,
                 chunk_tokens: int = 16, prefix_cache: bool = False,
                 speculative=None, draft_params=None, draft_cfg=None,
                 segment_steps: int = 8,
                 prefill_segment_steps: Optional[int] = None):
        if segment_steps < 1:
            raise ValueError("segment_steps must be >= 1")
        if prefill_segment_steps is not None and prefill_segment_steps < 1:
            raise ValueError("prefill_segment_steps must be >= 1")
        if speculative is not None and draft_cfg is not None:
            raise ValueError(
                "disaggregation supports the n-gram drafter only: a "
                "model drafter keeps a dense per-slot draft cache that "
                "would also need shipping slice-to-slice")
        self.cfg = cfg
        self.segment_steps = int(segment_steps)
        self.prefill_segment_steps = int(prefill_segment_steps
                                         or segment_steps)
        self.prefix_len = int(prefix_len)
        pf_rules = _slice_rules(cfg, prefill_mesh)
        de_rules = _slice_rules(cfg, decode_mesh)
        # The prefill tier holds a prompt only for the few chunks it
        # takes to compute it: max_new_cap=1 keeps its pool sized to
        # prompts, and prefill_only retires rows instead of decoding.
        self.prefill = sched_lib.DecodeScheduler(
            _replicate(params, prefill_mesh), cfg,
            n_slots=n_prefill_slots, prompt_len=prompt_len,
            max_new_cap=1, eos_id=eos_id, sampling=sampling,
            rules=pf_rules, mesh=prefill_mesh, prefix_len=prefix_len,
            seed=seed, kv="paged", kv_block=kv_block,
            kv_blocks=prefill_kv_blocks, prefill="chunked",
            chunk_tokens=chunk_tokens, prefix_cache=prefix_cache,
            prefill_only=True)
        # The decode tier never prefills: its "prompt length" is the
        # full prefilled stream (prompt + patch prefix) with
        # prefix_len=0, so max_len — and with it every position the
        # kernel sees — matches the colocated pool exactly.
        self.decode = sched_lib.DecodeScheduler(
            _replicate(params, decode_mesh), cfg,
            n_slots=n_decode_slots, prompt_len=prompt_len + prefix_len,
            max_new_cap=max_new_cap, eos_id=eos_id, sampling=sampling,
            rules=de_rules, mesh=decode_mesh, prefix_len=0, seed=seed,
            kv="paged", kv_block=kv_block, kv_blocks=decode_kv_blocks,
            prefill="chunked", chunk_tokens=chunk_tokens,
            speculative=speculative, draft_params=None, draft_cfg=None)
        # Wire geometry: enough table columns for the longest possible
        # prefilled stream — ONE compiled export/splice shape serves
        # every prompt length (short prompts ship masked-zero columns).
        self.ship_cols = int(kvc.blocks_needed(prompt_len + prefix_len,
                                               kv_block))
        self._export_fn = jax.jit(self._build_export())
        self._wire_sharding = None      # built lazily from real shapes
        # router state
        self.queue: List[sched_lib._Queued] = []   # priority backlog
        self._in_transit: List[_Shipment] = []
        self._orig_max_new: Dict[int, int] = {}
        self._snapshots: Dict[int, np.ndarray] = {}
        self._round = 0
        self._prefill_inflight = False   # a dispatched, unharvested segment
        # counters
        self.transfers = 0
        self.transfer_bytes = 0
        self.preemptions = 0
        self.replay_mismatches = 0
        self.completed = 0

    # ---------------- shipping ----------------------------------------

    def _build_export(self):
        kv_key = self.prefill._kv_key
        n_cols = self.ship_cols

        def export(pool, rows):
            """Gather one harvested row's leading blocks into a fresh
            (L, 1, n_cols, KV, block, hd) wire buffer. Fresh matters:
            the buffer aliases nothing in the pool, so the prefill tier
            may recycle the row's blocks (``release_slots``) while the
            device_put of this buffer is still in flight."""
            return pool.cache[kv_key].export_rows(rows, n_cols)

        return export

    def _ship(self, rec) -> None:
        """Export one harvested prefill row and dispatch its transfer
        toward the decode slice — all async: the export is a jitted
        gather on the prefill slice, the device_put returns immediately,
        and the splice that consumes the buffer (next round) carries
        the data dependency. Between dispatch and splice the shipment
        has a full round of prefill chunks and a decode segment to
        hide under — the double-buffering the module docstring argues."""
        clone = rec["req"]
        q = dataclasses.replace(
            clone, max_new=self._orig_max_new[clone.request_id])
        k, v = self._export_fn(self.prefill.pool,
                               np.asarray([rec["slot"]], np.int32))
        if self.decode.mesh is not None:
            if self._wire_sharding is None:
                self._wire_sharding = sh.transfer_sharding(
                    self.decode.rules, self.decode.mesh, k.shape)
            k = jax.device_put(k, self._wire_sharding)
            v = jax.device_put(v, self._wire_sharding)
        self.transfers += 1
        self.transfer_bytes += int(k.nbytes) + int(v.nbytes)
        self._in_transit.append(
            _Shipment(q, rec["t0"], rec["plen"], k, v, self._round))

    # ---------------- decode-tier admission ---------------------------

    def _splice_arrivals(self) -> int:
        """Splice in-transit shipments (most urgent first) into free
        decode slots, preempting lower-priority residents when the head
        shipment cannot fit. Shipments dispatched THIS round stay in
        flight — splicing only strictly-older ones is what guarantees
        the transfer a full round of overlap before anything waits on
        it."""
        spliced = 0
        while self._in_transit:
            order = sorted(
                range(len(self._in_transit)),
                key=lambda i: (self._in_transit[i].req.priority,
                               self._in_transit[i].req.deadline,
                               self._in_transit[i].req.request_id))
            i = order[0]
            t = self._in_transit[i]
            if t.round >= self._round:
                break
            need = int(kvc.blocks_needed(t.plen + t.req.max_new + 1,
                                         self.decode.kv_block))
            if (self.decode.free_slots < 1
                    or self.decode.free_blocks < need):
                if not self._maybe_preempt(t.req.priority, need):
                    break
            self.decode.splice_requests(
                [t.req], [t.t0], [t.plen], t.k, t.v)
            del self._in_transit[i]
            spliced += 1
        return spliced

    def _maybe_preempt(self, priority: int, need: int) -> bool:
        """The SLO layer's eviction plan, verbatim on the decode tier:
        evict strictly-lower-priority residents — most expendable class
        first, then most reclaimable blocks, then least replay work —
        and commit only if that actually admits the head shipment.
        Victims re-enter the backlog for recompute-from-prompt through
        the PREFILL tier (their blocks live on the decode slice; with
        prefix caching the replayed prompt usually maps straight back
        onto still-pinned prefill-tier blocks), and their snapshots
        gate the replayed stream bit-for-bit."""
        dec = self.decode
        victims = [s for s in range(dec.n_slots)
                   if dec._busy[s] and dec._slot_req[s] is not None
                   and dec._slot_req[s].priority > priority]
        if not victims:
            return False
        if dec._kv_key is not None:
            reclaim = np.asarray(
                dec.pool.cache[dec._kv_key].reclaimable())
        else:
            reclaim = np.zeros(dec.n_slots, np.int32)
        n_emitted = np.asarray(dec.pool.n_emitted)
        victims.sort(key=lambda s: (-dec._slot_req[s].priority,
                                    -int(reclaim[s]),
                                    int(n_emitted[s]), s))
        plan: List[int] = []
        slots_free, blocks_free = dec.free_slots, dec.free_blocks
        for s in victims:
            if slots_free >= 1 and blocks_free >= need:
                break
            plan.append(s)
            slots_free += 1
            blocks_free += int(dec._slot_blocks[s])
        if slots_free < 1 or blocks_free < need:
            return False
        for p in dec.preempt_slots(plan):
            self._snapshots[p.request_id] = p.tokens
            self.queue.append(sched_lib._Queued(
                p.request_id, p.prompt, p.max_new, p.key,
                p.prefix_embeds, p.frames, p.priority, p.deadline))
        self.preemptions += len(plan)
        return True

    # ---------------- submission --------------------------------------

    def submit(self, prompt, *, max_new: int,
               request_id: Optional[int] = None, key=None,
               prefix_embeds=None, frames=None, priority: int = 0,
               deadline: float = float("inf")) -> int:
        """Queue one request into the router backlog.

        Validation and rid assignment ride the prefill tier's submit
        (it owns the chunked-admission constraints); the decode-side
        residency check is ours, since only this layer knows the
        request will eventually hold ``plen + max_new + 1`` positions
        on the decode slice."""
        if not 1 <= max_new <= self.decode.max_new_cap:
            raise ValueError(
                f"max_new must be in [1, {self.decode.max_new_cap}]")
        prompt = np.asarray(prompt)
        if prompt.ndim == 2:
            need = self.decode.blocks_for(
                prompt.shape[1] + self.prefix_len, max_new)
            if need > self.decode.kv_blocks:
                raise ValueError(
                    f"request needs {need} decode-tier blocks but the "
                    f"pool has kv_blocks={self.decode.kv_blocks}")
        if self.pending == 0:
            self.reset_stats()
        rid = self.prefill.submit(
            prompt, max_new=1, request_id=request_id, key=key,
            prefix_embeds=prefix_embeds, frames=frames,
            priority=priority, deadline=deadline)
        q = self.prefill.queue.pop()
        self.queue.append(dataclasses.replace(q, max_new=int(max_new)))
        self._orig_max_new[rid] = int(max_new)
        return rid

    @property
    def pending(self) -> int:
        """Requests not yet finished, wherever they are in the
        pipeline: backlogged, prefilling, in flight between the
        slices, or decoding."""
        return (len(self.queue) + self.prefill.active_count
                + len(self._in_transit) + self.decode.pending)

    # ---------------- scheduling round --------------------------------

    def step(self, expect_arrivals: bool = False,
             max_steps: Optional[int] = None
             ) -> List[sched_lib.FinishedRequest]:
        """One router round, ordered for slice overlap:

        1. sort the backlog, feed the prefill tier, and LAUNCH its
           chunked segment asynchronously (``dispatch_segment`` — the
           host does not wait for it); a segment still in flight from
           an earlier round just keeps chewing instead;
        2. splice last round's shipments into decode slots (preempting
           lower-priority residents for an urgent head);
        3. run one bounded decode segment — the decode slice computes
           while the prefill slice chews its chunks, which is the
           whole point of disjoint submeshes;
        4. harvest finished prompts, export + device_put their blocks
           (async), release the prefill rows. The harvest POLLS the
           in-flight segment (``is_ready``) rather than waiting on it
           while the decode tier still has residents to serve — a
           long prompt's many-chunk segment spans several decode
           rounds without ever appearing in a running slot's
           inter-token gap. Only when the decode tier is starved is
           the prefill slice the critical path, and only then does
           the round block on it.

        Returns the requests that finished decoding this round.
        """
        self._round += 1
        # (1) prefill-slice admission + async segment launch — gated
        # on the previous segment being harvested: segment entry
        # clears `done` in-graph, so dispatching over unharvested
        # rows would drop their KV
        if not self._prefill_inflight:
            self.queue.sort(key=lambda q: (q.priority, q.deadline,
                                           q.request_id))
            feed = [dataclasses.replace(q, max_new=1)
                    for q in self.queue]
            self.prefill.queue.extend(feed)
            launched = self.prefill.dispatch_segment(
                expect_arrivals=True,
                max_steps=self.prefill_segment_steps)
            n_admitted = len(feed) - len(self.prefill.queue)
            self.prefill.queue.clear()
            del self.queue[:n_admitted]
            self._prefill_inflight = launched
        # (2) decode-slice admission from the in-transit queue
        self._splice_arrivals()
        # (3) bounded decode segment (overlapped with the prefill
        # slice's in-flight segment); pure drain at the pipeline tail
        more = bool(self.queue or self._in_transit
                    or self.prefill.active_count)
        cap = max_steps if max_steps is not None else (
            self.segment_steps if more else None)
        finished = self.decode.step(expect_arrivals=more
                                    or expect_arrivals, max_steps=cap)
        # (4) harvest the prefill slice; ship, then free the rows —
        # release MUST precede the next dispatch (a held done-row
        # counts as idle to the segment predicate, and segment entry
        # clears `done` in-graph)
        recs = []
        if self._prefill_inflight:
            decode_busy = (self.decode.active_count > 0
                           or bool(self._in_transit))
            if (not decode_busy
                    or _segment_done(self.prefill.pool.done)):
                recs = self.prefill.harvest_prefilled()
                self._prefill_inflight = False
        if recs:
            for rec in recs:
                self._ship(rec)
            self.prefill.release_slots([r["slot"] for r in recs])
        # (5) replay verification + lifecycle bookkeeping
        for f in finished:
            snap = self._snapshots.pop(f.request_id, None)
            if snap is not None and len(snap):
                m = min(len(snap), len(f.tokens))
                if not np.array_equal(np.asarray(f.tokens[:m]),
                                      snap[:m]):
                    self.replay_mismatches += 1
            self._orig_max_new.pop(f.request_id, None)
            self.completed += 1
        return finished

    def run_until_drained(self) -> List[sched_lib.FinishedRequest]:
        """Drive rounds until the whole pipeline is empty."""
        results: List[sched_lib.FinishedRequest] = []
        while self.pending:
            before = (self.pending, int(self.decode.pool.steps),
                      int(self.prefill.pool.steps))
            results.extend(self.step())
            after = (self.pending, int(self.decode.pool.steps),
                     int(self.prefill.pool.steps))
            if after == before:
                raise RuntimeError(
                    "disaggregated scheduler made no progress")
        return results

    def warmup(self) -> None:
        """Compile both tiers' admission/segment traces off the timed
        path (the export/splice pair still compiles on the first real
        shipment — drive one throwaway request for a full warmup)."""
        self.prefill.warmup()
        self.decode.warmup()

    # ---------------- stats / reporting -------------------------------

    def reset_stats(self) -> None:
        self.prefill.reset_stats()
        self.decode.reset_stats()
        self.transfers = 0
        self.transfer_bytes = 0
        self.preemptions = 0
        self.replay_mismatches = 0
        self.completed = 0

    @property
    def transfer_impl(self) -> str:
        """How prefilled KV reaches the decode kernel: "device_put:dcn"
        when the fleet spans processes (the shipment crosses host
        boundaries), "device_put:ics" within one process (ICI on real
        hardware; host RAM on CPU CI — reported distinctly from
        "colocated" so disagg numbers can't be misread as free)."""
        return ("device_put:dcn" if jax.process_count() > 1
                else "device_put:ics")

    @property
    def attn_impl(self) -> str:
        return self.decode.attn_impl

    @property
    def prefill_impl(self) -> str:
        return self.prefill.prefill_impl

    @property
    def total_steps(self) -> int:
        """Decode-tier loop iterations (the clock SLO metrics and
        benchmarks count in — prefill-slice iterations happen on other
        devices and steal nothing from it; they are reported as
        ``prefill_steps``)."""
        return self.decode.total_steps

    @property
    def prefill_steps(self) -> int:
        return self.prefill.total_steps

    @property
    def tokens_emitted(self) -> int:
        return self.decode.tokens_emitted

    @property
    def peak_resident(self) -> int:
        return self.decode.peak_resident
