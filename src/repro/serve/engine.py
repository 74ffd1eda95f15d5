"""Continuous-batching serving engine — device-resident decode.

The hot path is a jitted ``lax.while_loop``: tokens, per-sequence
positions, the alive mask, per-sequence emitted counts and the output
buffer all live on device, with the KV cache donated into the loop.
The host sees results exactly once per bucket (one ``jax.device_get``
of the packed outputs), not once per token — the seed engine's
per-token ``np.asarray`` sync and Python dispatch are gone, which is
where the operational J/token win lives (serving efficiency dominates
the footprint: Chasing Carbon / GreenFPGA).  The loop exits early the
moment every sequence has hit EOS or its own ``max_new_tokens``.

Buckets are *ragged* where the model family allows it
(``model.supports_ragged``): mixed-length prompts are right-padded to
the bucket max and share one prefill; per-sequence positions / valid
lengths are threaded through ``model.decode_step`` so each lane writes
its own cache slot and masks its own span.  Outputs are bit-identical
to serving each request alone (greedy; locked by tests).  Families
with rolling (SWA) windows or unfrozen state emit (hybrid/audio) fall
back to exact-length buckets.  Admission is slot-based: each bucket
fills up to ``max_batch`` slots from the pending queue at bucket
boundaries, completed requests drain into a results map, so sustained
load stays O(pending).

FRAC KV (``kv_frac_kbits``): prefill KV *and* every decode-written KV
slot are fake-quantized through the FRAC pipeline as they are produced
(slot-granular scales — see ``ops.fake_quant_slots`` — so batching
never changes a lane's numerics), holding ~k/32 of the fp32 bytes.
``stats.kv_bytes_full`` / ``stats.kv_bytes_frac`` book the modeled
capacity win with the codec's single source of truth,
``kernels/frac_pack/ops.compressed_nbytes``, over the whole decode
horizon — honest now that decode-written rows really are quantized.

Sustainability: every finished request is metered through a
``SustainabilityMeter`` — its token-share of bucket wall time at
facility power (J/token), chip occupancy, and the FRAC KV bytes'
flash-tier residency via ``embodied.flash_tb(recycled=True)``.  Only
tokens actually decoded are booked (early exit included).  Typed
``EnergyReport``s land in ``engine.reports[rid]``.

Paged mode (``paged=True``, families with ``model.supports_paged``):
the contiguous per-lane cache is replaced by a shared **page pool**
(``serve/paging.py``) — each lane owns a list of fixed-size pages, so
a skewed mixed-length bucket stops paying bucket-max padding in cache
memory, and the ESE meter books resident bytes over *allocated pages
only*.  Admission moves **inside** the decode loop: up to
``stage_depth`` pending requests are pre-staged (they share the
bucket's one ragged prefill; their prompt KV sits in pages, their
first token waits on device), and the moment a lane dies (EOS /
max_new) its pages return to a device-side free list and the next
staged request takes the lane without leaving the ``while_loop`` —
one host sync serves the whole super-bucket.  Outputs stay
bit-identical to the contiguous engine and to solo serving (locked by
tests/test_serve_paged.py).  MoE stacks page like dense ones (their
expert layer is dropless, models/moe.py); ``stats.expert_reads`` counts
the held experts that live lanes routed to.  Families that don't page
(rwkv's O(1) state, SWA, hybrid/audio) silently fall back to the
contiguous path.

An optional ``mesh`` shards params (weight rule), caches (decode-cache
rule, which also places the paged pool) and the loop's per-sequence
vectors (``serve_loop_spec``) via sharding/rules.py.

Flash oversubscription (``flash=FlashTier(...)``, paged mode only):
the page pool is sized for the *active wave* instead of the whole
super-bucket.  All admitted requests still share one ragged prefill,
but the waiting requests' prompt KV is evicted — coldest-first — into
the simulated recycled-NAND tier (serve/flash_tier.py) as lossless
FRAC cell streams, and the super-bucket is served as host-orchestrated
**waves** of up to ``max_batch`` requests: each wave faults its
requests' pages back in (running the fault-injection recovery ladder:
ECC → retry-read → lane re-prefill from the retained prompt), fills a
wave-sized pool, and reuses the same jitted paged loop with an empty
stage queue.  Extra host syncs per wave are the oversubscription
overhead (reported in stats); outputs stay bit-identical to the
non-oversubscribed engine and to solo serving because spills are
lossless and unrecoverable pages are *replayed*, never patched.  When
the tier cannot hold even one staged request (worn out / killed), the
super-bucket degrades to exactly the non-oversubscribed path.

Per-request deadlines (``max_wall_s``): expired pending requests are
reaped at bucket/wave boundaries (freed like EOS, spilled pages
discarded), and lanes already decoding have their ``max_new`` clamped
from the measured step-time estimate so a request cannot overrun its
budget by more than the loop granularity.  Timeouts are counted in
``stats.timeouts`` and the affected rids land in ``engine.timeouts``.
"""
from __future__ import annotations

import os
import time
import warnings
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro import spans
from repro.configs.base import ModelConfig
from repro.core.ese.meter import MeterConfig, SustainabilityMeter
from repro.core.ese.records import EnergyReport
from repro.models import model
from repro.models.common import greedy_sample, is_leaf_spec
from repro.models.transformer import EXPERT_READS
from repro.serve import paging


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (len,) int32
    max_new_tokens: int = 16
    output: list[int] = field(default_factory=list)
    done: bool = False
    t_submit: float = 0.0
    t_first: float | None = None
    t_done: float | None = None
    max_wall_s: float | None = None    # deadline from t_submit (None = ∞)
    eff_max_new: int | None = None     # deadline-clamped budget last used
    timed_out: bool = False


@dataclass
class ServeStats:
    requests: int = 0
    tokens: int = 0
    prefills: int = 0
    decode_steps: int = 0           # device loop iterations (from the loop)
    host_syncs: int = 0             # decode-phase host transfers (1/bucket)
    decode_s: float = 0.0           # wall from first token ready to the
                                    # bucket's host transfer (decode phase)
    ttft_s: list[float] = field(default_factory=list)
    kv_bytes_full: int = 0          # fp bytes the caches would occupy
    kv_bytes_frac: int = 0          # bytes after the FRAC kbits dial
    kv_bytes_peak: int = 0          # max concurrently-resident cache bytes
                                    # (paged: the *allocated-pages* model
                                    # the ESE meter books; contiguous:
                                    # allocation == residency)
    kv_bytes_pool: int = 0          # max physically provisioned bytes
                                    # (paged: the pow2-rounded pool)
    kv_pages_peak: int = 0          # paged: max pages live at once
    admissions: int = 0             # paged: in-loop slot refills
    expert_reads: int = 0           # paged MoE: held experts at least one
                                    # live lane routed to, summed over
                                    # decode steps and MoE layers
    attn_transient_peak: int = 0    # paged: modeled peak per-layer
                                    # attention-read transient bytes per
                                    # decode step (gather pays the
                                    # bucket-max table width, the fused
                                    # kernel one page column — see
                                    # kernels/paged_attn/ops.py)
    timeouts: int = 0               # requests expired by max_wall_s
    oversub_waves: int = 0          # flash mode: waves decoded
    spills: int = 0                 # flash mode: pool pages evicted
    faultins: int = 0               # flash mode: pages read back
    ecc_corrected: int = 0          # recovery ladder stage 1 hits
    retry_reads: int = 0            # stage 2: extra-sense retry reads
    reprefills: int = 0             # stage 3: lanes replayed from prompt
    reprefill_tokens: int = 0       # prompt tokens recomputed by stage 3
    flash_bytes_peak: int = 0       # max bytes live on the spill tier


@partial(jax.jit, static_argnums=1)
def _frac_kv_rows(cache, kbits: int):
    """Slot-granular FRAC fake-quant of a prefill KV cache (one scale
    per (K, hd) row), as one fused program: eagerly, each step would
    hold a full fp32 copy of the cache."""
    from repro.kernels.frac_pack import ops as fops

    return jax.tree.map(
        lambda leaf: fops.fake_quant_slots(leaf, kbits, row_dims=2), cache)


def build_decode_loop(mcfg: ModelConfig, *, eos_id: int | None = None,
                      kv_kbits: int | None = None, ragged: bool = False,
                      out_cap: int = 1):
    """Jitted device-resident multi-token decode.

    Returns ``loop(params, cache, tok0, pos0, max_new) ->
    (out (B, out_cap) int32, n_out (B,) int32, steps int32 scalar,
    final cache)``.
    The cache is donated; the carry (tokens, positions, alive mask,
    output buffer, emitted counts) never leaves the device, and the
    ``while_loop`` exits as soon as every lane is dead (EOS or its own
    ``max_new``).  ``ragged`` decodes with per-sequence positions;
    otherwise the shared scalar position keeps the cheap
    dynamic-update-slice cache write.
    """

    def loop(params, cache, tok0, pos0, max_new):
        B = tok0.shape[0]
        col = jnp.arange(out_cap, dtype=jnp.int32)[None, :]   # (1, out_cap)
        out = jnp.where(col == 0, tok0[:, None], 0).astype(jnp.int32)
        n_out = jnp.ones((B,), jnp.int32)
        alive = n_out < max_new
        if eos_id is not None:
            alive = alive & (tok0 != eos_id)

        def cond(c):
            return c[2].any()

        def body(c):
            cache, tok, alive, pos, out, n_out, steps = c
            p = pos if ragged else pos[0]
            logits, cache = model.decode_step(mcfg, params, cache, tok, p,
                                              kv_kbits=kv_kbits)
            nxt = greedy_sample(logits)
            # one-hot predicated write: dead lanes record nothing
            out = jnp.where(alive[:, None] & (col == n_out[:, None]),
                            nxt[:, None], out)
            n_out = n_out + alive.astype(jnp.int32)
            alive = alive & (n_out < max_new)
            if eos_id is not None:
                alive = alive & (nxt != eos_id)
            tok = jnp.where(alive, nxt, tok)
            return (cache, tok, alive, pos + 1, out, n_out, steps + 1)

        c = jax.lax.while_loop(
            cond, body, (cache, tok0, alive, pos0, out, n_out, jnp.int32(0)))
        # the final cache is returned (and dropped by the caller) so the
        # donated input has a same-shaped output to alias into — true
        # in-place decode, no per-bucket cache copy
        return c[4], c[5], c[6], c[0]

    return jax.jit(loop, donate_argnums=(1,))


def build_paged_decode_loop(mcfg: ModelConfig, *, eos_id: int | None = None,
                            kv_kbits: int | None = None, out_cap: int = 1,
                            page_size: int = 16, paged_kernel: bool = False):
    """Jitted paged decode with in-loop admission (the super-bucket).

    Returns ``loop(params, pool, page_table, free_stack, free_top,
    tok0, pos0, staged_tok0, staged_len, staged_pt, max_new) ->
    (out (R, out_cap), n_out (R,), steps, pages_peak,
    pages_per_req (R,), admissions, expert_reads, final pool)`` where
    ``R = B + Q`` requests (B decode lanes + Q pre-staged).  The pool
    is donated.  For an MoE stack the carried pools hold one more
    leaf, the ``transformer.EXPERT_READS`` counter, which every step's
    MoE layers add to; ``expert_reads`` is its final value (0 for a
    dense stack).

    The carry holds, besides the contiguous loop's vectors, the page
    table, the free-list stack, a lane→request map and the page
    accounting scalars.  Each iteration: (1) lanes whose next write
    crosses into an unallocated page pop one from the free stack
    (``paging.alloc_pages``); (2) one ``model.decode_step_paged`` —
    dead lanes' writes route to the trash page; (3) tokens land in
    per-*request* output rows (a lane serves several requests over its
    lifetime); (4) a per-lane maintenance pass frees dead lanes' pages
    to the stack and admits the next staged request into the lane —
    its prompt pages are already resident, its first token already
    recorded, so admission is a handful of scalar writes and the
    ``while_loop`` never leaves the device.  The loop exits only when
    every lane is dead *and* the stage queue is drained.
    """

    def loop(params, pool, page_table, free_stack, free_top,
             tok0, pos0, staged_tok0, staged_len, staged_pt, max_new):
        B = tok0.shape[0]
        Q = staged_tok0.shape[0]
        R = B + Q
        mp = page_table.shape[1]
        rows_b = jnp.arange(B)
        # request-indexed vectors get a trailing trash row R: dead
        # lanes' predicated writes land there instead of branching
        mn1 = jnp.concatenate([max_new, jnp.zeros((1,), jnp.int32)])
        out = jnp.zeros((R + 1, out_cap), jnp.int32)
        out = out.at[rows_b, 0].set(tok0)
        n_out = jnp.zeros((R + 1,), jnp.int32).at[rows_b].set(1)
        alive = 1 < max_new[:B]
        if eos_id is not None:
            alive = alive & (tok0 != eos_id)
        ppr = jnp.concatenate([
            (page_table > 0).sum(axis=1, dtype=jnp.int32),
            (staged_pt > 0).sum(axis=1, dtype=jnp.int32),
            jnp.zeros((1,), jnp.int32),
        ])
        in_use = ppr.sum()
        if mcfg.is_moe:
            pool = dict(pool, **{EXPERT_READS: jnp.asarray(0, jnp.int32)})
        c = dict(pool=pool, pt=page_table, fs=free_stack,
                 ft=jnp.asarray(free_top, jnp.int32), tok=tok0, pos=pos0,
                 alive=alive, lane=rows_b.astype(jnp.int32), out=out,
                 n_out=n_out, sn=jnp.asarray(0, jnp.int32), in_use=in_use,
                 peak=in_use, ppr=ppr, adm=jnp.asarray(0, jnp.int32),
                 steps=jnp.asarray(0, jnp.int32))

        def maintain(c):
            """Free dead lanes' pages; refill each dead lane from the
            stage queue (skipping straight past dead-on-arrival
            requests, whose prompt pages bounce back to the stack)."""

            def lane_fix(b, c):
                row = c["pt"][b]
                dead_own = (~c["alive"][b]) & (row[0] > 0)
                row, fs, ft, n = paging.free_lane_pages(
                    row, c["fs"], c["ft"], dead_own)
                c = dict(c, pt=c["pt"].at[b].set(row), fs=fs, ft=ft,
                         in_use=c["in_use"] - n)

                def adm_cond(c):
                    return (~c["alive"][b]) & (c["sn"] < Q)

                def adm_body(c):
                    qi = c["sn"]
                    req = B + qi
                    t0 = staged_tok0[qi]
                    a = 1 < mn1[req]
                    if eos_id is not None:
                        a = a & (t0 != eos_id)
                    srow, fs, ft, nf = paging.free_lane_pages(
                        staged_pt[qi], c["fs"], c["ft"], ~a)
                    return dict(
                        c, pt=c["pt"].at[b].set(srow), fs=fs, ft=ft,
                        tok=c["tok"].at[b].set(t0),
                        pos=c["pos"].at[b].set(staged_len[qi]),
                        alive=c["alive"].at[b].set(a),
                        lane=c["lane"].at[b].set(req),
                        out=c["out"].at[req, 0].set(t0),
                        n_out=c["n_out"].at[req].set(1),
                        sn=qi + 1, in_use=c["in_use"] - nf,
                        adm=c["adm"] + 1)

                if Q == 0:          # static: nothing staged to trace
                    return c
                return jax.lax.while_loop(adm_cond, adm_body, c)

            with jax.named_scope(spans.LOOP_ADMIT):
                return jax.lax.fori_loop(0, B, lane_fix, c)

        def cond(c):
            return c["alive"].any()

        def body(c):
            # 1. on-demand allocation for this step's KV writes
            with jax.named_scope(spans.LOOP_ALLOC):
                cols = jnp.clip(c["pos"] // page_size, 0, mp - 1)
                need = c["alive"] & (c["pt"][rows_b, cols] < 0)
                pt, ft, m = paging.alloc_pages(c["pt"], c["fs"], c["ft"],
                                               need, cols)
                ppr = c["ppr"].at[jnp.where(need, c["lane"], R)].add(
                    need.astype(jnp.int32))
                in_use = c["in_use"] + m
                peak = jnp.maximum(c["peak"], in_use)
            # 2. one token for every lane
            logits, pool = model.decode_step_paged(
                mcfg, params, c["pool"], pt, c["tok"], c["pos"],
                kv_kbits=kv_kbits, write_mask=c["alive"],
                paged_kernel=paged_kernel)
            with jax.named_scope(spans.HEAD):
                nxt = greedy_sample(logits)
            # 3. emit into the lane's *request* row
            with jax.named_scope(spans.LOOP_EMIT):
                rr = jnp.where(c["alive"], c["lane"], R)
                out = c["out"].at[
                    rr, jnp.clip(c["n_out"][rr], 0, out_cap - 1)].set(nxt)
                n_out = c["n_out"].at[rr].add(c["alive"].astype(jnp.int32))
                alive = c["alive"] & (n_out[c["lane"]] < mn1[c["lane"]])
                if eos_id is not None:
                    alive = alive & (nxt != eos_id)
                tok = jnp.where(alive, nxt, c["tok"])
                pos = c["pos"] + alive.astype(jnp.int32)
            c = dict(c, pool=pool, pt=pt, ft=ft, tok=tok, pos=pos,
                     alive=alive, out=out, n_out=n_out, in_use=in_use,
                     peak=peak, ppr=ppr, steps=c["steps"] + 1)
            # 4. free + refill (keeps cond() true while work remains)
            return maintain(c)

        # dead-on-arrival initial lanes must admit before the first
        # cond() check, or a bucket of max_new=1 requests with a full
        # stage queue would exit immediately
        c = jax.lax.while_loop(cond, body, maintain(c))
        pool = dict(c["pool"])
        reads = pool.pop(EXPERT_READS, jnp.asarray(0, jnp.int32))
        return (c["out"][:R], c["n_out"][:R], c["steps"], c["peak"],
                c["ppr"][:R], c["adm"], reads, pool)

    return jax.jit(loop, donate_argnums=(1,))


class ServeEngine:
    def __init__(self, mcfg: ModelConfig, params, *, max_batch: int = 8,
                 eos_id: int | None = None,
                 kv_frac_kbits: int | None = None,
                 meter: SustainabilityMeter | None = None,
                 mesh=None, paged: bool = False, page_size: int = 16,
                 stage_depth: int = 16, flash=None,
                 paged_kernel: bool | None = None):
        self.mcfg = mcfg
        self.max_batch = max_batch
        self.eos_id = eos_id
        self.kv_frac_kbits = kv_frac_kbits
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.page_size = page_size
        self.stage_depth = max(0, stage_depth)
        # families without an appendable KV cache fall back to the
        # contiguous layout: same results, different residency — loudly,
        # so capacity planning done against the paged byte model isn't
        # silently invalidated (docs/serving.md)
        self.paged = bool(paged) and model.supports_paged(mcfg)
        if paged and not self.paged:
            warnings.warn(
                f"paged=True requested but family {mcfg.family!r} does "
                "not support a paged KV cache (no appendable per-token "
                "slots); falling back to the contiguous layout — outputs "
                "are identical, the paged byte model does not apply.",
                UserWarning, stacklevel=2)
        # fused page-walk attention (kernels/paged_attn) instead of the
        # gather_pages read.  None defers to REPRO_PAGED_KERNEL — the
        # operational escape hatch, same contract as REPRO_FRAC_MODE —
        # then defaults off (the gather oracle stays the shipping path).
        if paged_kernel is None:
            env = os.environ.get("REPRO_PAGED_KERNEL")
            if env is None:
                paged_kernel = False
            elif env.lower() in ("1", "true", "on"):
                paged_kernel = True
            elif env.lower() in ("0", "false", "off"):
                paged_kernel = False
            else:
                raise ValueError(
                    f"REPRO_PAGED_KERNEL={env!r}: expected one of "
                    "1|true|on|0|false|off")
        self.paged_kernel = bool(paged_kernel) and self.paged
        if flash is not None:
            if not self.paged:
                raise ValueError(
                    "flash= (the recycled-flash spill tier) requires "
                    "paged=True on a family with model.supports_paged — "
                    f"family {mcfg.family!r}, paged={paged}")
            if mesh is not None:
                raise ValueError(
                    "flash= does not compose with mesh= yet: wave "
                    "fault-in reassembles caches host-side")
        self.flash = flash
        self.recovery: dict[int, dict] = {}    # rid -> recovery ledger
        self.timeouts: set[int] = set()
        self._step_s_est: float | None = None  # EWMA decode step time
        self.meter = meter or SustainabilityMeter(MeterConfig(), name="serve")
        self.reports: dict[int, EnergyReport] = {}
        self.mesh = mesh
        if mesh is not None:
            from repro.sharding import rules

            params = jax.device_put(
                params, rules.param_shardings(model.param_specs(mcfg), mesh))
        self.params = params
        self._pending: list[Request] = []   # O(pending): completed drain out
        self._results: dict[int, list[int]] = {}
        self._next_rid = 0
        self.stats = ServeStats()
        self._ragged_ok = model.supports_ragged(mcfg)
        self._prefill = jax.jit(self._prefill_fn)
        self._loops: dict[tuple, object] = {}

    @property
    def queue_depth(self) -> int:
        """Requests submitted but not yet completed — the load signal
        the fleet router (serve/router.py) scores regions on."""
        return len(self._pending)

    # -- admission -----------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16,
               max_wall_s: float | None = None) -> int:
        rid = self._next_rid
        self._next_rid += 1
        self._pending.append(Request(rid, np.asarray(prompt, np.int32),
                                     max_new_tokens, t_submit=time.time(),
                                     max_wall_s=max_wall_s))
        self.stats.requests += 1
        return rid

    # -- deadlines -----------------------------------------------------------
    def _finish_timeout(self, r: Request, now: float) -> None:
        """Expire a request like EOS: whatever it produced so far is its
        result, its spilled pages are dropped unread, and it leaves the
        queue — a stuck or endlessly-retrying lane cannot wedge the
        super-bucket behind it."""
        r.done = True
        r.t_done = now
        r.timed_out = True
        self._results[r.rid] = r.output
        self.stats.timeouts += 1
        self.timeouts.add(r.rid)
        if self.flash is not None:
            self.flash.discard(r.rid)
        self._pending = [p for p in self._pending if p.rid != r.rid]

    def _reap_expired(self) -> None:
        now = time.time()
        for r in [p for p in self._pending
                  if p.max_wall_s is not None
                  and now - p.t_submit >= p.max_wall_s]:
            self._finish_timeout(r, now)

    # -- chaos plane (serve/faults.py) ----------------------------------------
    def evict_pending(self, rids=None) -> list[Request]:
        """Remove pending requests (all of them, or the given rids)
        without serving them: their spilled flash pages are discarded
        and the Request objects — retained prompts included — returned
        so the caller (the fleet's crash recovery / migration ladder)
        can re-queue them on another replica."""
        if rids is None:
            victims = list(self._pending)
        else:
            want = set(rids)
            victims = [p for p in self._pending if p.rid in want]
        gone = {p.rid for p in victims}
        self._pending = [p for p in self._pending if p.rid not in gone]
        if self.flash is not None:
            for p in victims:
                self.flash.discard(p.rid)
        return victims

    def crash(self) -> list[Request]:
        """Simulate the replica process dying: every in-flight and
        staged request is lost — partial decode output, completed
        results, per-request reports, all process memory.  Returns the
        lost Requests (with their prompts) so the fleet can re-queue
        them on survivors; under greedy decode a re-served prompt
        regenerates bit-identical tokens, so recovery is exact.  The
        meter survives (it is the fleet's view of the region, not
        process state)."""
        victims = self.evict_pending()
        for p in victims:
            p.output = []           # partial decode dies with the process
        self._results.clear()
        self.reports.clear()
        self.recovery.clear()
        return victims

    def _deadline_max_new(self, r: Request) -> int:
        """Per-request decode budget for the next loop entry: the
        remaining wall budget divided by the measured step time (EWMA),
        floor 1 (the jitted loop cannot preempt a lane mid-flight, so
        granularity is one loop entry — documented in docs/serving.md)."""
        mn = max(1, r.max_new_tokens)
        if r.max_wall_s is None or not self._step_s_est:
            r.eff_max_new = mn
            return mn
        remaining = r.max_wall_s - (time.time() - r.t_submit)
        mn = max(1, min(mn, int(remaining / self._step_s_est)))
        r.eff_max_new = mn
        return mn

    def _note_steps(self, dt_s: float, steps: int) -> None:
        if steps > 0:
            per = dt_s / steps
            self._step_s_est = (per if self._step_s_est is None
                                else 0.7 * self._step_s_est + 0.3 * per)

    def _next_bucket(self) -> list[Request]:
        """Fill up to ``max_batch`` slots from the pending queue.

        Ragged families: the FIFO head anchors the bucket and the free
        slots go to the pending requests nearest in prompt length
        (bounds padding waste while keeping head-of-line latency).
        Exact-length families: the largest same-length group.
        """
        if not self._pending:
            return []
        if self._ragged_ok:
            head = self._pending[0]
            hl = len(head.prompt)
            rest = sorted(self._pending[1:],
                          key=lambda r: abs(len(r.prompt) - hl))
            return [head] + rest[: self.max_batch - 1]
        by_len: dict[int, list[Request]] = {}
        for r in self._pending:
            by_len.setdefault(len(r.prompt), []).append(r)
        best = max(by_len.values(), key=len)
        return best[: self.max_batch]

    def run(self) -> dict[int, list[int]]:
        """Serve until the pending queue is empty.  Contiguous mode:
        requests submitted between buckets join free slots at the next
        bucket boundary.  Paged mode: each super-bucket drains up to
        ``max_batch + stage_depth`` requests through in-loop admission.
        Returns {rid: tokens} for every completed request."""
        with TraceAnnotation(spans.RUN, R=len(self._pending)):
            while self._pending:
                self._reap_expired()
                if not self._pending:
                    break
                if self.paged and self.flash is not None:
                    self._serve_flash_bucket()
                elif self.paged:
                    self._serve_paged_bucket()
                else:
                    self._serve_bucket(self._next_bucket())
            return dict(self._results)

    def _bucket_geometry(self, reqs: list[Request]):
        """Shared bucket prep for both cache layouts: per-request
        lengths, right-padded prompt matrix, per-request max_new
        (clamped >= 1) and the decode horizon rounded up to a power of
        two — per-lane max_new bounds emission inside the loop and
        n_out trims the result, so the only effect of the rounding is
        a bounded set of compiled loop variants instead of one
        recompile per distinct max_new mix.  Byte accounting books the
        *actual* horizon (``kv_bytes_peak``); the rounded allocation is
        ``kv_bytes_pool``."""
        lens = np.asarray([len(r.prompt) for r in reqs], np.int32)
        S = int(lens.max())
        max_new = np.asarray([self._deadline_max_new(r) for r in reqs],
                             np.int32)
        horizon = int(max_new.max())
        out_cap = 1 << (horizon - 1).bit_length()
        prompts = np.zeros((len(reqs), S), np.int32)
        for i, r in enumerate(reqs):
            prompts[i, : lens[i]] = r.prompt
        return lens, S, max_new, horizon, out_cap, prompts

    def _contig_cache_bytes(self, B: int, seq_len: int) -> int:
        return sum(
            int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
            for s in jax.tree.leaves(
                model.cache_specs(self.mcfg, B, seq_len),
                is_leaf=is_leaf_spec)
            if jnp.issubdtype(s.dtype, jnp.floating))

    # -- one bucket ----------------------------------------------------------
    def _serve_bucket(self, bucket: list[Request]) -> None:
        B = len(bucket)
        with TraceAnnotation(spans.ADMIT):
            lens, S, max_new, horizon, out_cap, prompts = \
                self._bucket_geometry(bucket)
            ragged = self._ragged_ok and bool((lens != S).any())
            batch = {"tokens": jnp.asarray(prompts)}
            if self.mcfg.family == "audio":
                batch["enc_embeds"] = jnp.zeros(
                    (B, self.mcfg.encoder_seq, self.mcfg.d_model),
                    jnp.bfloat16)
            pos0 = jnp.asarray(lens)
            mn = jnp.asarray(max_new)
        t_bucket0 = time.time()
        with TraceAnnotation(spans.PREFILL, R=B, S=S):
            tok0, cache = self._prefill(
                self.params, batch, jnp.asarray(lens) if ragged else None)
            self.stats.prefills += 1
            cache = self._grow_cache(cache, B, S + out_cap)
            # the contiguous layout holds every lane at bucket-max for
            # the whole bucket (the numbers the paged layout beats —
            # bench_serve gates both ratios).  Symmetric with the paged
            # side: peak = the actual horizon (resident model), pool =
            # the pow2-rounded allocation (physical) — never-writable
            # rounding tail excluded from peak on both layouts.
            self.stats.kv_bytes_peak = max(
                self.stats.kv_bytes_peak,
                self._contig_cache_bytes(B, S + horizon))
            self.stats.kv_bytes_pool = max(
                self.stats.kv_bytes_pool,
                self._contig_cache_bytes(B, S + out_cap))
            bucket_kv_frac = 0
            if self.kv_frac_kbits is not None:
                cache, bucket_kv_frac = self._frac_cache(cache, B,
                                                         S + horizon)
            if self.mesh is not None:
                from jax.sharding import NamedSharding

                from repro.sharding import rules

                specs = model.cache_specs(self.mcfg, B, S + out_cap)
                cache = jax.device_put(
                    cache, rules.cache_shardings(specs, self.mesh, B))
                vec, _ = rules.serve_loop_spec(self.mesh, B)
                sh = NamedSharding(self.mesh, vec)
                tok0, pos0, mn = jax.device_put((tok0, pos0, mn),
                                                (sh, sh, sh))
        # first token is ready here: TTFT measured from each request's
        # own submit time (a sync, not a transfer — the value stays on
        # device and rides the output buffer)
        with TraceAnnotation(spans.FIRST_TOKEN_SYNC):
            tok0.block_until_ready()
        t_first = time.time()
        for r in bucket:
            r.t_first = t_first
            self.stats.ttft_s.append(t_first - r.t_submit)
        with TraceAnnotation(spans.LOOP, out_cap=out_cap):
            loop = self._get_loop(ragged, out_cap)
            out, n_out, steps, _ = loop(self.params, cache, tok0, pos0, mn)
            # the decode phase's single host transfer
            out_np, n_np, steps_np = jax.device_get((out, n_out, steps))
        with TraceAnnotation(spans.FINISH, steps=int(steps_np)):
            self.stats.host_syncs += 1
            now = time.time()
            self.stats.decode_steps += int(steps_np)
            self._note_steps(now - t_first, int(steps_np))
            self.stats.decode_s += now - t_first
            self._finish_bucket(bucket, out_np, n_np, now, now - t_bucket0,
                                lambda i: bucket_kv_frac // B)

    def _finish_bucket(self, reqs, out_np, n_np, now, bucket_dt,
                       kv_bytes_fn) -> None:
        """Shared bucket-completion tail for both cache layouts:
        results, token stats, per-request meter booking (the request's
        token-share of bucket wall time plus its FRAC KV flash
        residency slice — early exit books only the tokens actually
        decoded), and the pending-queue drain.  ``kv_bytes_fn(i)`` is
        request ``i``'s FRAC KV bytes: its per-lane share of the grown
        contiguous cache, or its own allocated pages when paged."""
        total_toks = int(n_np.sum()) or 1
        done_ids = set()
        for i, r in enumerate(reqs):
            ntok = int(n_np[i])
            r.output = [int(t) for t in out_np[i, :ntok]]
            r.done = True
            r.t_done = now
            # a deadline-clamped lane that used its whole clamped budget
            # was cut by the clock, not by EOS/max_new: book the timeout
            if (r.max_wall_s is not None and r.eff_max_new is not None
                    and r.eff_max_new < max(1, r.max_new_tokens)
                    and ntok >= r.eff_max_new):
                r.timed_out = True
                self.stats.timeouts += 1
                self.timeouts.add(r.rid)
            done_ids.add(r.rid)
            self._results[r.rid] = r.output
            self.stats.tokens += ntok
            self.reports[r.rid] = self.meter.request(
                ntok, bucket_dt * ntok / total_toks,
                rid=r.rid, kv_frac_bytes=kv_bytes_fn(i),
                kv_occupancy_s=bucket_dt,
            )
        self._pending = [p for p in self._pending if p.rid not in done_ids]

    # -- one paged super-bucket ----------------------------------------------
    def _serve_paged_bucket(self) -> None:
        """Serve up to ``max_batch`` lanes plus ``stage_depth`` staged
        requests through one prefill, one while_loop, one host sync.

        All R requests share one ragged right-padded prefill (per-lane
        numerics are batch-independent, so this is bit-identical to
        prefilling each alone); every request's prompt KV is scattered
        into its pages and its first token staged on device.  The loop
        then decodes B lanes, refilling each dead lane from the stage
        queue in-loop (see build_paged_decode_loop).  Byte accounting
        books *allocated pages only* — the per-request ``EnergyReport``
        carries its own pages' FRAC bytes, and ``stats.kv_bytes_peak``
        tracks the true high-water mark of concurrently live pages.
        """
        with TraceAnnotation(spans.ADMIT):
            nb = min(self.max_batch, len(self._pending))
            reqs = self._pending[: nb + self.stage_depth]
            staged_n = len(reqs) - nb
            lens, S, max_new, _, out_cap, prompts = \
                self._bucket_geometry(reqs)
            # pow2=True bounds the compiled loop variants (pool + table
            # shapes round up; spare pages idle on the free stack) — B
            # and Q are already bounded by max_batch / stage_depth,
            # out_cap by its own rounding
            plan = paging.plan_pages(lens, max_new, nb, self.page_size,
                                     pow2=True)
            full_table = np.concatenate([plan.page_table, plan.staged_pt])
            pi, oi = paging.pool_scatter_indices(
                full_table, lens, S, plan.n_pages, self.page_size)
            pool_specs = model.paged_pool_specs(
                self.mcfg, plan.n_pages, self.page_size)
            pi, oi = jnp.asarray(pi), jnp.asarray(oi)
            pt = jnp.asarray(plan.page_table)
            spt = jnp.asarray(plan.staged_pt)
            fs = jnp.asarray(plan.free_stack)
            pos0 = jnp.asarray(lens[:nb])
            slen = jnp.asarray(lens[nb:])
            mn = jnp.asarray(max_new)
            if self.mesh is not None:
                from jax.sharding import NamedSharding

                from repro.sharding import rules

                rep = NamedSharding(self.mesh,
                                    rules.serve_paged_spec(self.mesh))
                pt, spt, fs, pos0, slen, mn = jax.device_put(
                    (pt, spt, fs, pos0, slen, mn), (rep,) * 6)
        t_bucket0 = time.time()
        with TraceAnnotation(spans.PREFILL, R=len(reqs), S=S):
            tok0, cache = self._prefill(
                self.params, {"tokens": jnp.asarray(prompts)},
                jnp.asarray(lens))
            self.stats.prefills += 1
            if self.kv_frac_kbits is not None:
                # same slot-granular fake-quant as the contiguous FRAC
                # tier (one scale per (K, hd) row) — page layout changes
                # where bytes LIVE, never a lane's numerics
                cache = _frac_kv_rows(cache, self.kv_frac_kbits)
        with TraceAnnotation(spans.POOL_FILL, pages=plan.n_pages):
            pool = jax.tree.map(
                lambda spec, leaf: paging.fill_pool(
                    jnp.zeros(spec.shape, leaf.dtype), leaf, pi, oi),
                pool_specs, cache, is_leaf=is_leaf_spec)
            del cache               # the pool holds the prompt KV now
            if self.mesh is not None:
                pool = jax.device_put(
                    pool, rules.cache_shardings(pool_specs, self.mesh, nb))
        with TraceAnnotation(spans.FIRST_TOKEN_SYNC):
            tok0.block_until_ready()
        t_first = time.time()
        for r in reqs:
            r.t_first = t_first
            self.stats.ttft_s.append(t_first - r.t_submit)
        with TraceAnnotation(spans.LOOP, out_cap=out_cap):
            loop = self._get_paged_loop(out_cap)
            out, n_out, steps, peak, ppr, adm, reads, _ = loop(
                self.params, pool, pt, fs, np.int32(plan.free_top),
                tok0[:nb], pos0, tok0[nb:], slen, spt, mn)
            # the super-bucket's single host transfer
            out_np, n_np, steps_np, peak_np, ppr_np, adm_np, reads_np = \
                jax.device_get((out, n_out, steps, peak, ppr, adm, reads))
        with TraceAnnotation(spans.FINISH, steps=int(steps_np)):
            self.stats.host_syncs += 1
            now = time.time()
            self.stats.decode_steps += int(steps_np)
            self._note_steps(now - t_first, int(steps_np))
            self.stats.decode_s += now - t_first
            self.stats.admissions += int(adm_np)
            self.stats.expert_reads += int(reads_np)
            assert int(adm_np) == staged_n, "stage queue not drained in-loop"
            self._book_pages(nb, plan, peak_np, ppr_np, reqs, out_np, n_np,
                             now, now - t_bucket0)

    # -- flash-oversubscribed super-bucket -------------------------------------
    def _serve_flash_bucket(self) -> None:
        """Oversubscribed super-bucket: one shared ragged prefill for
        active + staged requests, the staged requests' prompt KV evicted
        (coldest-first) into the flash tier, then host-orchestrated
        waves of up to ``max_batch`` lanes — each wave faults its pages
        back in through the recovery ladder and runs the same jitted
        paged loop over a *wave-sized* pool.  The HBM high-water mark is
        one wave's pool instead of the whole bucket's (the
        sequences-per-pool-byte win bench_serve gates); the extra host
        syncs per wave and any recovery work are the reported overhead.
        A tier that cannot hold even one staged request degrades to
        exactly the non-oversubscribed path."""
        from repro.serve import flash_tier as ftier

        with TraceAnnotation(spans.ADMIT):
            nb = min(self.max_batch, len(self._pending))
            cand = self._pending[: nb + self.stage_depth]
            staged = cand[nb:]
            # LRU victim order over the cold staged prompts (their KV is
            # untouched since submit), then a greedy capacity dry-run
            order = ftier.pick_victims(
                [(i, r.t_submit) for i, r in enumerate(staged)])
            sizes_all: list[int] = []
            fit: list[int] = []
            for i in order:
                sizes = self._spill_page_sizes(len(staged[i].prompt))
                if self.flash.would_fit(sizes_all + sizes):
                    sizes_all += sizes
                    fit.append(i)
            if fit:
                reqs = cand[:nb] + [staged[i] for i in sorted(fit)]
                lens, S, max_new, _, out_cap, prompts = \
                    self._bucket_geometry(reqs)
        if not fit:
            # exhausted tier (or nothing staged): exactly PR-5 behavior
            self._serve_paged_bucket()
            return
        t_bucket0 = time.time()
        with TraceAnnotation(spans.PREFILL, R=len(reqs), S=S):
            tok0, cache = self._prefill(
                self.params, {"tokens": jnp.asarray(prompts)},
                jnp.asarray(lens))
            self.stats.prefills += 1
            if self.kv_frac_kbits is not None:
                cache = _frac_kv_rows(cache, self.kv_frac_kbits)
            leaves, treedef = jax.tree.flatten(cache)
        with TraceAnnotation(spans.FIRST_TOKEN_SYNC):
            tok0_np = np.asarray(jax.device_get(tok0))
        t_first = time.time()
        t0map = {r.rid: int(tok0_np[i]) for i, r in enumerate(reqs)}
        # spill the staged prompt KV straight from the prefill transient
        # (those pages never enter the HBM pool); a request whose spill
        # fails mid-way (capacity drifted under an injected event) rolls
        # back and stays pending for the next super-bucket
        staged_reqs = reqs[nb:]
        queue: list[Request] = []
        if staged_reqs:
            with TraceAnnotation(spans.SPILL, R=len(staged_reqs)):
                staged_np = jax.device_get([l[:, nb:] for l in leaves])
                self.stats.host_syncs += 1   # oversubscription overhead
                for j, r in enumerate(staged_reqs):
                    if self._spill_request(r, staged_np, j):
                        queue.append(r)
                    else:
                        self.flash.discard(r.rid)
        for r in reqs[:nb] + queue:          # the actually-served set
            r.t_first = t_first
            self.stats.ttft_s.append(t_first - r.t_submit)
        # wave 1: active lanes decode from the device-resident prefill
        # slices — the hot set never round-trips through the host
        self._serve_wave(reqs[:nb], [l[:, :nb] for l in leaves],
                         treedef, t0map)
        while queue:
            now = time.time()
            for r in [q for q in queue
                      if q.max_wall_s is not None
                      and now - q.t_submit >= q.max_wall_s]:
                self._finish_timeout(r, now)
                queue.remove(r)
            if not queue:
                break
            wave, queue = queue[: self.max_batch], queue[self.max_batch:]
            with TraceAnnotation(spans.FAULT_IN, R=len(wave)):
                wave_np = self._fault_in_wave(wave, leaves, t0map)
            self._serve_wave(wave, wave_np, treedef, t0map)
        # flash I/O energy: device-level ops at wear.py prices plus the
        # spilled bytes' recycled-flash embodied residency share
        io = self.flash.drain_io()
        if io["reads"] or io["writes"] or io["erases"]:
            dt = time.time() - t_bucket0
            self.meter.flash_io(
                io["energy_j"], reads=io["reads"], writes=io["writes"],
                erases=io["erases"],
                tb_s=self.flash.stats.bytes_live_peak * dt / 1e12)
        fs = self.flash.stats
        self.stats.spills = fs.spills
        self.stats.faultins = fs.faultins
        self.stats.ecc_corrected = fs.ecc_corrected
        self.stats.retry_reads = fs.retry_reads
        self.stats.flash_bytes_peak = max(self.stats.flash_bytes_peak,
                                          fs.bytes_live_peak)

    def _spill_page_sizes(self, plen: int) -> list[int]:
        """Byte size of each prompt page of a length-``plen`` request as
        spilled: all layers' k/v rows for the page's *valid* slots only
        (the right-padding never leaves the device)."""
        row_b = self._page_bytes()[0] // self.page_size
        ps = self.page_size
        return [row_b * (min(plen, (pg + 1) * ps) - pg * ps)
                for pg in range(paging.pages_for(plen, ps))]

    def _spill_request(self, r: Request, staged_np, j: int) -> bool:
        """Evict request ``r``'s prompt pages (leaf-concatenated bytes,
        valid rows only) into the flash tier.  False = tier full."""
        ps = self.page_size
        plen = len(r.prompt)
        for pg in range(paging.pages_for(plen, ps)):
            lo, hi = pg * ps, min(plen, (pg + 1) * ps)
            data = b"".join(
                np.ascontiguousarray(l[:, j, lo:hi]).tobytes()
                for l in staged_np)
            if not self.flash.spill(r.rid, pg, data):
                return False
        return True

    def _fault_in_wave(self, wave, leaves, t0map) -> list:
        """Restore a wave's prompt KV from the flash tier into
        prefill-cache-shaped numpy leaves, running the recovery ladder
        per page; lanes with an unrecoverable page are replayed from
        their retained prompts in one ragged re-prefill (stage 3)."""
        ps = self.page_size
        lens_w = [len(r.prompt) for r in wave]
        S_w = max(lens_w)
        outs = [np.zeros((l.shape[0], len(wave), S_w) + tuple(l.shape[3:]),
                         dtype=l.dtype) for l in leaves]
        failed: list[int] = []
        for j, r in enumerate(wave):
            rec = self.recovery.setdefault(
                r.rid, {"ecc": 0, "retry": 0, "lost_pages": 0,
                        "reprefill": False, "tokens_replayed": 0})
            ok = True
            for pg in range(paging.pages_for(lens_w[j], ps)):
                data, stage = self.flash.fault_in(r.rid, pg)
                if stage == "ecc":
                    rec["ecc"] += 1
                elif stage == "retry":
                    rec["retry"] += 1
                if data is None:
                    rec["lost_pages"] += 1
                    ok = False      # keep draining the lane's other pages
                    continue
                self._write_page(outs, j, pg, data, lens_w[j])
            if not ok:
                failed.append(j)
        if failed:
            self._reprefill(wave, failed, outs, t0map)
        return outs

    def _write_page(self, outs, j: int, pg: int, data: bytes,
                    plen: int) -> None:
        """Split one restored page's bytes back into the cache leaves
        (inverse of the ``_spill_request`` concatenation)."""
        ps = self.page_size
        lo, hi = pg * ps, min(plen, (pg + 1) * ps)
        off = 0
        for o in outs:
            tail = tuple(o.shape[3:])
            n = o.shape[0] * (hi - lo) * int(np.prod(tail))
            seg = n * o.dtype.itemsize
            o[:, j, lo:hi] = np.frombuffer(
                data[off:off + seg], dtype=o.dtype
            ).reshape((o.shape[0], hi - lo) + tail)
            off += seg
        assert off == len(data), "page byte split out of register"

    def _reprefill(self, wave, failed, outs, t0map) -> None:
        """Recovery stage 3: replay the failed lanes' prompts through
        one ragged prefill.  Prefill is deterministic and its per-lane
        numerics batch-independent, so the regenerated KV — and the
        first token, asserted against the original — is bit-identical
        to what was lost; the cost is the replayed prompt tokens."""
        reqs = [wave[j] for j in failed]
        lens = np.asarray([len(r.prompt) for r in reqs], np.int32)
        S = int(lens.max())
        prompts = np.zeros((len(reqs), S), np.int32)
        for i, r in enumerate(reqs):
            prompts[i, : lens[i]] = r.prompt
        t_rec0 = time.time()
        tok0, cache = self._prefill(
            self.params, {"tokens": jnp.asarray(prompts)}, jnp.asarray(lens))
        self.stats.prefills += 1
        if self.kv_frac_kbits is not None:
            cache = _frac_kv_rows(cache, self.kv_frac_kbits)
        tok0_np, rp = jax.device_get((tok0, jax.tree.leaves(cache)))
        self.stats.host_syncs += 1           # recovery overhead
        for i, j in enumerate(failed):
            r = wave[j]
            assert int(tok0_np[i]) == t0map[r.rid], \
                "re-prefill diverged from the original prefill"
            for o, src in zip(outs, rp):
                o[:, j, : lens[i]] = src[:, i, : lens[i]]
            self.stats.reprefills += 1
            self.stats.reprefill_tokens += int(lens[i])
            rec = self.recovery[r.rid]
            rec["reprefill"] = True
            rec["tokens_replayed"] += int(lens[i])
        # resilience has a carbon price: the replayed prefill's compute
        # goes to the meter's recovery ledger (detail["recovery"])
        self.meter.recovery(time.time() - t_rec0, reprefills=len(failed),
                            tokens_replayed=int(lens.sum()))

    def _serve_wave(self, wreqs, wave_leaves, treedef, t0map) -> None:
        """One non-oversubscribed paged decode over a wave-sized pool —
        the same jitted loop as the plain paged path with an empty stage
        queue (Q=0 statically skips the admission machinery)."""
        ps = self.page_size
        t_wave0 = time.time()
        with TraceAnnotation(spans.ADMIT):
            lens = np.asarray([len(r.prompt) for r in wreqs], np.int32)
            S_w = int(lens.max())
            max_new = np.asarray(
                [self._deadline_max_new(r) for r in wreqs], np.int32)
            out_cap = 1 << (int(max_new.max()) - 1).bit_length()
            plan = paging.plan_pages(lens, max_new, len(wreqs), ps,
                                     pow2=True)
            pi, oi = paging.pool_scatter_indices(
                plan.page_table, lens, S_w, plan.n_pages, ps)
            pool_specs = model.paged_pool_specs(self.mcfg, plan.n_pages, ps)
            pi, oi = jnp.asarray(pi), jnp.asarray(oi)
            tok0 = jnp.asarray([t0map[r.rid] for r in wreqs], jnp.int32)
        with TraceAnnotation(spans.POOL_FILL, pages=plan.n_pages):
            cache_w = jax.tree.unflatten(
                treedef, [jnp.asarray(l[:, :, :S_w]) for l in wave_leaves])
            pool = jax.tree.map(
                lambda spec, leaf: paging.fill_pool(
                    jnp.zeros(spec.shape, leaf.dtype), leaf, pi, oi),
                pool_specs, cache_w, is_leaf=is_leaf_spec)
        with TraceAnnotation(spans.LOOP, out_cap=out_cap):
            loop = self._get_paged_loop(out_cap)
            out, n_out, steps, peak, ppr, adm, reads, _ = loop(
                self.params, pool, jnp.asarray(plan.page_table),
                jnp.asarray(plan.free_stack), np.int32(plan.free_top),
                tok0, jnp.asarray(lens), jnp.zeros((0,), jnp.int32),
                jnp.zeros((0,), jnp.int32), jnp.asarray(plan.staged_pt),
                jnp.asarray(max_new))
            out_np, n_np, steps_np, peak_np, ppr_np, adm_np, reads_np = \
                jax.device_get((out, n_out, steps, peak, ppr, adm, reads))
        with TraceAnnotation(spans.FINISH, steps=int(steps_np)):
            self.stats.host_syncs += 1
            now = time.time()
            self.stats.decode_steps += int(steps_np)
            self._note_steps(now - t_wave0, int(steps_np))
            self.stats.decode_s += now - t_wave0
            assert int(adm_np) == 0
            self.stats.expert_reads += int(reads_np)
            self.stats.oversub_waves += 1
            self._book_pages(len(wreqs), plan, peak_np, ppr_np, wreqs,
                             out_np, n_np, now, now - t_wave0)

    def _book_pages(self, nb, plan, peak_np, ppr_np, reqs, out_np, n_np,
                    now, dt) -> None:
        """Paged bucket tail: page and byte high-water marks, each
        request's FRAC bytes from its own allocated pages, then
        ``_finish_bucket``."""
        self._note_attn_transient(nb, plan.page_table.shape[1])
        page_full_b, page_frac_b = self._page_bytes()
        self.stats.kv_pages_peak = max(self.stats.kv_pages_peak,
                                       int(peak_np))
        self.stats.kv_bytes_peak = max(self.stats.kv_bytes_peak,
                                       int(peak_np) * page_full_b)
        self.stats.kv_bytes_pool = max(self.stats.kv_bytes_pool,
                                       plan.n_pages * page_full_b)
        kv_bytes_fn = lambda i: 0
        if self.kv_frac_kbits is not None:
            pages_total = int(ppr_np.sum())
            self.stats.kv_bytes_full += pages_total * page_full_b
            self.stats.kv_bytes_frac += pages_total * page_frac_b
            kv_bytes_fn = lambda i: int(ppr_np[i]) * page_frac_b
        self._finish_bucket(reqs, out_np, n_np, now, dt, kv_bytes_fn)

    def _page_bytes(self) -> tuple[int, int]:
        """(full, frac) resident bytes per allocated page, summed over
        every layer's k/v pool leaf — frac books each page as its own
        FRAC stream (``ops.compressed_nbytes_pages``)."""
        from repro.kernels.frac_pack import ops as fops

        specs = model.paged_pool_specs(self.mcfg, 2, self.page_size)
        full = frac = 0
        for s in jax.tree.leaves(specs, is_leaf=is_leaf_spec):
            layers = s.shape[0]
            elems = int(np.prod(s.shape[2:]))    # one page, one layer
            full += layers * elems * jnp.dtype(s.dtype).itemsize
            if self.kv_frac_kbits is not None:
                frac += layers * fops.compressed_nbytes_pages(
                    1, elems, self.kv_frac_kbits)
        return full, frac

    def _get_paged_loop(self, out_cap: int):
        key = ("paged", out_cap, self.paged_kernel)
        if key not in self._loops:
            self._loops[key] = build_paged_decode_loop(
                self.mcfg, eos_id=self.eos_id, kv_kbits=self.kv_frac_kbits,
                out_cap=out_cap, page_size=self.page_size,
                paged_kernel=self.paged_kernel)
        return self._loops[key]

    def _note_attn_transient(self, nb: int, max_pages: int) -> None:
        """Stamp the modeled peak attention-read transient of this
        bucket's decode steps (kernels/paged_attn/ops.py byte model) —
        what the CI bench gate compares between the gather and fused
        read paths."""
        from repro.kernels.paged_attn import ops as pops

        cfg = self.mcfg
        K, hd = cfg.num_kv_heads, cfg.head_dim
        G = cfg.num_heads // K
        item = 2 if cfg.dtype in ("bfloat16", "float16") else 4
        if self.paged_kernel:
            b = pops.kernel_transient_bytes(
                nb, self.page_size, K, G, hd, item,
                chunk=min(pops.PAGES_PER_CHUNK, max_pages))
        else:
            b = pops.gather_transient_bytes(nb, max_pages, self.page_size,
                                            K, G, hd, item)
        self.stats.attn_transient_peak = max(
            self.stats.attn_transient_peak, b)

    # -- pieces --------------------------------------------------------------
    def _prefill_fn(self, params, batch, lengths):
        logits, cache = model.prefill(self.mcfg, params, batch,
                                      lengths=lengths)
        return greedy_sample(logits[:, -1]), cache

    def _get_loop(self, ragged: bool, out_cap: int):
        key = (ragged, out_cap)
        if key not in self._loops:
            self._loops[key] = build_decode_loop(
                self.mcfg, eos_id=self.eos_id, kv_kbits=self.kv_frac_kbits,
                ragged=ragged, out_cap=out_cap)
        return self._loops[key]

    def energy_report(self) -> EnergyReport:
        """Cumulative EnergyReport over everything served so far."""
        return self.meter.report()

    def _frac_cache(self, cache, B: int, S_cache: int):
        """Emulate a FRAC-stored KV cache: every float leaf goes through
        slot-granular fake-quant at ``kv_frac_kbits`` (one scale per
        (kv_heads, head_dim) row for attention KV — the cell-array write
        unit — so a lane's fidelity never depends on its bucket
        neighbours; state-space leaves quantize per trailing row).
        Decode-written slots are quantized the same way *inside* the
        loop (model.decode_step kv_kbits).  Books the modeled byte
        savings over the *actual* decode horizon (``S_cache`` = prompt
        + bucket max_new) via the codec's ``compressed_nbytes`` — the
        allocated cache may be padded further to a power-of-two tail
        for compile-variant bounding, but those never-writable slots
        are not billed.  Returns (cache, frac bytes)."""
        from repro.kernels.frac_pack import ops as fops

        k = self.kv_frac_kbits
        specs = model.cache_specs(self.mcfg, B, S_cache)
        leaves, treedef = jax.tree.flatten(cache)
        spec_leaves = jax.tree.leaves(specs, is_leaf=is_leaf_spec)
        frac_bytes = 0
        new = []
        for leaf, spec in zip(leaves, spec_leaves):
            if jnp.issubdtype(leaf.dtype, jnp.floating):
                n = int(np.prod(spec.shape))       # horizon, not allocation
                self.stats.kv_bytes_full += n * leaf.dtype.itemsize
                # packed words + one fp32 scale per quant block; the
                # codec owns this math (exact also for fractional k)
                frac_bytes += fops.compressed_nbytes(n, k)
                rd = 2 if spec.dims[-2:] == ("kv_heads", "head_dim") else 1
                leaf = fops.fake_quant_slots(leaf, k, row_dims=rd)
            new.append(leaf)
        self.stats.kv_bytes_frac += frac_bytes
        return jax.tree.unflatten(treedef, new), frac_bytes

    def _grow_cache(self, cache, B: int, target: int):
        return grow_cache(self.mcfg, cache, B, target)


def grow_cache(mcfg: ModelConfig, cache, B: int, target: int):
    """Pad prefill caches (built at prompt length) out to the decode
    horizon.  Rolling (SWA) caches already have fixed window size."""
    specs = model.cache_specs(mcfg, B, target)

    def grow(spec, leaf):
        want = spec.shape
        if leaf.shape == want:
            return leaf
        pads = [(0, w - h) for h, w in zip(leaf.shape, want)]
        return jnp.pad(leaf, pads)

    return jax.tree.map(grow, specs, cache,
                        is_leaf=lambda x: is_leaf_spec(x))
