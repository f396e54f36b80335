"""Batched serving engines.

Two schedulers over the unified block-decode core
(``repro.core.block_loop``), both exposing the same **request-level,
incremental API** (types in ``repro.serving.api``):

- ``add_request(GenerationRequest) -> id`` — enqueue one request
  (engine-assigned unique id when ``id=None``);
- ``step() -> list[BlockEvent]`` — advance one block boundary and return
  the blocks that finalized this step (block-causal finalization means a
  returned block is committed and will never change — the natural exact
  streaming unit);
- ``abort(id)`` — drop a queued or in-flight request (freeing its lane
  and, in the paged layout, its pages) without perturbing other lanes;
- ``has_unfinished()`` — anything queued or decoding;
- ``stream(requests)`` — iterator yielding :class:`BlockEvent` as blocks
  commit;
- ``generate(requests)`` — thin drain-the-stepper wrapper returning final
  :class:`GenerationOutput` per request (bit-identical to the historical
  batch-synchronous behavior).

Sampling parameters are **per-request** (:class:`SamplingParams`):
temperature, confidence threshold, max_tokens, RNG seed and EOS override
all resolve against ``ServeConfig`` defaults and are threaded through the
decode loops as per-lane ``(b,)`` arrays
(:class:`repro.core.block_loop.LaneParams`), so one continuous batch can
mix greedy and sampled lanes. Sampled lanes draw with their *own* PRNG
stream (advanced only on the lane's own active iterations), which keeps
every lane bit-identical to its isolated decode regardless of batch
composition — the same isolation-exactness invariant the scheduler
already relied on for greedy lanes.

The two schedulers:

- :class:`Engine` — **static batching**: requests are padded into
  fixed-shape batches and each batch runs the full jitted sampler to
  completion. ``step()`` launches one batch and emits its block events at
  once.

- :class:`ContinuousEngine` — **continuous block-level batching**: a
  persistent decode batch of ``max_batch`` lanes advances one *block* per
  jitted step, each lane at its own block offset
  (:func:`repro.core.block_loop.lane_block_forward`). At every block
  boundary finished lanes are evicted, their cache rows reset
  (:func:`repro.core.cache.reset`), and queued requests admitted mid-flight
  (prompt prefill committed into the freed rows via ``commit_rows``).
  Block-causal cache exactness makes lane recycling loss-free, so a lane
  admitted mid-flight decodes bit-identically to one decoded in isolation.

The continuous engine runs over either KV layout
(``ServeConfig.cache_layout``):

- ``dense``: per-lane ``max_len`` KV rows — admission is slot-bound.
- ``paged``: a global page pool (page size = block size) with per-lane page
  tables (:class:`repro.core.cache.PagedCache`). Admission is *page*-bound:
  a request is admitted whenever pages for its prompt and next block exist
  (no whole-sequence reservation), each block boundary allocates just the
  pages the live lanes' next blocks need, and eviction returns a lane's
  pages to the pool. Lanes that cannot get their next page stall for a
  round; if every live lane stalls, the youngest lane is preempted (pages
  freed, request requeued — loss-free, since re-decoding from the
  request's own RNG stream is deterministic). A pool holding one full
  canvas is the deadlock-free minimum; sizing it below ``max_batch`` full
  canvases is what buys higher concurrency per HBM byte at mixed
  generation lengths.

  Paged scheduling is *sync-free and overlapped*: because the device
  allocator (:func:`repro.core.cache.alloc`) is deterministic — lanes
  scanned in index order, all-or-nothing per lane, pages handed out
  lowest-index-first — the host mirrors page accounting exactly (per-lane
  allocated-slot high-water mark + a free-page counter) and never blocks
  on device allocation results. Each ``step()`` dispatches
  in-flight-block alloc → admission → decode → *next-block prefetch*
  back-to-back; in-flight lanes get pages **before** admissions (a
  newcomer can't starve a running lane), and the prefetch claims the
  following block's pages while the current block's results drain, so the
  next boundary's alloc is a no-op. Page identity never feeds the decode
  math, so dense and paged decodes stay bit-identical.

Metrics follow the paper (Tables 1–2): per-request latency, TPS (valid
tokens / wall-clock), refinement steps, generation length. The continuous
engine reports true per-request latency (arrival → completion, queueing
included) instead of a per-chunk average.
"""
from __future__ import annotations

import bisect
import time
from typing import Any, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ServeConfig
from repro.core import cache as C
from repro.core import diffusion as D
from repro.core import masks
from repro.core.block_loop import (
    STRATEGIES,
    LaneParams,
    SamplerSpec,
    _gen_lengths,
    init_canvas,
    lane_block_forward,
    run_block_loop,
)
from repro.core.sampler import SAMPLERS
from repro.models import forward, unembed_matrix
from repro.serving import tracing
from repro.serving.api import (
    BlockEvent,
    GenerationOutput,
    GenerationRequest,
    Request,  # noqa: F401  (re-exported legacy name)
    ResolvedSamplingParams,
    Response,  # noqa: F401  (re-exported legacy name)
    SamplingParams,
    normalize_requests,
)


def _validate_requests(requests: Sequence[GenerationRequest]) -> None:
    keys0 = frozenset(requests[0].extras or {})
    for r in requests:
        if frozenset(r.extras or {}) != keys0:
            raise ValueError(
                "all requests in a batch must carry the same extras keys: "
                f"request {requests[0].id} has {sorted(keys0)}, request "
                f"{r.id} has {sorted(r.extras or {})}")


def _resolve(req: GenerationRequest, serve: ServeConfig,
             cfg: ModelConfig) -> ResolvedSamplingParams:
    params = req.params if req.params is not None else SamplingParams()
    return params.resolve(serve, cfg, request_id=req.id,
                          legacy_max_tokens=req.max_tokens)


def _validate_params(req: GenerationRequest, serve: ServeConfig) -> None:
    """Per-request params constraints, checked at ``add_request`` time so
    a bad request fails its own submission (HTTP 400) instead of blowing
    up the shared decode step later.

    - Non-threshold samplers have no per-lane selection loop.
    - ``fused_select`` engines are greedy-only: a sampled lane in the
      batch would silently flip its greedy chunk-mates from the fused
      online-softmax kernel to the dense selection path, whose last-ULP
      confidence differences could break isolated-decode exactness.
    """
    if req.params is None or req.params.is_engine_default:
        return
    if STRATEGIES[serve.sampler].finalize != "threshold":
        raise ValueError(
            "per-request SamplingParams require a threshold-finalize "
            f"sampler; {serve.sampler!r} uses "
            f"{STRATEGIES[serve.sampler].finalize!r} (set the knobs "
            "globally in ServeConfig instead)")
    if serve.fused_select and (req.params.temperature or 0) > 0:
        raise ValueError(
            "fused_select engines serve greedy requests only "
            "(per-request temperature > 0 would mix fused and dense "
            "selection paths within one batch); disable fused_select to "
            "serve sampled requests")


def _lane_key(rp: ResolvedSamplingParams) -> np.ndarray:
    """A request's RNG stream root: ``PRNGKey(seed)`` — scheduler- and
    batch-invariant, so isolated and batched decodes draw identically."""
    return np.asarray(jax.random.PRNGKey(rp.seed), np.uint32)


def _finish_reason(gen: np.ndarray, glen_raw: int,
                   rp: ResolvedSamplingParams) -> str:
    """"stop" when the request's EOS token landed within its budget."""
    if not np.any(gen == rp.eos_token_id):
        return "length"
    if rp.max_tokens is not None and glen_raw > rp.max_tokens:
        return "length"
    return "stop"


class _RequestStepper:
    """Shared request-level surface of both engines: id/param validation at
    enqueue time, and the ``stream()``/``generate()`` drains over the
    engine-specific ``step()``."""

    def _register(self, request: GenerationRequest, taken) -> None:
        """Validate and id-assign one request at ``add_request`` time (so a
        bad request fails its own submission, not the shared decode step)."""
        _validate_params(request, self.serve)
        self._next_id = normalize_requests([request], self._next_id,
                                           taken=taken)
        if len(np.asarray(request.prompt)) != self.spec.prompt_len:
            raise ValueError(
                f"prompt length {len(np.asarray(request.prompt))} != engine "
                f"prompt_len {self.spec.prompt_len}")

    def stream(self, requests: Sequence[GenerationRequest], key=None):
        """Drain ``requests`` through the stepper, yielding a
        :class:`BlockEvent` the moment each block commits."""
        if not requests:
            return
        if self.has_unfinished():
            raise RuntimeError("engine busy: drain or abort in-flight "
                               "requests before a fresh stream()/generate()")
        _validate_requests(requests)
        self._reset(key)
        ids = [self.add_request(r) for r in requests]
        try:
            while self.has_unfinished():
                yield from self.step()
        finally:
            # early exit (break / generator GC): drop this call's
            # leftovers so the engine isn't wedged "busy" forever
            # (abort of already-completed ids is a no-op)
            if self.has_unfinished():
                for rid in ids:
                    self.abort(rid)

    def generate(self, requests: Sequence[GenerationRequest],
                 key=None) -> List[GenerationOutput]:
        """Thin drain-the-stepper wrapper returning the final outputs in
        completion order; bit-identical to the historical batch API."""
        return [ev.output for ev in self.stream(requests, key=key)
                if ev.finished]


class _Flight:
    """Host-side record of one in-flight request (continuous engine).
    ``arrival`` is the request's effective arrival offset: its trace
    ``arrival_s`` or, in incremental use, when ``add_request`` was called
    — so latency/queueing report arrival → completion, not engine-boot →
    completion."""
    __slots__ = ("req", "rp", "admit_t", "arrival", "blocks_done")

    def __init__(self, req: GenerationRequest, rp: ResolvedSamplingParams,
                 admit_t: float, arrival: float):
        self.req = req
        self.rp = rp
        self.admit_t = admit_t
        self.arrival = arrival
        self.blocks_done = 0


class Engine(_RequestStepper):
    """Static fixed-shape batching over any sampler strategy.

    The incremental API steps at *batch* granularity: ``step()`` pops up
    to ``max_batch`` queued requests, runs the jitted sampler to
    completion, and emits every block event of the batch at once.
    ``generate()`` drains the stepper and is bit-identical to the
    historical batch-synchronous behavior.
    """

    def __init__(self, params, cfg: ModelConfig, serve: ServeConfig,
                 prompt_len: int, *, pos_offset: int = 0,
                 use_long_window: bool = False):
        if serve.page_pool_pages is not None:
            raise ValueError(
                "page_pool_pages is only honored by the continuous "
                "scheduler with the paged layout; the static engine runs "
                "whole sequences to completion, so its paged pool is "
                "always sized dense-equivalent (batch x full canvas)")
        self.params = params
        self.cfg = cfg
        self.serve = serve
        self.spec = SamplerSpec(
            prompt_len=prompt_len, gen_len=serve.gen_length,
            block_size=serve.block_size, conf_threshold=serve.conf_threshold,
            temperature=serve.temperature,
            cache_refresh_interval=serve.cache_refresh_interval,
            pos_offset=pos_offset, cache_layout=serve.cache_layout,
            fused_select=serve.fused_select)
        self._use_long_window = use_long_window
        sampler = SAMPLERS[serve.sampler]
        kwargs = {}
        if serve.sampler == "cdlm" and use_long_window:
            kwargs["use_long_window"] = True

        def run(params, prompts, key, extras):
            return sampler(params, prompts, cfg=cfg, spec=self.spec, key=key,
                           extras=extras, **kwargs)

        self._run = jax.jit(run)
        self._lanes_jit: Dict[bool, Any] = {}
        self._warm = False
        self._next_id = 0
        self._reset()

    # -- incremental core ---------------------------------------------------
    def _reset(self, key=None) -> None:
        self._key = key if key is not None else jax.random.PRNGKey(0)
        self._queue: List[GenerationRequest] = []

    def add_request(self, request: GenerationRequest) -> int:
        """Enqueue one request; returns its (possibly engine-assigned) id."""
        self._register(request, {r.id for r in self._queue})
        self._queue.append(request)
        return request.id

    def has_unfinished(self) -> bool:
        return bool(self._queue)

    def abort(self, request_id: int) -> bool:
        """Drop a queued request (static batches run synchronously, so
        nothing is ever mid-flight between ``step()`` calls)."""
        for i, r in enumerate(self._queue):
            if r.id == request_id:
                del self._queue[i]
                return True
        return False

    def warmup(self, extras=None, *, per_request: bool = False):
        """Compile the scalar decode path; ``per_request=True`` (servers)
        also precompiles the per-lane-params variants so the first request
        carrying explicit :class:`SamplingParams` doesn't stall the
        serving loop on a jit compile."""
        b = self.serve.max_batch
        prompts = jnp.zeros((b, self.spec.prompt_len), jnp.int32)
        self._run(self.params, prompts, jax.random.PRNGKey(0),
                  extras or {}).tokens.block_until_ready()
        if (per_request
                and STRATEGIES[self.serve.sampler].finalize == "threshold"):
            lanes = LaneParams(
                temperature=jnp.zeros((b,), jnp.float32),
                conf_threshold=jnp.full((b,), self.serve.conf_threshold,
                                        jnp.float32),
                eos_id=jnp.full((b,), self.cfg.eos_token_id, jnp.int32),
                key=jnp.zeros((b, 2), jnp.uint32))
            self._lanes_runner(False)(
                self.params, prompts, lanes, extras or {}
            ).tokens.block_until_ready()
            if not self.serve.fused_select:  # sampled+fused is rejected
                self._lanes_runner(True)(
                    self.params, prompts, lanes, extras or {}
                ).tokens.block_until_ready()
        self._warm = True

    def _lanes_runner(self, sampled: bool):
        """Jitted per-lane-params variant of the sampler (two
        specializations: all-greedy lanes, and lanes that draw)."""
        if sampled not in self._lanes_jit:
            # only reachable for threshold-finalize samplers:
            # _validate_params rejects per-request params on others at
            # add_request time
            strategy = STRATEGIES[self.serve.sampler]

            def run(params, prompts, lanes, extras):
                return run_block_loop(
                    params, prompts, cfg=self.cfg, spec=self.spec,
                    strategy=strategy, extras=extras,
                    use_long_window=self._use_long_window,
                    lane_params=lanes, lane_sampled=sampled)

            self._lanes_jit[sampled] = jax.jit(run)
        return self._lanes_jit[sampled]

    def _n_emit_blocks(self, gen: np.ndarray,
                       rp: ResolvedSamplingParams) -> int:
        """Blocks to stream: through the first block containing the
        request's EOS (later blocks were early-stopped to [MASK] or are
        post-EOS filler), else up to the request's ``max_tokens`` cap
        (rounded up to a block), else the whole grid."""
        B = self.spec.block_size
        cap = self.spec.n_blocks
        if rp.max_tokens is not None:
            cap = max(1, min(cap, -(-rp.max_tokens // B)))
        hits = np.flatnonzero(gen == rp.eos_token_id)
        if hits.size:
            return min(int(hits[0]) // B + 1, cap)
        return cap

    def step(self) -> List[BlockEvent]:
        """Run one batch of up to ``max_batch`` queued requests to
        completion; returns every block event of the batch (final events
        carry the :class:`GenerationOutput`)."""
        if not self._queue:
            return []
        Bmax = self.serve.max_batch
        chunk = self._queue[:Bmax]
        _validate_requests(chunk)  # before consuming: a mismatched-extras
        del self._queue[:Bmax]     # chunk must not silently vanish
        rps = [_resolve(r, self.serve, self.cfg) for r in chunk]
        pad = Bmax - len(chunk)
        prompts = np.stack([np.asarray(r.prompt) for r in chunk]
                           + [np.asarray(chunk[-1].prompt)] * pad)
        extras = {}
        if chunk[0].extras:
            for k in chunk[0].extras:
                arrs = ([r.extras[k] for r in chunk]
                        + [chunk[-1].extras[k]] * pad)
                extras[k] = jnp.asarray(np.stack(arrs))
        self._key, sub = jax.random.split(self._key)
        # a chunk is one jit call, so any request with explicit params
        # moves the WHOLE chunk to the per-lane path. At temperature 0 the
        # two paths select identically; on a sampled-default engine
        # (ServeConfig.temperature > 0) this swaps bare chunk-mates from
        # the historical shared batch RNG stream to their own per-request
        # streams (PRNGKey(seed or id)) — batch-composition-independent,
        # but different draws than an all-bare chunk.
        use_lanes = any(r.params is not None
                        and not r.params.is_engine_default for r in chunk)
        t0 = time.perf_counter()
        if use_lanes:
            prps = rps + [rps[-1]] * pad
            lanes = LaneParams(
                temperature=jnp.asarray([p.temperature for p in prps],
                                        jnp.float32),
                conf_threshold=jnp.asarray([p.conf_threshold for p in prps],
                                           jnp.float32),
                eos_id=jnp.asarray([p.eos_token_id for p in prps],
                                   jnp.int32),
                key=jnp.asarray(np.stack([_lane_key(p) for p in prps])))
            sampled = any(p.temperature > 0 for p in prps)
            res = self._lanes_runner(sampled)(
                self.params, jnp.asarray(prompts), lanes, extras)
        else:
            res = self._run(self.params, jnp.asarray(prompts), sub, extras)
        res.tokens.block_until_ready()
        dt = (time.perf_counter() - t0) / len(chunk)
        toks = np.asarray(res.tokens)
        steps = np.asarray(res.steps)
        glens = np.asarray(res.gen_lengths)
        P, B = self.spec.prompt_len, self.spec.block_size
        events: List[BlockEvent] = []
        for j, (r, rp) in enumerate(zip(chunk, rps)):
            gen = toks[j, P:]
            glen_raw = int(glens[j])
            # reason is judged on the untrimmed span (same rule as the
            # continuous engine: EOS landing exactly on the cap is "stop")
            reason = _finish_reason(gen, glen_raw, rp)
            glen = glen_raw
            if rp.max_tokens is not None:
                glen = min(glen, rp.max_tokens)
                gen = gen[:rp.max_tokens]
            out = GenerationOutput(
                id=r.id, tokens=gen, gen_length=glen, steps=int(steps[j]),
                latency_s=dt, finish_reason=reason)
            n_blocks = self._n_emit_blocks(gen, rp)
            for blk in range(n_blocks):
                events.append(BlockEvent(
                    request_id=r.id, index=blk, start=blk * B,
                    tokens=toks[j, P + blk * B:P + (blk + 1) * B].copy(),
                    finished=(blk == n_blocks - 1),
                    output=out if blk == n_blocks - 1 else None))
        return events


# ---------------------------------------------------------------------------
# Continuous block-level batching
# ---------------------------------------------------------------------------
class _SlotState(NamedTuple):
    tokens: jnp.ndarray       # (N, P+G) canvases
    cache: Any                # batch KV cache, lanes on axis 1
    blk: jnp.ndarray          # (N,) int32 — each lane's current block index
    lane_nblocks: jnp.ndarray  # (N,) int32 — blocks this request decodes
    live: jnp.ndarray         # (N,) bool — lane occupied and unfinished
    steps: jnp.ndarray        # (N,) int32 refinement iterations
    calls: jnp.ndarray        # () int32 total forward passes
    temps: jnp.ndarray        # (N,) float32 per-lane temperature
    taus: jnp.ndarray         # (N,) float32 per-lane conf threshold
    eos: jnp.ndarray          # (N,) int32 per-lane EOS token
    keys: jnp.ndarray         # (N, 2) uint32 per-lane PRNG keys


class ContinuousEngine(_RequestStepper):
    """Slot-based continuous batching over the CDLM exact-cache strategy.

    Scheduling happens at block boundaries: each jitted ``_decode_block``
    call advances every live lane by one block (threshold refinement +
    commit pass); between calls the host evicts finished lanes and admits
    arrived requests into the freed slots — that boundary is exactly one
    ``step()`` of the incremental API, and the blocks finalized by it are
    the returned :class:`BlockEvent` stream. Only the ``cdlm`` strategy is
    supported — approximate-cache strategies refresh KV from the *whole*
    canvas, which couples lanes to batch-global state, and only the exact
    block-causal cache makes per-lane recycling loss-free.

    Per-request sampling: each lane carries its own temperature, τ, EOS
    and PRNG key (``_SlotState.temps/taus/eos/keys``). Greedy and sampled
    lanes mix freely; a sampled lane's key advances only on its own active
    refinement iterations, so its draws are independent of batch
    composition and bit-identical to its isolated decode.
    """

    def __init__(self, params, cfg: ModelConfig, serve: ServeConfig,
                 prompt_len: int, *, use_long_window: bool = False,
                 use_paged_kernel: bool = False):
        if serve.sampler != "cdlm":
            raise ValueError(
                "ContinuousEngine requires the 'cdlm' strategy (exact "
                f"block-causal cache); got sampler={serve.sampler!r}")
        if use_paged_kernel and serve.cache_layout != C.PAGED:
            raise ValueError("use_paged_kernel requires cache_layout='paged'")
        if cfg.is_encoder_decoder:
            raise ValueError("ContinuousEngine does not support "
                             "encoder-decoder models yet (per-lane encoder "
                             "state is not scheduled)")
        if serve.cache_layout not in C.CACHE_LAYOUTS:
            raise ValueError(f"unknown cache layout {serve.cache_layout!r} "
                             f"(expected one of {C.CACHE_LAYOUTS})")
        if (serve.cache_layout != C.PAGED
                and serve.page_pool_pages is not None):
            raise ValueError("page_pool_pages requires cache_layout='paged' "
                             "— the dense layout preallocates per-lane "
                             "buffers and would silently ignore the budget")
        if serve.fused_select and serve.temperature > 0:
            raise ValueError(
                "fused_select is greedy-only: a sampled default "
                "(temperature > 0) would route every step through the "
                "dense selection path, mixing fused and dense decodes "
                "across batch compositions")
        self.params = params
        self.cfg = cfg
        self.serve = serve
        self.spec = SamplerSpec(
            prompt_len=prompt_len, gen_len=serve.gen_length,
            block_size=serve.block_size, conf_threshold=serve.conf_threshold,
            temperature=serve.temperature, early_stop=True,
            cache_layout=serve.cache_layout, fused_select=serve.fused_select)
        # fused unembed+select decode: lane forwards skip the lm_head and
        # candidates/confidences come from the vocab-tiled selection kernel
        # — no (b, B, V) logits in the refinement loop. Engages only on
        # all-greedy steps; a step with any sampled lane needs logits.
        self._fused = serve.fused_select
        self.n_lanes = serve.max_batch
        self.paged = serve.cache_layout == C.PAGED
        P, B = prompt_len, serve.block_size
        T = prompt_len + serve.gen_length
        if self.paged:
            self._n_tables = -(-T // B)
            self.n_pages = (serve.page_pool_pages
                            if serve.page_pool_pages is not None
                            else self.n_lanes * self._n_tables)
            if self.n_pages < self._n_tables:
                raise ValueError(
                    f"page pool of {self.n_pages} pages cannot back one "
                    f"full request ({self._n_tables} pages of {B} tokens "
                    f"for prompt {P} + gen {serve.gen_length}) — this is "
                    "the deadlock-free minimum")
            # pages a fresh request needs at admission: prompt + first block
            self._admit_pages = C.pages_for_span(0, P + B, B)
        else:
            self.n_pages = 0
        self._use_long_window = use_long_window
        # opt-in Pallas flash-decode over the page table (TPU hot path;
        # interpret-mode on CPU — numerically equal to the gather path to
        # fp32 tolerance, not bit-equal, since reduction order differs)
        self._paged_attention_fn = None
        if use_paged_kernel:
            from repro.kernels.decode_attn import paged_decode_attention
            self._paged_attention_fn = paged_decode_attention
        self._jit_admit = jax.jit(self._admit)
        self._jit_decode_block = jax.jit(self._decode_block,
                                         static_argnames=("sampled",))
        self._jit_evict = jax.jit(self._evict)
        self._jit_alloc_block = jax.jit(self._alloc_block)
        self._jit_gen_lengths = jax.jit(
            lambda tokens, eos: _gen_lengths(tokens, self.spec, self.cfg,
                                             eos_id=eos))
        self._warm = False
        self._next_id = 0
        # work counters and host seconds per phase over the engine's life
        # (``counters()``): fixed keys, written by the stepping thread only
        self.clock = tracing.PhaseClock()
        self._counts = dict.fromkeys(
            ("steps", "admit_calls", "admitted_lanes", "forwards",
             "lane_iters_active", "blocks_emitted"), 0)
        self._reset()

    # -- jitted state transitions -------------------------------------------
    def _init_state(self) -> _SlotState:
        N = self.n_lanes
        T = self.spec.prompt_len + self.spec.gen_len
        if self.paged:
            cache = C.init_paged_cache(
                self.cfg, N, self._n_tables * self.spec.block_size,
                n_pages=self.n_pages, page_size=self.spec.block_size,
                dtype=self.cfg.dtype)
        else:
            cache = C.init_cache(self.cfg, N, T, dtype=self.cfg.dtype)
        return _SlotState(
            tokens=jnp.full((N, T), self.cfg.mask_token_id, jnp.int32),
            cache=cache,
            blk=jnp.zeros((N,), jnp.int32),
            lane_nblocks=jnp.full((N,), self.spec.n_blocks, jnp.int32),
            live=jnp.zeros((N,), bool),
            steps=jnp.zeros((N,), jnp.int32),
            calls=jnp.zeros((), jnp.int32),
            temps=jnp.zeros((N,), jnp.float32),
            taus=jnp.full((N,), self.spec.conf_threshold, jnp.float32),
            eos=jnp.full((N,), self.cfg.eos_token_id, jnp.int32),
            keys=jnp.zeros((N, 2), jnp.uint32))

    def _admit(self, params, state: _SlotState, prompts, admit, nblocks,
               temps, taus, eos, keys):
        """Admit requests into freed lanes: write canvases and per-lane
        sampling params, reset cache rows (paged: allocate prompt +
        first-block pages), prefill prompts under the block-causal mask,
        commit into those rows.

        Returns ``(state, ok)`` — ``ok`` is the admitted-lane mask that got
        its pages (always the admit mask itself for the dense layout; the
        host only admits within the free-page budget, so a False is a
        scheduler bug and is asserted on the host side)."""
        spec, cfg = self.spec, self.cfg
        canvas = init_canvas(prompts, spec, cfg)
        tokens = jnp.where(admit[:, None], canvas, state.tokens)
        cache = C.reset(state.cache, admit)
        ok = admit
        if self.paged:
            cache, ok = C.alloc(cache, admit, 0,
                                spec.prompt_len + spec.block_size)
        with jax.named_scope(tracing.PREFILL):
            out = forward(params, tokens[:, :spec.prompt_len], cfg=cfg,
                          mode=masks.BLOCK_CAUSAL,
                          prompt_len=spec.full_prompt_len,
                          block_size=spec.block_size,
                          attn_impl=spec.attn_impl, return_logits=False)
            cache = C.commit_rows(cache, out.emissions, 0, admit)
        return state._replace(
            tokens=tokens, cache=cache,
            blk=jnp.where(admit, 0, state.blk),
            lane_nblocks=jnp.where(admit, nblocks, state.lane_nblocks),
            live=state.live | admit,
            steps=jnp.where(admit, 0, state.steps),
            calls=state.calls + 1,
            temps=jnp.where(admit, temps, state.temps),
            taus=jnp.where(admit, taus, state.taus),
            eos=jnp.where(admit, eos, state.eos),
            keys=jnp.where(admit[:, None], keys, state.keys)), ok

    def _evict(self, state: _SlotState, rows) -> _SlotState:
        """Release lanes: mark dead and reset their cache (paged: return
        their pages to the pool)."""
        return state._replace(cache=C.reset(state.cache, rows),
                              live=state.live & ~rows)

    def _alloc_block(self, state: _SlotState):
        """Paged: ensure every live lane has pages for its current block.
        Returns ``(state, ok)``; a live lane with ``ok=False`` stalls this
        round (its table is untouched — all-or-nothing per lane)."""
        spec = self.spec
        P, B = spec.prompt_len, spec.block_size
        starts = P + jnp.clip(state.blk, 0, spec.n_blocks - 1) * B
        cache, ok = C.alloc(state.cache, state.live, starts, starts + B)
        return state._replace(cache=cache), ok

    def _decode_block(self, params, state: _SlotState, run, *,
                      sampled: bool) -> _SlotState:
        """Advance lanes selected by ``run`` by one block: threshold
        refinement to completion, then the exact commit pass into each
        lane's cache rows. Live lanes outside ``run`` (page-stalled) are
        left untouched and retry at the next boundary.

        ``sampled`` (static) is True when any lane in the batch draws
        categorically: the refinement forwards then carry logits and
        per-lane keys advance for active lanes. All-greedy steps keep the
        (optionally fused, lm_head-free) greedy path bit-for-bit."""
        spec, cfg = self.spec, self.cfg
        P, B = spec.prompt_len, spec.block_size
        live = state.live & run
        starts = P + jnp.clip(state.blk, 0, spec.n_blocks - 1) * B
        fused = self._fused and not sampled

        def slice_blocks(tokens):
            return jax.vmap(
                lambda t, s: jax.lax.dynamic_slice(t, (s,), (B,)))(
                    tokens, starts)

        def scatter_blocks(tokens, blocks):
            return jax.vmap(
                lambda t, b, s: jax.lax.dynamic_update_slice(t, b, (s,)))(
                    tokens, blocks, starts)

        all_block = jnp.ones((1, B), bool)

        def cond(st):
            tokens, steps, calls, keys, it = st
            bt = slice_blocks(tokens)
            act = jnp.any(bt == cfg.mask_token_id, axis=-1) & live
            return jnp.any(act) & (it < B)

        @jax.named_scope(tracing.REFINE)
        def body(st):
            tokens, steps, calls, keys, it = st
            bt = slice_blocks(tokens)
            active = jnp.any(bt == cfg.mask_token_id, axis=-1) & live
            if sampled:
                keys, subs = D.split_lane_keys(keys, active)
            net, _ = lane_block_forward(
                params, tokens, starts, state.cache, cfg=cfg, spec=spec,
                use_long_window=self._use_long_window,
                paged_attention_fn=self._paged_attention_fn,
                return_hidden=fused)
            with jax.named_scope(tracing.SELECT):
                if fused:
                    cand, conf = D.confidence_and_candidates_fused(
                        net, unembed_matrix(params, cfg), bt,
                        cfg.mask_token_id, 0.0, None,
                        softcap=cfg.final_logit_softcap)
                elif sampled:
                    cand, conf = D.confidence_and_candidates_per_lane(
                        net, bt, cfg.mask_token_id, state.temps, subs)
                else:
                    cand, conf = D.confidence_and_candidates(
                        net, bt, cfg.mask_token_id, 0.0, None)
                sel = D.select_threshold_in_block(conf, all_block,
                                                  state.taus[:, None])
            sel = sel & active[:, None]
            bt = jnp.where(sel, cand.astype(bt.dtype), bt)
            return (scatter_blocks(tokens, bt),
                    steps + active.astype(jnp.int32), calls + 1, keys, it + 1)

        tokens, steps, calls, keys, _ = jax.lax.while_loop(
            cond, body,
            (state.tokens, state.steps, state.calls, state.keys,
             jnp.zeros((), jnp.int32)))

        # commit pass: recompute the finalized blocks' KV exactly, only for
        # the lanes that ran, each at its own offset (only emissions are
        # consumed, so the lm_head is always skipped here)
        with jax.named_scope(tracing.COMMIT):
            _, emissions = lane_block_forward(
                params, tokens, starts, state.cache, cfg=cfg, spec=spec,
                use_long_window=self._use_long_window,
                paged_attention_fn=self._paged_attention_fn,
                return_hidden=True)
            cache = C.commit_rows(state.cache, emissions, starts, live)
        calls = calls + 1

        bt = slice_blocks(tokens)
        eos_hit = jnp.any(bt == state.eos[:, None], axis=-1)
        blk = jnp.where(live, state.blk + 1, state.blk)
        finished = live & (eos_hit | (blk >= state.lane_nblocks))
        return state._replace(tokens=tokens, cache=cache, blk=blk,
                              live=state.live & ~finished, steps=steps,
                              calls=calls, keys=keys)

    # -- host-side scheduler -------------------------------------------------
    def _reset(self, key=None) -> None:
        del key  # per-request RNG streams derive from SamplingParams.seed
        self._state = self._init_state()
        self._queue: List[GenerationRequest] = []
        self._flights: List[Optional[_Flight]] = [None] * self.n_lanes
        self._resolved: Dict[int, ResolvedSamplingParams] = {}
        # effective arrival offset per request id (trace arrival_s, or the
        # add_request() wall-clock offset in incremental/server use)
        self._arrival: Dict[int, float] = {}
        # blocks already streamed per request id: a preempted request
        # re-decodes from scratch (bit-identically), but its re-decoded
        # blocks must not be re-emitted to stream consumers
        self._emitted: Dict[int, int] = {}
        self._t0 = time.perf_counter()
        # pages in use and lanes run, sampled at every decode step: running
        # peak, sum and count
        self._pool = [0, 0, 0]
        self._lanes = [0, 0, 0]
        self._preemptions = 0
        self._stall_rounds = 0
        # host mirror of the device page allocator (paged layout): per-lane
        # allocated table-slot high-water mark and the pool's free count.
        # cache.alloc is deterministic (lane-index order, all-or-nothing,
        # lowest-index-first pages) and every span this engine allocates is
        # a contiguous slot prefix, so (hi, free) reproduce its decisions
        # exactly — step() never reads an allocation result off the device.
        self._host_hi = np.zeros((self.n_lanes,), np.int64)
        self._host_blk = np.zeros((self.n_lanes,), np.int64)
        self._host_free = self.n_pages
        # the device's forward count and per-lane iteration counts as last
        # read, for the counters' increments
        self._calls_seen = 0
        self._steps_seen = np.zeros((self.n_lanes,), np.int64)

    # -- host page-accounting mirror (paged layout) --------------------------
    def _host_target_hi(self, blk: int) -> int:
        """Table slots a lane must hold through block ``blk``:
        ceil(P/B) prompt slots + blk+1 block slots (spans are contiguous
        slot prefixes, so this is the whole allocation state)."""
        P, B = self.spec.prompt_len, self.spec.block_size
        return -(-P // B) + min(int(blk), self.spec.n_blocks - 1) + 1

    def _host_alloc(self, rows: np.ndarray) -> np.ndarray:
        """Mirror ``cache.alloc`` for ``rows``'s current blocks: lane-index
        order, all-or-nothing per lane. Returns the per-lane ok mask and
        commits successful lanes to the mirror."""
        ok = np.zeros((self.n_lanes,), bool)
        for i in range(self.n_lanes):
            if not rows[i]:
                continue
            t = self._host_target_hi(self._host_blk[i])
            need = max(0, t - int(self._host_hi[i]))
            if need <= self._host_free:
                self._host_free -= need
                self._host_hi[i] = max(int(self._host_hi[i]), t)
                ok[i] = True
        return ok

    def _host_evict(self, rows: np.ndarray) -> None:
        """Mirror ``cache.reset``: a lane's pages all return to the pool."""
        for i in np.flatnonzero(rows):
            self._host_free += int(self._host_hi[i])
            self._host_hi[i] = 0

    def page_accounting(self):
        """(host_free, device_free) — equal by construction; the device
        read exists for tests/debugging only (it synchronizes)."""
        dev = int(np.asarray(C.free_page_count(self._state.cache)))
        return self._host_free, dev

    def warmup(self, extras=None, *, per_request: bool = False):
        """Compile the admit/decode/evict paths; ``per_request=True``
        (servers) also precompiles the sampled decode variant — see
        :meth:`Engine.warmup`."""
        if extras:
            raise ValueError("ContinuousEngine does not support request "
                             "extras (encoder/prefix embeds) yet")
        state = self._init_state()
        N, P = self.n_lanes, self.spec.prompt_len
        state, _ = self._jit_admit(
            self.params, state, jnp.zeros((N, P), jnp.int32),
            jnp.ones((N,), bool),
            jnp.full((N,), self.spec.n_blocks, jnp.int32),
            state.temps, state.taus, state.eos, state.keys)
        run = jnp.ones((N,), bool)
        if self.paged:
            state, ok = self._jit_alloc_block(state)
            run = state.live & ok
            state = self._jit_evict(state, jnp.zeros((N,), bool))
        state = self._jit_decode_block(self.params, state, run,
                                       sampled=False)
        if (self.serve.temperature > 0
                or (per_request and not self._fused)):
            # precompile the sampled decode variant: the engine default
            # makes every lane sampled, or (servers) any request may carry
            # temperature > 0 and compiling lazily would stall the serving
            # loop on the first sampled request
            self._jit_decode_block(self.params, state, run, sampled=True)
        self._jit_gen_lengths(state.tokens, state.eos).block_until_ready()
        self._warm = True

    def _lane_nblocks(self, rp: ResolvedSamplingParams) -> int:
        B = self.spec.block_size
        if rp.max_tokens is None:
            return self.spec.n_blocks
        return max(1, min(self.spec.n_blocks, -(-rp.max_tokens // B)))

    # -- incremental core ---------------------------------------------------
    def add_request(self, request: GenerationRequest) -> int:
        """Enqueue one request (admitted at the next block boundary with a
        free lane / enough free pages); returns its unique id."""
        if request.extras:
            raise ValueError("ContinuousEngine does not support request "
                             "extras (encoder/prefix embeds) yet")
        self._register(request,
                       {r.id for r in self._queue}
                       | {f.req.id for f in self._flights if f is not None})
        self._resolved[request.id] = _resolve(request, self.serve, self.cfg)
        self._arrival[request.id] = max(request.arrival_s,
                                        time.perf_counter() - self._t0)
        # stable arrival-order insertion (insort keeps FIFO among equal
        # arrival_s); requeued preemption victims sit at the front by
        # construction (direct insert(0) in step())
        bisect.insort(self._queue, request, key=lambda r: r.arrival_s)
        return request.id

    def has_unfinished(self) -> bool:
        return bool(self._queue) or any(f is not None for f in self._flights)

    def abort(self, request_id: int) -> bool:
        """Drop a queued or in-flight request. An in-flight lane is evicted
        at once — its cache rows reset and (paged) its pages returned to
        the pool — without touching any other lane."""
        for i, r in enumerate(self._queue):
            if r.id == request_id:
                del self._queue[i]
                self._resolved.pop(request_id, None)
                self._emitted.pop(request_id, None)
                self._arrival.pop(request_id, None)
                return True
        for lane, fl in enumerate(self._flights):
            if fl is not None and fl.req.id == request_id:
                row = np.zeros((self.n_lanes,), bool)
                row[lane] = True
                self._state = self._jit_evict(self._state, jnp.asarray(row))
                if self.paged:
                    self._host_evict(row)
                self._flights[lane] = None
                self._resolved.pop(request_id, None)
                self._emitted.pop(request_id, None)
                self._arrival.pop(request_id, None)
                return True
        return False

    def _sampled_step(self) -> bool:
        return any(f is not None and f.rp.temperature > 0
                   for f in self._flights)

    def step(self) -> List[BlockEvent]:
        """Advance one block boundary: (paged) back the in-flight lanes'
        current blocks with pages, admit arrived requests into free lanes,
        run one block-level decode for the runnable lanes, (paged) prefetch
        the survivors' *next* blocks, evict finished lanes. Returns one
        :class:`BlockEvent` per block finalized this step (final blocks
        carry the request's :class:`GenerationOutput`).

        The paged path is dispatch-only up to the decode: the run mask and
        the admission budget come from the host page mirror, so no device
        allocation result is ever read back. In-flight lanes allocate
        before admissions (newcomers can't starve a running lane), and most
        boundaries find their pages already claimed by the previous step's
        prefetch. The step's one read of device results is the ``sync``
        phase; each phase is a span of :mod:`repro.serving.tracing`.
        """
        with self.clock.span("step"):
            return self._step()

    def _step(self) -> List[BlockEvent]:
        N, P, B = self.n_lanes, self.spec.prompt_len, self.spec.block_size
        span = self.clock.span
        state = self._state
        now = time.perf_counter() - self._t0

        with span("pages"):
            # ---- paged: back the in-flight lanes' current blocks FIRST ----
            live = np.asarray([f is not None for f in self._flights])
            run = np.zeros((N,), bool)
            if self.paged and live.any():
                run = self._host_alloc(live)
                while not run.any():
                    # every live lane is page-starved: preempt the youngest
                    # (its pages go back to the pool, its request re-enters
                    # the queue — the request's own deterministic RNG
                    # stream makes the re-decode loss-free)
                    victims = [i for i in range(N) if live[i]]
                    victim = max(victims,
                                 key=lambda i: (self._flights[i].admit_t, i))
                    if len(victims) == 1:
                        raise RuntimeError(
                            "page pool exhausted with a single live lane — "
                            "pool sizing invariant violated")
                    vrow = np.zeros((N,), bool)
                    vrow[victim] = True
                    state = self._jit_evict(state, jnp.asarray(vrow))
                    self._host_evict(vrow)
                    self._queue.insert(0, self._flights[victim].req)
                    self._flights[victim] = None
                    self._preemptions += 1
                    live[victim] = False
                    run = self._host_alloc(live)
                # one dispatch, no result read: the device allocator's
                # decisions equal the host plan by construction
                state, _ = self._jit_alloc_block(state)
                if (live & ~run).any():
                    self._stall_rounds += 1
            elif not self.paged:
                run = live.copy()

            # ---- admission at the block boundary ----
            # paged: budgeted by the mirror's free *pages* for prompt + next
            # block, not by whole-sequence reservation — a request enters
            # as soon as its next block can be backed
            admitted: List[int] = []
            for lane in range(N):
                if self._flights[lane] is not None:
                    continue
                if not self._queue or self._queue[0].arrival_s > now:
                    break
                if self.paged and self._host_free < self._admit_pages:
                    break
                req = self._queue.pop(0)
                self._flights[lane] = _Flight(
                    req, self._resolved[req.id], admit_t=now,
                    arrival=self._arrival.get(req.id, req.arrival_s))
                admitted.append(lane)
                if self.paged:
                    self._host_free -= self._admit_pages
                    self._host_hi[lane] = self._admit_pages
                    self._host_blk[lane] = 0

        admit = np.zeros((N,), bool)
        if admitted:
            flights = [self._flights[lane] for lane in admitted]
            with span("admit",
                      ids=tracing.ids(f.req.id for f in flights)):
                prompts = np.zeros((N, P), np.int32)
                nblocks = np.zeros((N,), np.int32)
                temps = np.zeros((N,), np.float32)
                taus = np.zeros((N,), np.float32)
                eos = np.zeros((N,), np.int32)
                keys = np.zeros((N, 2), np.uint32)
                for lane, fl in zip(admitted, flights):
                    admit[lane] = True
                    prompts[lane] = np.asarray(fl.req.prompt)
                    nblocks[lane] = self._lane_nblocks(fl.rp)
                    temps[lane] = fl.rp.temperature
                    taus[lane] = fl.rp.conf_threshold
                    eos[lane] = fl.rp.eos_token_id
                with span("admit.keys"):
                    for lane, fl in zip(admitted, flights):
                        keys[lane] = _lane_key(fl.rp)
                state, _ = self._jit_admit(
                    self.params, state, jnp.asarray(prompts),
                    jnp.asarray(admit), jnp.asarray(nblocks),
                    jnp.asarray(temps), jnp.asarray(taus), jnp.asarray(eos),
                    jnp.asarray(keys))
                run = run | admit
        if all(f is None for f in self._flights):
            # nothing decoding and nothing arrived yet: idle to the next
            # arrival instead of spinning
            self._state = state
            if self._queue:
                with span("sleep"):
                    wait = self._queue[0].arrival_s - (time.perf_counter()
                                                       - self._t0)
                    if wait > 0:
                        time.sleep(wait)
            return []

        # ---- one block-level decode step for the runnable lanes ----
        with span("decode"):
            if self.paged:
                _sample(self._pool, self.n_pages - self._host_free)
            _sample(self._lanes, int(run.sum()))
            self._host_blk[run] += 1
            state = self._jit_decode_block(self.params, state,
                                           jnp.asarray(run),
                                           sampled=self._sampled_step())
            if self.paged:
                # prefetch: claim the surviving lanes' next-block pages
                # while this boundary's results drain — dispatched before
                # any result is read, so the next step's in-flight alloc is
                # a no-op
                state, _ = self._jit_alloc_block(state)
        with span("sync"):
            live, calls, steps_arr = jax.device_get(
                (state.live, state.calls, state.steps))
        self._count(admit, int(calls), steps_arr)
        if self.paged:
            with span("pages"):
                self._host_alloc(live)
        t_done = time.perf_counter() - self._t0

        # ---- block events + eviction of finished lanes ----
        ran = [i for i in range(N)
               if run[i] and self._flights[i] is not None]
        done_lanes = [i for i in ran if not live[i]]
        with span("emit", ids=tracing.ids(self._flights[i].req.id
                                          for i in done_lanes)):
            events = self._emit(state, ran, live, steps_arr, t_done,
                                bool(done_lanes))
        if done_lanes and self.paged:
            with span("evict"):
                # return the finished lanes' pages to the pool *now* so the
                # next admission sees them
                drow = np.zeros((N,), bool)
                drow[done_lanes] = True
                state = self._jit_evict(state, jnp.asarray(drow))
                self._host_evict(drow)
        self._state = state
        return events

    def _count(self, admit: np.ndarray, calls: int,
               steps: np.ndarray) -> None:
        """Add one decode step's work to the counters, from the device's
        forward count and per-lane iteration counts read at the sync
        (admitted lanes restart theirs from 0)."""
        c = self._counts
        c["steps"] += 1
        if admit.any():
            c["admit_calls"] += 1
            c["admitted_lanes"] += int(admit.sum())
            self._steps_seen[admit] = 0
        # the device's count is int32 and wraps
        c["forwards"] += (calls - self._calls_seen) % (1 << 32)
        self._calls_seen = calls
        steps = steps.astype(np.int64)
        c["lane_iters_active"] += int((steps - self._steps_seen).sum())
        self._steps_seen = steps

    def _emit(self, state: _SlotState, ran: List[int], live: np.ndarray,
              steps_arr: np.ndarray, t_done: float,
              finished: bool) -> List[BlockEvent]:
        """Block events of the lanes that ran; a finished lane's final
        event carries its :class:`GenerationOutput` and frees its lane."""
        P, B = self.spec.prompt_len, self.spec.block_size
        toks = glens = None
        if finished:
            # full-canvas transfer only when a request completed (the
            # legacy cadence); in-flight boundaries move one block per
            # ran lane below
            toks = np.asarray(state.tokens)
            glens = np.asarray(self._jit_gen_lengths(state.tokens,
                                                     state.eos))
        events: List[BlockEvent] = []
        for lane in ran:
            fl = self._flights[lane]
            blk = fl.blocks_done
            fl.blocks_done += 1
            if live[lane] and blk < self._emitted.get(fl.req.id, 0):
                continue  # preemption re-decode: block already streamed
            self._emitted[fl.req.id] = blk + 1
            lo, hi = P + blk * B, P + (blk + 1) * B
            block_toks = (toks[lane, lo:hi].copy() if toks is not None
                          else np.asarray(state.tokens[lane, lo:hi]))
            ev = BlockEvent(
                request_id=fl.req.id, index=blk, start=blk * B,
                tokens=block_toks, finished=not live[lane])
            if ev.finished:
                gen = toks[lane, P:].copy()
                glen_raw = int(glens[lane])
                # reason judged on the untrimmed span; the returned span
                # is sliced to the cap (same contract as the static
                # engine — no [MASK] filler past max_tokens)
                reason = _finish_reason(gen, glen_raw, fl.rp)
                glen = glen_raw
                if fl.rp.max_tokens is not None:
                    glen = min(glen, fl.rp.max_tokens)
                    gen = gen[:fl.rp.max_tokens]
                ev.output = GenerationOutput(
                    id=fl.req.id, tokens=gen, gen_length=glen,
                    steps=int(steps_arr[lane]),
                    latency_s=t_done - fl.arrival,
                    queue_s=fl.admit_t - fl.arrival,
                    finish_reason=reason)
                self._flights[lane] = None
                self._resolved.pop(fl.req.id, None)
                self._emitted.pop(fl.req.id, None)
                self._arrival.pop(fl.req.id, None)
            events.append(ev)
        self._counts["blocks_emitted"] += len(events)
        return events

    def counters(self) -> Dict[str, Any]:
        """Work and host time since construction, cumulative (a reset for a
        fresh ``stream()`` does not clear them), read lock-free as
        :meth:`page_pool_stats` is:

        - ``steps``: decode steps; ``admit_calls``, ``admitted_lanes``:
          admission prefills and the lanes they admitted;
        - ``forwards``: model forwards the device ran (its own count, read
          at each step's sync), of which ``refine_forwards`` are the
          refinement loop's: one per admission and one commit per step are
          the rest;
        - ``lane_iters_active``: refinement iterations summed over lanes
          that still had a masked position, so ``lane_iters_active /
          (refine_forwards * max_batch)`` is the share of lane-iterations
          that did work;
        - ``blocks_emitted``: block events returned;
        - ``host_s``: host seconds per phase of
          :data:`repro.serving.tracing.SPANS` (nested phases are also
          inside their parent's time; ``driver.*`` is filled by an
          ``EngineDriver`` over this engine).
        """
        c: Dict[str, Any] = dict(self._counts)
        c["refine_forwards"] = (c["forwards"] - c["admit_calls"]
                                - c["steps"])
        c["host_s"] = dict(self.clock.host_s)
        return c

    def page_pool_stats(self) -> Dict[str, float]:
        """Occupancy report since the last reset (paged layout; zeros for
        dense). Pages are sampled at every block boundary."""
        peak, total, n = self._pool
        if not self.paged or not n:
            return {"n_pages": float(self.n_pages), "peak_pages": 0.0,
                    "avg_pages": 0.0, "peak_occupancy": 0.0,
                    "preemptions": 0.0, "stall_rounds": 0.0}
        return {
            "n_pages": float(self.n_pages),
            "peak_pages": float(peak),
            "avg_pages": total / n,
            "peak_occupancy": peak / self.n_pages,
            "preemptions": float(self._preemptions),
            "stall_rounds": float(self._stall_rounds),
        }

    def concurrency_stats(self) -> Dict[str, float]:
        """Decoding-lane concurrency since the last reset, sampled at every
        block-level decode step (both layouts)."""
        peak, total, n = self._lanes
        if not n:
            return {"peak_lanes": 0.0, "avg_lanes": 0.0}
        return {"peak_lanes": float(peak), "avg_lanes": total / n}


def _sample(acc: list, value: int) -> None:
    """Fold one sample into a running ``[peak, sum, count]``."""
    acc[0] = max(acc[0], value)
    acc[1] += value
    acc[2] += 1


def make_engine(params, cfg: ModelConfig, serve: ServeConfig,
                prompt_len: int, **kw):
    """Engine factory switched by ``serve.scheduler``."""
    if serve.scheduler == "continuous":
        if kw.pop("pos_offset", 0):
            raise ValueError("ContinuousEngine does not support prefix "
                             "embeds (pos_offset != 0) yet")
        return ContinuousEngine(params, cfg, serve, prompt_len, **kw)
    if serve.scheduler == "static":
        return Engine(params, cfg, serve, prompt_len, **kw)
    raise ValueError(f"unknown scheduler {serve.scheduler!r} "
                     "(expected 'static' or 'continuous')")


def efficiency_report(responses: Sequence[GenerationOutput]) -> Dict[str, float]:
    """Per-sample averages, the paper's reporting convention (App. A.3)."""
    if not responses:
        return {"latency_s": 0.0, "steps": 0.0, "gen_length": 0.0, "tps": 0.0}
    lat = float(np.mean([r.latency_s for r in responses]))
    steps = float(np.mean([r.steps for r in responses]))
    glen = float(np.mean([r.gen_length for r in responses]))
    tps = glen / lat if lat > 0 else float("inf")
    return {"latency_s": lat, "steps": steps, "gen_length": glen, "tps": tps}
