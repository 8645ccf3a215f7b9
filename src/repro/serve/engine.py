"""Production serving engine: paged KV cache + ragged continuous batching,
with structured failure semantics.

The reference :class:`~repro.serve.server.Server` prefills one request at
a time into a dense per-slot cache and decodes the whole batch in one
loop.  This engine is the production shape of the same loop:

- **Paged KV cache** -- one physical pool per attention layer
  (``LM.init_paged_cache``), fixed-size blocks handed out by a
  :class:`~repro.serve.paged.BlockAllocator`, per-sequence block tables,
  attention reads either gathered or streamed block-by-block by the
  fused square kernel (``attention._attn_paged_step`` routes per shape
  via ``kernels.routing``).  Blocks are allocated on admit, grown on
  demand during decode, and freed the moment a sequence finishes --
  memory scales with live tokens, not with ``max_slots * max_len``.
  Sliding-window archs additionally retire blocks as their positions age
  out of the window (``EngineConfig.window_eviction``), capping each
  sequence's footprint at ``ceil(window / block_size) + 1`` blocks
  however long it runs.
- **Continuous batching with per-slot ragged positions** -- every decode
  step advances all live slots at their own absolute offsets (one (B, 1)
  call); a finished slot is refilled from the queue without draining the
  batch.
- **Chunked prefill admission** -- prompts are processed in
  ``prefill_chunk``-token chunks interleaved with decode steps (one chunk
  per engine step), so a long prompt never stalls in-flight decodes.
- **Prepared-weight decode path** -- ``prepared=True`` runs
  ``LM.prepare_params`` ONCE at engine start and serves every decode /
  prefill GEMM from the weight-stationary prepared operands (paper
  §4-§5: the regime where a weight loaded once streams against many
  activations is exactly LLM decode).

Resilience contract (the part PR 5 lacked)
------------------------------------------
Nothing a single request does -- an oversize prompt, a deadline it
cannot meet, a poisoned logits row, repeated preemption, even a failing
model step -- may kill the batch.  Every submitted request ends in
exactly one **terminal status** (:class:`RequestStatus`), returned as a
:class:`RequestResult` from :meth:`Engine.run` / drained from
:meth:`Engine.drain_finished` after :meth:`Engine.step`:

``COMPLETED``    finished normally (EOS or ``max_new_tokens``);
``REJECTED``     refused at ``submit`` (invalid geometry, or shed by the
                 bounded admission queue's load-shed policy);
``TIMED_OUT``    its deadline or the run's wall budget expired (partial
                 tokens are returned);
``FAILED``       a fault the engine absorbed on its behalf: preemption
                 budget exhausted, persistent step failures, non-finite
                 logits (numerics guard), or the no-progress watchdog;
``CANCELLED``    :meth:`Engine.cancel` was called on it.

Mechanisms: per-request **deadlines** (``EngineConfig.deadline_s`` /
``Request.deadline_s``) and a per-run wall budget (``max_wall_s``); a
**bounded admission queue** (``queue_limit``) with an explicit shed
policy (``reject-new`` | ``evict-oldest``); a **preemption budget**
(``max_preemptions``) so two long requests can never thrash each other
forever; bounded **step retries** (``max_step_retries`` -- the model
calls are functional, so a failed call mutated nothing and retrying is
token-exact); a **no-progress watchdog** (``watchdog_steps``) that
converts a stuck scheduler into surfaced errors; and an engine-level
**numerics guard** (``guard=True``) that fails a slot whose logits go
non-finite instead of serving garbage argmax tokens (the core-layer
guard -- square-route demotion -- lives in :mod:`repro.core.guards` /
:mod:`repro.kernels.routing` and is scoped over every step when
``guard=True``; with ``jit=True`` the traces additionally carry
host-callback finite probes, drained after every model call with
demote + re-jit + token-exact retry -- see :meth:`Engine._guarded_call`
and docs/robustness.md).  Terminal paths all release their slot's
blocks, so
the allocator's free count returns to its initial value however a run
ends (chaos-tested under seeded fault injection, ``serve/faults.py``).

Greedy outputs are token-for-token identical to one-request-at-a-time
sequential generation (tested against the dense reference ``Server``),
with or without faults for every request a fault does not poison.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import enum
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import guards
from repro.kernels.sq_paged_attn import walk_bounds
from repro.models.attention import EMPTY_POS
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serve import paged as paged_mod
from repro.serve.faults import FaultInjector, FaultyAllocator
from repro.serve.server import Request

__all__ = ["EngineConfig", "EngineMetrics", "Engine", "RequestStatus",
           "RequestResult", "SHED_POLICIES", "eviction_window"]

SHED_POLICIES = ("reject-new", "evict-oldest")


def _named(fn, name: str):
    """A fresh closure over ``fn`` whose ``__name__`` is ``name``."""
    def program(*args):
        return fn(*args)
    program.__name__ = program.__qualname__ = name
    return program


def eviction_window(cfg) -> Optional[int]:
    """The model's uniform block-eviction horizon, or None.

    Freed blocks are invisible to EVERY layer only when every
    attention-bearing layer masks by a sliding window; the horizon is the
    LARGEST such window (layers with smaller windows simply mask more of
    the live blocks).  Any full-attention layer (window None) disables
    eviction -- its queries may reach arbitrarily old positions.
    """
    from repro.models import blocks as blk
    windows = []
    for kind in cfg.layer_kinds:
        if kind not in blk.PAGEABLE_KINDS:
            continue
        w = blk._window_for(kind, cfg)
        if w is None:
            return None
        windows.append(int(w))
    return max(windows) if windows else None


class RequestStatus(str, enum.Enum):
    """Terminal request statuses (see the module docstring)."""
    COMPLETED = "completed"
    REJECTED = "rejected"
    TIMED_OUT = "timed_out"
    FAILED = "failed"
    CANCELLED = "cancelled"

    def __str__(self):
        return self.value


@dataclasses.dataclass
class RequestResult:
    """One request's terminal outcome.  ``tokens`` holds whatever was
    generated before the terminal event (complete output for
    ``COMPLETED``, partial for ``TIMED_OUT``/``FAILED``/``CANCELLED``,
    empty for ``REJECTED``)."""
    rid: int
    status: RequestStatus
    tokens: List[int]
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.status is RequestStatus.COMPLETED


@dataclasses.dataclass
class EngineConfig:
    max_slots: int = 8            # concurrent decode batch width
    block_size: int = 16          # tokens per cache block
    num_blocks: int = 64          # pool size (block 0 reserved null)
    blocks_per_seq: int = 8       # per-sequence context ceiling, in blocks
    prefill_chunk: int = 32       # prompt tokens processed per engine step
    max_new_tokens: int = 32
    eos_id: int = -1              # -1: never terminates early
    temperature: float = 0.0      # 0 = greedy (the bit-equivalence mode)
    prepared: bool = False        # LM.prepare_params at engine start
    jit: bool = True              # False: eager steps (benchmarks -- the
                                  # prepared amortization is visible only
                                  # when the per-call prep really executes;
                                  # also the regime where the core-layer
                                  # guard falls back IN-LINE; jitted guarded
                                  # engines use the compiled probe + drain +
                                  # re-jit path instead, _guarded_call)
    # ---- resilience (see module docstring) ----
    deadline_s: Optional[float] = None   # per-request wall budget from
                                         # submit (Request.deadline_s wins)
    max_wall_s: Optional[float] = None   # whole-run() budget
    queue_limit: Optional[int] = None    # bounded admission queue depth
    shed_policy: str = "reject-new"      # full-queue policy (SHED_POLICIES)
    max_preemptions: int = 8      # per-request; exceeded -> FAILED
    max_step_retries: int = 8     # consecutive failed model calls tolerated
    watchdog_steps: int = 200     # no-progress ticks before surfacing
    guard: bool = False           # numerics guard: fail non-finite-logits
                                  # slots; scope the core-layer square-route
                                  # guard over every step
    window_eviction: bool = True  # SWA archs: free blocks older than
                                  # pos - window back to the pool (caps a
                                  # sequence's footprint at the window;
                                  # no-op for full-attention archs)

    def __post_init__(self):
        if self.shed_policy not in SHED_POLICIES:
            raise ValueError(f"unknown shed_policy {self.shed_policy!r}; "
                             f"expected one of {SHED_POLICIES}")

    @property
    def max_len(self) -> int:
        return self.blocks_per_seq * self.block_size


@dataclasses.dataclass
class EngineMetrics:
    """Serving counters the benchmarks report (utilization as the metric,
    per the multisystolic-array scheduling framing -- not single-call
    latency), plus the backpressure/failure counters the resilience layer
    surfaces."""
    tokens_out: int = 0
    prefill_tokens: int = 0
    decode_steps: int = 0
    decode_slot_steps: int = 0    # sum of live slots over decode steps
    # paged-attention table columns over the live slots of every decode
    # step: those the fused kernel walks (its live range, hi - lo) and
    # those the tables span (blocks_per_seq); host-side, from positions
    paged_cols_walked: int = 0
    paged_cols_spanned: int = 0
    prefill_chunks: int = 0
    preemptions: int = 0
    peak_blocks_used: int = 0
    # ---- backpressure / failure accounting ----
    completed: int = 0
    rejected: int = 0             # refused at submit (invalid or shed)
    shed: int = 0                 # of rejected: evicted by `evict-oldest`
    timeouts: int = 0             # deadline / wall-budget expiries
    failures: int = 0             # FAILED terminals (budget, steps, guard)
    cancelled: int = 0
    step_failures: int = 0        # caught model-call exceptions (retried)
    watchdog_trips: int = 0
    guard_trips: int = 0          # non-finite logits rows + compiled-guard
                                  # probe trips (core contraction probes)
    guard_rejits: int = 0         # fresh traces forced by route demotions
    peak_queue_depth: int = 0
    # running sum/count (not a per-step list: a long-lived engine steps
    # forever and the bookkeeping must stay O(1))
    util_sum: float = 0.0
    util_steps: int = 0
    ttft_s: Dict[int, float] = dataclasses.field(default_factory=dict)
    wall_s: float = 0.0
    # held-expert MoE statistics, summed over layers and steps on the
    # device (the cache's ``moe_counters``) and read only when asked:
    # top-k picks of valid tokens, picks on held experts, rows the grouped
    # expert GEMMs computed.  ``device_read`` (set by the engine of such a
    # model) refreshes them; ``summary()`` calls it.
    moe_picks: Optional[int] = None
    moe_picks_held: Optional[int] = None
    moe_rows_padded: Optional[int] = None
    device_read: Optional[Callable[[], None]] = dataclasses.field(
        default=None, repr=False, compare=False)
    # Histogram-backed latency percentiles (fixed buckets: O(1) state,
    # same bounded-bookkeeping rule as the running sums above).  The mean
    # hides the preemption/retry tail; p95/p99 expose it.  ``ttft_hist``
    # is observed at TERMINAL time from the final ``ttft_s`` value -- a
    # preempted request's rolled-back TTFT never lands in the histogram
    # (histograms cannot un-observe), only the TTFT its caller actually
    # saw.  ``decode_step_hist`` observes each ragged decode step from
    # its dispatch through the synced sample -- the per-token latency
    # every live slot paid that step.
    ttft_hist: obs_metrics.Histogram = dataclasses.field(
        default_factory=lambda: obs_metrics.Histogram("engine_ttft_seconds"))
    decode_step_hist: obs_metrics.Histogram = dataclasses.field(
        default_factory=lambda: obs_metrics.Histogram(
            "engine_decode_step_seconds"))

    @property
    def tokens_per_s(self) -> float:
        return self.tokens_out / self.wall_s if self.wall_s else 0.0

    @property
    def mean_ttft_s(self) -> float:
        """Mean time-to-first-token over requests that GOT a first token.
        Shed/rejected requests never enter ``ttft_s`` (they saw no model
        work), so backpressure cannot skew the latency read; the empty
        case is 0.0, never a division by zero.  (Kept for bench-trajectory
        compatibility; the histogram percentiles are the honest read.)"""
        return (sum(self.ttft_s.values()) / len(self.ttft_s)
                if self.ttft_s else 0.0)

    @property
    def mean_utilization(self) -> float:
        return self.util_sum / self.util_steps if self.util_steps else 0.0

    @property
    def paged_walk_share(self) -> float:
        """Share of the decode steps' table columns the fused paged-
        attention kernel walks (the rest it skips as dead)."""
        return (self.paged_cols_walked / self.paged_cols_spanned
                if self.paged_cols_spanned else 0.0)

    @property
    def batch_occupancy(self) -> float:
        """Mean live slots per decode step (continuous-batching payoff)."""
        return (self.decode_slot_steps / self.decode_steps
                if self.decode_steps else 0.0)

    def summary(self) -> Dict[str, float]:
        if self.device_read is not None:
            self.device_read()
        out = {
            "tokens_out": self.tokens_out,
            "tokens_per_s": self.tokens_per_s,
            "mean_ttft_s": self.mean_ttft_s,
            "mean_block_utilization": self.mean_utilization,
            "peak_blocks_used": self.peak_blocks_used,
            "batch_occupancy": self.batch_occupancy,
            "paged_walk_share": self.paged_walk_share,
            "decode_steps": self.decode_steps,
            "prefill_chunks": self.prefill_chunks,
            "preemptions": self.preemptions,
            "completed": self.completed,
            "rejected": self.rejected,
            "shed": self.shed,
            "timeouts": self.timeouts,
            "failures": self.failures,
            "cancelled": self.cancelled,
            "step_failures": self.step_failures,
            "watchdog_trips": self.watchdog_trips,
            "guard_trips": self.guard_trips,
            "guard_rejits": self.guard_rejits,
            "peak_queue_depth": self.peak_queue_depth,
            "ttft_p50_s": self.ttft_hist.quantile(0.50),
            "ttft_p95_s": self.ttft_hist.quantile(0.95),
            "ttft_p99_s": self.ttft_hist.quantile(0.99),
            "decode_step_p50_s": self.decode_step_hist.quantile(0.50),
            "decode_step_p95_s": self.decode_step_hist.quantile(0.95),
            "decode_step_p99_s": self.decode_step_hist.quantile(0.99),
        }
        if self.moe_picks is not None:
            out.update(moe_picks=self.moe_picks,
                       moe_picks_held=self.moe_picks_held,
                       moe_rows_padded=self.moe_rows_padded)
        return out


@dataclasses.dataclass
class _Slot:
    req: Request
    n_prefilled: int = 0
    pos: int = 0                  # next cache position to write (decode)
    last_tok: int = 0
    remaining: int = 0
    state: str = "prefill"        # "prefill" | "decode"


class Engine:
    def __init__(self, model, params, cfg: EngineConfig, seed: int = 0,
                 faults: Optional[FaultInjector] = None,
                 registry: Optional[obs_metrics.MetricsRegistry] = None):
        self.model = model
        self.cfg = cfg
        self.params = (model.prepare_params(params) if cfg.prepared
                       else params)
        self.key = jax.random.PRNGKey(seed)
        self._faults = faults

        self.allocator = paged_mod.BlockAllocator(cfg.num_blocks,
                                                  cfg.block_size)
        if faults is not None:
            # the wrapper delegates state to the real allocator, so leak
            # accounting still reads the true pool
            self.allocator = FaultyAllocator(self.allocator, faults)
        self.tables = paged_mod.BlockTables(self.allocator, cfg.max_slots,
                                            cfg.blocks_per_seq)
        # arch eligibility (plain decoder LM, every layer's decode cache a
        # KV dict) is validated here, before any jit setup
        self.cache = model.init_paged_cache(cfg.num_blocks * cfg.block_size)
        self.pos_pool = jnp.asarray(
            paged_mod.empty_pos_pool(cfg.num_blocks, cfg.block_size))
        # SWA archs: the uniform horizon past which blocks are freed back
        # to the pool (None: full-attention arch, or eviction disabled)
        self._evict_window = (eviction_window(model.cfg)
                              if cfg.window_eviction else None)
        # the leading columns every layer's window has left: the fused
        # paged-attention kernel walks none of them
        self._walk_window = eviction_window(model.cfg)

        bs = cfg.block_size

        def chunk(params, cache, pos_pool, tables, tokens, positions):
            hidden, cache, pos_pool = model.decode_paged(
                params, cache, tokens, positions, tables, pos_pool,
                block_size=bs)
            return hidden, cache, pos_pool

        def decode(params, cache, pos_pool, tables, tokens, positions):
            hidden, cache, pos_pool = model.decode_paged(
                params, cache, tokens, positions, tables, pos_pool,
                block_size=bs)
            logits = model.logits(params, hidden)[:, -1]   # (B, V)
            return logits, cache, pos_pool

        def logits_at(params, hidden, idx):
            h = jax.lax.dynamic_slice_in_dim(hidden, idx, 1, axis=1)
            return model.logits(params, h)[:, 0]           # (1, V)

        # raw model fns are kept so the compiled guard can re-jit after a
        # RouteHealth demotion (demotion is a trace-time branch: a cached
        # trace keeps serving the square route until a fresh trace).  Keyed
        # by program name; each is served as ``self._<name>``.
        self._model_fns = {"chunk": chunk, "decode": decode,
                           "logits_at": logits_at}
        self._jit_model_fns()
        from repro.kernels import routing as _routing
        self._route_epoch = _routing.route_epoch()

        self.slots: List[Optional[_Slot]] = [None] * cfg.max_slots
        self.queue: List[Request] = []
        self.results: Dict[int, RequestResult] = {}
        self.metrics = EngineMetrics()
        # --- observability (docs/observability.md) ---------------------
        # Fresh per-engine registry by default so the chaos-suite
        # conservation invariants (submitted == sum of terminals) stay
        # per-run; launchers pass one registry to merge the whole stack.
        # In the registry, ``rejected`` EXCLUDES shed (shed gets its own
        # counter) so the terminal counters PARTITION submissions --
        # unlike ``EngineMetrics.shed``, which is a subset of
        # ``EngineMetrics.rejected``.
        self.registry = (registry if registry is not None
                         else obs_metrics.MetricsRegistry())
        reg = self.registry
        self._c_requests = {
            "submitted": reg.counter("engine_requests_submitted_total"),
            "completed": reg.counter("engine_requests_completed_total"),
            "rejected": reg.counter("engine_requests_rejected_total"),
            "shed": reg.counter("engine_requests_shed_total"),
            "timeouts": reg.counter("engine_requests_timeouts_total"),
            "failures": reg.counter("engine_requests_failures_total"),
            "cancelled": reg.counter("engine_requests_cancelled_total"),
        }
        self._c_work = {
            "tokens": reg.counter("engine_tokens_generated_total",
                                  help="tokens sampled (executed work: "
                                       "counts regeneration after "
                                       "preemption, unlike tokens_out)"),
            "prefill_chunks": reg.counter("engine_prefill_chunks_total"),
            "decode_steps": reg.counter("engine_decode_steps_total"),
            "preemptions": reg.counter("engine_preemptions_total"),
            "step_failures": reg.counter("engine_step_failures_total"),
            "watchdog_trips": reg.counter("engine_watchdog_trips_total"),
            "guard_trips": reg.counter("engine_guard_trips_total"),
            "guard_rejits": reg.counter("engine_guard_rejits_total"),
        }
        if "moe_counters" in self.cache:
            self._c_moe = [
                reg.counter("engine_moe_picks_total",
                            help="top-k router picks of served tokens, "
                                 "every MoE layer"),
                reg.counter("engine_moe_picks_held_total",
                            help="picks on experts this engine holds"),
                reg.counter("engine_moe_rows_padded_total",
                            help="rows the grouped expert GEMMs computed "
                                 "(held picks padded to row tiles)")]
            self.metrics.device_read = self.read_device_counters
        # the registry's latency histograms ARE the EngineMetrics ones
        # (one observe feeds both views)
        self.metrics.ttft_hist = reg.histogram("engine_ttft_seconds")
        self.metrics.decode_step_hist = reg.histogram(
            "engine_decode_step_seconds")
        self._newly_finished: List[RequestResult] = []
        self._arrival: Dict[int, float] = {}
        self._deadline: Dict[int, float] = {}     # rid -> absolute engine time
        self._preempts: Dict[int, int] = {}       # rid -> times preempted
        self._tick = 0
        self._heap_frozen = False                 # see _freeze_heap
        self._skew = 0.0                          # fault-injected clock skew
        self._idle_ticks = 0                      # watchdog state
        self._fail_streak = {"prefill": 0, "decode": 0}

    # ------------------------------------------------------------ helpers
    def _now(self) -> float:
        """The engine clock: wall time plus any injected skew (deadlines
        run on this clock, so chaos tests expire them without sleeping)."""
        return time.perf_counter() + self._skew

    def _sample(self, logits) -> np.ndarray:
        if self.cfg.temperature <= 0.0:
            toks = jnp.argmax(logits, axis=-1)
        else:
            self.key, sub = jax.random.split(self.key)
            toks = jax.random.categorical(sub, logits / self.cfg.temperature)
        with obs_trace.span("engine.sync", cat="engine", what="sample"):
            return np.asarray(toks)

    def _table_rows(self, rows: slice):
        """Block-table rows as a device array, from a snapshot: on the CPU
        backend ``jnp.asarray`` may alias an aligned numpy buffer, and the
        host edits the table in place (grow, evict, release) while a
        program dispatched earlier can still be reading it."""
        return jnp.asarray(self.tables.table[rows].copy())

    def _reset_pos(self, blocks: List[int]) -> None:
        """Return ``blocks``' slots of the position ledger to EMPTY_POS.
        The list is padded with the null block (whose positions are always
        EMPTY_POS) to a power of two, so the scatter compiles once per
        bucket rather than once per count of freed blocks."""
        if blocks:
            with obs_trace.span("engine.reset_pos", cat="engine",
                                n=len(blocks)):
                n = 1 << (len(blocks) - 1).bit_length()
                idx = self.tables.reset_slots_index(
                    list(blocks)
                    + [paged_mod.NULL_BLOCK] * (n - len(blocks)))
                self.pos_pool = self.pos_pool.at[jnp.asarray(idx)].set(
                    EMPTY_POS)

    def _release(self, slot_id: int) -> None:
        self._reset_pos(self.tables.release(slot_id))
        self.slots[slot_id] = None

    # ------------------------------------------------- terminal accounting
    def _count_terminal(self, status: RequestStatus) -> None:
        m = self.metrics
        if status is RequestStatus.COMPLETED:
            m.completed += 1
            self._c_requests["completed"].inc()
        elif status is RequestStatus.TIMED_OUT:
            m.timeouts += 1
            self._c_requests["timeouts"].inc()
        elif status is RequestStatus.FAILED:
            m.failures += 1
            self._c_requests["failures"].inc()
        elif status is RequestStatus.CANCELLED:
            m.cancelled += 1
            self._c_requests["cancelled"].inc()

    def _result(self, req: Request, status: RequestStatus,
                error: Optional[str] = None) -> RequestResult:
        """Record a request's terminal status (bounded bookkeeping: every
        per-rid map is popped here, whatever the terminal path)."""
        res = RequestResult(req.rid, status, list(req.out or []), error)
        self.results[req.rid] = res
        self._newly_finished.append(res)
        self._arrival.pop(req.rid, None)
        self._deadline.pop(req.rid, None)
        self._preempts.pop(req.rid, None)
        self._count_terminal(status)
        # the FINAL ttft (a preempted-then-regenerated request re-measures;
        # this is the one its caller saw) feeds the percentile histogram
        ttft = self.metrics.ttft_s.get(req.rid)
        if ttft is not None:
            self.metrics.ttft_hist.observe(ttft)
        obs_trace.event("request.terminal", cat="engine", rid=req.rid,
                        status=str(status))
        return res

    def _terminate(self, slot_id: int, status: RequestStatus,
                   error: Optional[str] = None) -> None:
        """End a slotted request: record the terminal status (partial
        tokens kept) and recycle its blocks."""
        self._result(self.slots[slot_id].req, status, error)
        self._release(slot_id)

    def _finish(self, slot_id: int) -> None:
        self._terminate(slot_id, RequestStatus.COMPLETED)

    def _reject(self, req: Request, msg: str, shed: bool = False) -> None:
        self.metrics.rejected += 1
        if shed:
            self.metrics.shed += 1
        # registry terminals PARTITION submissions: shed is counted as
        # shed there, NOT also as rejected (see __init__)
        self._c_requests["shed" if shed else "rejected"].inc()
        self._result(req, RequestStatus.REJECTED, msg)

    # ----------------------------------------------------------- admission
    def submit(self, requests: List[Request]) -> None:
        """Enqueue requests.  Invalid or shed requests are REJECTED with a
        terminal status (never an exception -- one bad request must not
        kill a batch); the single raising case is a duplicate ``rid``,
        which is a caller bug that would corrupt the results keying."""
        with obs_trace.span("engine.submit", cat="engine", n=len(requests)):
            for req in requests:
                self._submit_one(req)

    def _submit_one(self, req: Request) -> None:
        cfg = self.cfg
        if req.rid in self.results or req.rid in self._arrival:
            raise ValueError(
                f"duplicate request id {req.rid}: a rid already "
                f"queued, in flight, or finished would silently "
                f"overwrite its result; use fresh rids per request")
        self._c_requests["submitted"].inc()
        obs_trace.event("request.submit", cat="engine", rid=req.rid,
                        prompt_tokens=len(req.tokens))
        if len(req.tokens) == 0:
            self._reject(req, "empty prompt (there is no position to "
                              "sample the first token from)")
            return
        total = len(req.tokens) + cfg.max_new_tokens
        if total > cfg.max_len:
            self._reject(
                req, f"prompt {len(req.tokens)} + max_new "
                     f"{cfg.max_new_tokens} exceeds the per-sequence "
                     f"ceiling {cfg.max_len} ({cfg.blocks_per_seq} "
                     f"blocks x {cfg.block_size})")
            return
        if self.allocator.blocks_for(total) > cfg.num_blocks - 1:
            self._reject(
                req, f"needs {self.allocator.blocks_for(total)} blocks "
                     f"but the pool only has {cfg.num_blocks - 1} "
                     f"allocatable ones")
            return
        if cfg.queue_limit is not None \
                and len(self.queue) >= cfg.queue_limit:
            if cfg.shed_policy == "reject-new":
                self._reject(req, f"admission queue full "
                                  f"(queue_limit={cfg.queue_limit}, "
                                  f"shed_policy=reject-new)", shed=True)
                return
            # evict-oldest: shed the oldest *queued* request (in-flight
            # work is never thrown away by admission pressure)
            victim = self.queue.pop(0)
            self._reject(victim,
                         f"shed from the admission queue by a newer "
                         f"request (queue_limit={cfg.queue_limit}, "
                         f"shed_policy=evict-oldest)", shed=True)
        now = self._now()
        self._arrival[req.rid] = now
        budget = (req.deadline_s if req.deadline_s is not None
                  else cfg.deadline_s)
        if budget is not None:
            self._deadline[req.rid] = now + float(budget)
        self.queue.append(req)
        self.metrics.peak_queue_depth = max(
            self.metrics.peak_queue_depth, len(self.queue))

    def cancel(self, rid: int) -> bool:
        """Cancel a queued or in-flight request (terminal status
        CANCELLED, partial tokens returned, blocks recycled).  Returns
        False if ``rid`` is not pending."""
        with obs_trace.span("engine.cancel", cat="engine", rid=rid):
            for req in self.queue:
                if req.rid == rid:
                    self.queue.remove(req)
                    self._result(req, RequestStatus.CANCELLED, "cancelled")
                    return True
            for slot_id, slot in enumerate(self.slots):
                if slot is not None and slot.req.rid == rid:
                    self._terminate(slot_id, RequestStatus.CANCELLED,
                                    "cancelled")
                    return True
            return False

    def drain_finished(self) -> List[RequestResult]:
        """Terminal results accumulated since the last drain (streaming
        callers poll this after each :meth:`step`)."""
        out, self._newly_finished = self._newly_finished, []
        return out

    # ------------------------------------------------------------ deadlines
    def _expire_deadlines(self) -> None:
        if not self._deadline:
            return
        now = self._now()
        expired = {rid for rid, dl in self._deadline.items() if now >= dl}
        if not expired:
            return
        for req in [q for q in self.queue if q.rid in expired]:
            self.queue.remove(req)
            self._result(req, RequestStatus.TIMED_OUT,
                         "deadline expired while queued")
        for slot_id, slot in enumerate(self.slots):
            if slot is not None and slot.req.rid in expired:
                self._terminate(slot_id, RequestStatus.TIMED_OUT,
                                "deadline expired mid-generation")

    # ----------------------------------------------------------- preemption
    def _preempt_for(self, needy_slot: int) -> bool:
        """Release the youngest active slot (ties: highest slot id) and
        requeue its request at the queue head.  Greedy regeneration is
        deterministic, so outputs are unaffected -- only latency is.
        Evicting strictly youngest-first (the needy slot may evict itself)
        guarantees the oldest request always progresses: it is only ever
        chosen when alone, and alone in the pool its whole-sequence need
        fits by the submit() check, so its growth can never fail.

        A victim past its preemption budget FAILS cleanly instead of
        requeueing (its blocks are still freed): two long requests can
        degrade each other's latency, never livelock the engine."""
        del needy_slot
        victims = [i for i, s in enumerate(self.slots) if s is not None]
        if not victims:
            return False
        victim = max(victims, key=lambda i: (self._arrival[
            self.slots[i].req.rid], i))
        v = self.slots[victim]
        rid = v.req.rid
        self.metrics.preemptions += 1
        self._c_work["preemptions"].inc()
        n = self._preempts[rid] = self._preempts.get(rid, 0) + 1
        obs_trace.event("engine.preempt", cat="engine", rid=rid, count=n)
        if n > self.cfg.max_preemptions:
            # partial tokens stay in the result: they were delivered work
            self._terminate(victim, RequestStatus.FAILED,
                            f"preemption budget exhausted ({n} preemptions "
                            f"> max_preemptions={self.cfg.max_preemptions})")
            return True
        # roll the victim's DELIVERED-token accounting back: tokens_out /
        # ttft describe what reaches the caller, and the regeneration will
        # recount them (prefill/decode step counters stay -- they measure
        # executed work, which preemption really does repeat)
        self.metrics.tokens_out -= len(v.req.out or [])
        self.metrics.ttft_s.pop(rid, None)
        v.req.out = None                      # regenerate from scratch
        self.queue.insert(0, v.req)
        self._release(victim)
        return True

    # ----------------------------------------------------------- schedule
    def _admit(self) -> bool:
        admitted = False
        for slot_id in range(self.cfg.max_slots):
            if self.slots[slot_id] is not None or not self.queue:
                continue
            req = self.queue[0]
            # Under windowed eviction a sequence never holds more than
            # ~window tokens' worth of blocks, so admission only reserves
            # the first prefill chunk; prefill grows (and evicts) chunk by
            # chunk.  Without eviction the whole prompt is reserved up
            # front, exactly as before.
            need = (len(req.tokens) if self._evict_window is None
                    else min(len(req.tokens), self.cfg.prefill_chunk))
            if not self.tables.ensure(slot_id, need):
                break                          # pool exhausted: wait
            self.queue.pop(0)
            self.slots[slot_id] = _Slot(req=req)
            obs_trace.event("request.admit", cat="engine", rid=req.rid,
                            slot=slot_id)
            admitted = True
        return admitted

    def _jit_model_fns(self) -> None:
        # each call wraps the raw fns in FRESH closures before jitting:
        # jax's trace cache is keyed on the underlying callable, so
        # re-jitting the same object after a RouteHealth demotion would
        # silently reuse the pre-demotion program.  The closure carries
        # the program's name, so the compiled module (``jit_decode``) and
        # its device events in a profiler trace are named after it.
        for name, fn in self._model_fns.items():
            setattr(self, "_" + name,
                    jax.jit(_named(fn, name)) if self.cfg.jit else fn)

    def _guarded_call(self, name: str, *args):
        """Run the model program ``name`` (``self._<name>``) under the
        compiled numerics guard.

        With ``guard=True, jit=True`` the traces carry host-callback
        finite probes (see ``core/guards``): after each call the
        pending-trip ledger is drained into ``RouteHealth``; on a trip
        the returned value is suspect, so it is DISCARDED, the model fns
        are re-jitted if a demotion moved the route epoch (fresh traces
        see the demoted -- standard -- route), and the call retries on
        identical inputs.  The calls are functional (engine state is
        assigned only on success by the callers), so the retry is
        token-exact.  Eager guarded engines (``jit=False``) keep the
        in-line dispatcher fallback and skip the drain entirely."""
        fn = "_" + name
        if not (self.cfg.guard and self.cfg.jit):
            return getattr(self, fn)(*args)
        from repro.kernels import routing
        for _ in range(self.cfg.max_step_retries + 1):
            out = getattr(self, fn)(*args)
            with obs_trace.span("engine.sync", cat="engine",
                                what="guarded_call", fn=name):
                jax.block_until_ready(out)
            trips = guards.drain_pending_trips()
            if not trips:
                return out
            n_trips = sum(trips.values())
            self.metrics.guard_trips += n_trips
            self._c_work["guard_trips"].inc(n_trips)
            if routing.route_epoch() != self._route_epoch:
                self._route_epoch = routing.route_epoch()
                with obs_trace.span("engine.rejit", cat="engine",
                                    fn=name):
                    self._jit_model_fns()
                self.metrics.guard_rejits += 1
                self._c_work["guard_rejits"].inc()
        # retries exhausted with a key the breaker could not demote; the
        # per-slot logits guard downstream isolates the damage
        return out

    def _step_failed(self, kind: str, exc: Exception,
                     involved: List[int]) -> None:
        """A model call raised.  The calls are functional (state is
        assigned only on success), so nothing was mutated: retrying next
        tick is token-exact.  ``max_step_retries`` consecutive failures
        convert into clean per-request FAILED terminals."""
        self.metrics.step_failures += 1
        self._c_work["step_failures"].inc()
        obs_trace.event("engine.step_failure", cat="engine", kind=kind,
                        streak=self._fail_streak[kind] + 1)
        self._fail_streak[kind] += 1
        if self._fail_streak[kind] > self.cfg.max_step_retries:
            msg = (f"{kind} step failed {self._fail_streak[kind]} "
                   f"consecutive times (max_step_retries="
                   f"{self.cfg.max_step_retries}): {exc!r}")
            for slot_id in involved:
                if self.slots[slot_id] is not None:
                    self._terminate(slot_id, RequestStatus.FAILED, msg)
            self._fail_streak[kind] = 0

    def _prefill_one(self) -> bool:
        cfg = self.cfg
        cand = [i for i, s in enumerate(self.slots)
                if s is not None and s.state == "prefill"]
        if not cand:
            return False
        # oldest arrival first: FIFO time-to-first-token
        slot_id = min(cand, key=lambda i: (self._arrival[
            self.slots[i].req.rid], i))
        slot = self.slots[slot_id]
        rid = slot.req.rid
        prompt = np.asarray(slot.req.tokens, np.int32)
        lo = slot.n_prefilled
        chunk = prompt[lo:lo + cfg.prefill_chunk]
        if self._evict_window is not None:
            # retire blocks no query at position >= lo can reach, then
            # grow the table to cover this chunk (admission only reserved
            # the first chunk); preempt youngest-first when the pool is
            # dry, exactly like the decode growth loop.
            with obs_trace.span("engine.grow", cat="engine", rid=rid):
                freed = self.tables.evict_window(slot_id, lo,
                                                 self._evict_window)
                if freed:
                    obs_trace.event("engine.evict", cat="engine", rid=rid,
                                    blocks=len(freed))
                self._reset_pos(freed)
                while self.slots[slot_id] is not None and \
                        not self.tables.ensure(slot_id, lo + len(chunk)):
                    if not self._preempt_for(slot_id):
                        return False           # retry next tick
            if self.slots[slot_id] is None:    # preempted itself
                return True
        C = cfg.prefill_chunk
        try:
            # the span covers the injector hook too: an injected raise is
            # an error-tagged span, not a gap in the trace
            with obs_trace.span("engine.prefill_chunk", cat="engine",
                                rid=rid, lo=lo, n=len(chunk)):
                toks = np.zeros((1, C), np.int32)
                poss = np.full((1, C), -1, np.int32)
                toks[0, :len(chunk)] = chunk
                poss[0, :len(chunk)] = np.arange(lo, lo + len(chunk),
                                                 dtype=np.int32)
                tables_row = self._table_rows(slice(slot_id, slot_id + 1))
                if self._faults is not None:
                    self._faults.before_step("prefill")
                hidden, cache, pos_pool = self._guarded_call(
                    "chunk", self.params, self.cache, self.pos_pool,
                    tables_row, jnp.asarray(toks), jnp.asarray(poss))
        except Exception as e:                        # noqa: BLE001
            self._step_failed("prefill", e, [slot_id])
            return False
        self._fail_streak["prefill"] = 0
        self.cache, self.pos_pool = cache, pos_pool
        slot.n_prefilled = lo + len(chunk)
        self.metrics.prefill_chunks += 1
        self._c_work["prefill_chunks"].inc()
        self.metrics.prefill_tokens += len(chunk)
        if slot.n_prefilled == len(prompt):      # final chunk: first token
            with obs_trace.span("engine.first_token", cat="engine", rid=rid):
                logits = self._guarded_call("logits_at", self.params, hidden,
                                            jnp.int32(len(chunk) - 1))
                # one reduce + scalar transfer (nan/+inf propagate through
                # max), not an elementwise isfinite over the vocab row
                if cfg.guard:
                    with obs_trace.span("engine.sync", cat="engine",
                                        what="guard"):
                        finite = np.isfinite(float(jnp.max(logits)))
                    if not finite:
                        self.metrics.guard_trips += 1
                        self._terminate(slot_id, RequestStatus.FAILED,
                                        "non-finite prefill logits "
                                        "(numerics guard)")
                        return True
                tok = int(self._sample(logits)[0])
                self.metrics.ttft_s[rid] = self._now() - self._arrival[rid]
                obs_trace.event("request.first_token", cat="engine", rid=rid,
                                ttft_s=self.metrics.ttft_s[rid])
                slot.req.out = [tok]
                self.metrics.tokens_out += 1
                self._c_work["tokens"].inc()
                slot.last_tok = tok
                slot.pos = len(prompt)
                slot.remaining = cfg.max_new_tokens - 1
                slot.state = "decode"
                if tok == cfg.eos_id or slot.remaining <= 0:
                    self._finish(slot_id)
        return True

    def _decode_all(self) -> bool:
        cfg = self.cfg
        live = [i for i, s in enumerate(self.slots)
                if s is not None and s.state == "decode"]
        if not live:
            return False
        # grow every live slot's table to cover this step's write; preempt
        # youngest-first when the pool is dry.  A slot that can neither
        # grow nor find a victim (transient allocator exhaustion) simply
        # skips this tick -- it retries next tick, and the watchdog
        # surfaces the condition if it never clears.
        blocked = set()
        with obs_trace.span("engine.grow", cat="engine", n_live=len(live)):
            for slot_id in live:
                if self._evict_window is not None \
                        and self.slots[slot_id] is not None:
                    freed = self.tables.evict_window(
                        slot_id, self.slots[slot_id].pos, self._evict_window)
                    if freed:
                        obs_trace.event("engine.evict", cat="engine",
                                        rid=self.slots[slot_id].req.rid,
                                        blocks=len(freed))
                    self._reset_pos(freed)
                while self.slots[slot_id] is not None and \
                        not self.tables.ensure(slot_id,
                                               self.slots[slot_id].pos + 1):
                    if not self._preempt_for(slot_id):
                        blocked.add(slot_id)
                        break
        live = [i for i, s in enumerate(self.slots)
                if s is not None and s.state == "decode"
                and i not in blocked]
        if not live:
            return False
        dispatched = False
        try:
            # input build through the synced sample and the token
            # bookkeeping; the span covers the injector hook too, so an
            # injected raise is an error-tagged span, not a gap
            with obs_trace.span("engine.decode_step", cat="engine",
                                n_live=len(live)):
                B = cfg.max_slots
                toks = np.zeros((B, 1), np.int32)
                poss = np.full((B, 1), -1, np.int32)
                for i in live:
                    toks[i, 0] = self.slots[i].last_tok
                    poss[i, 0] = self.slots[i].pos
                tables = self._table_rows(slice(None))
                lo, hi = walk_bounds(poss, cfg.block_size, self._walk_window)
                walked = int((hi - lo).sum())
                toks, poss = jnp.asarray(toks), jnp.asarray(poss)
                if self._faults is not None:
                    self._faults.before_step("decode")
                t0 = time.perf_counter()
                logits, cache, pos_pool = self._guarded_call(
                    "decode", self.params, self.cache, self.pos_pool,
                    tables, toks, poss)
                # the calls are functional: a raise before this point left
                # nothing mutated; one after it is the host's own fault
                dispatched = True
                self._fail_streak["decode"] = 0
                self.cache, self.pos_pool = cache, pos_pool
                if self._faults is not None:
                    logits = self._faults.poison_logits(
                        logits, self.metrics.decode_steps)
                nxt = self._sample(logits)
                # one ragged decode step = one new token per live slot:
                # dispatch through the synced sample IS the per-token
                # latency those slots paid
                self.metrics.decode_step_hist.observe(
                    time.perf_counter() - t0)
                finite = None
                if cfg.guard:
                    # per-row max probe: nan/+inf propagate, so a poisoned
                    # row reads non-finite with one reduce instead of an
                    # elementwise isfinite pass over (slots, vocab)
                    with obs_trace.span("engine.sync", cat="engine",
                                        what="guard"):
                        finite = np.isfinite(
                            np.asarray(jnp.max(logits, axis=-1)))
                self.metrics.decode_steps += 1
                self._c_work["decode_steps"].inc()
                self.metrics.decode_slot_steps += len(live)
                self.metrics.paged_cols_walked += walked
                self.metrics.paged_cols_spanned += \
                    len(live) * cfg.blocks_per_seq
                for i in live:
                    if finite is not None and not finite[i]:
                        # fail THIS slot, not the batch: argmax over a
                        # poisoned row would silently serve token 0 forever
                        self.metrics.guard_trips += 1
                        self._terminate(i, RequestStatus.FAILED,
                                        "non-finite logits (numerics guard)")
                        continue
                    slot = self.slots[i]
                    tok = int(nxt[i])
                    slot.req.out.append(tok)
                    self.metrics.tokens_out += 1
                    self._c_work["tokens"].inc()
                    slot.pos += 1
                    slot.last_tok = tok
                    slot.remaining -= 1
                    if tok == cfg.eos_id or slot.remaining <= 0:
                        self._finish(i)
        except Exception as e:                        # noqa: BLE001
            if dispatched:
                raise
            self._step_failed("decode", e, live)
            return False
        return True

    # ------------------------------------------------------------ watchdog
    def _watchdog_fire(self) -> None:
        """No scheduler progress for ``watchdog_steps`` consecutive ticks
        with work still pending: convert the stall into surfaced per-
        request errors instead of an infinite ``run()`` loop."""
        self.metrics.watchdog_trips += 1
        self._c_work["watchdog_trips"].inc()
        obs_trace.event("engine.watchdog", cat="engine",
                        idle_ticks=self._idle_ticks)
        msg = (f"watchdog: no scheduler progress for {self._idle_ticks} "
               f"consecutive steps (persistent allocator exhaustion or "
               f"failing model calls)")
        for req in list(self.queue):
            self.queue.remove(req)
            self._result(req, RequestStatus.FAILED, msg)
        for slot_id, slot in enumerate(self.slots):
            if slot is not None:
                self._terminate(slot_id, RequestStatus.FAILED, msg)
        self._idle_ticks = 0

    def _abort_remaining(self, status: RequestStatus, msg: str) -> None:
        for req in list(self.queue):
            self.queue.remove(req)
            self._result(req, status, msg)
        for slot_id, slot in enumerate(self.slots):
            if slot is not None:
                self._terminate(slot_id, status, msg)

    # ----------------------------------------------------------------- API
    def step(self) -> bool:
        """One scheduler tick: expire deadlines, admit, one prefill chunk,
        one ragged decode step.  Returns False when there is nothing left
        to do.  Newly-terminal results are available from
        :meth:`drain_finished`."""
        self._tick += 1
        if self._faults is not None:
            self._skew += self._faults.clock_skew(self._tick)
        guard_ctx = (guards.guarded() if self.cfg.guard
                     else contextlib.nullcontext())
        with obs_trace.span("engine.tick", cat="engine", tick=self._tick), \
                guard_ctx:
            self._expire_deadlines()
            with obs_trace.span("engine.admit", cat="engine"):
                did = self._admit()
            did = self._prefill_one() or did
            did = self._decode_all() or did
            self.metrics.util_sum += self.allocator.utilization
            self.metrics.util_steps += 1
            self.metrics.peak_blocks_used = max(
                self.metrics.peak_blocks_used, self.allocator.used_blocks)
            pending = bool(self.queue) \
                or any(s is not None for s in self.slots)
            if pending and not did:
                self._idle_ticks += 1
                if self._idle_ticks >= self.cfg.watchdog_steps:
                    self._watchdog_fire()
                    pending = False
            else:
                self._idle_ticks = 0
        if not self._heap_frozen and self.metrics.decode_steps \
                and self.metrics.prefill_chunks:
            self._freeze_heap()
        return did or pending

    def _freeze_heap(self) -> None:
        """Once a decode step and a prefill chunk have run (so the step
        programs are compiled), collect once and move every live object
        into the collector's permanent generation (``gc.freeze``).  A full
        collection otherwise walks the whole heap -- JAX's modules, traces
        and caches, the weights' tree -- and stalls the tick it lands in
        (68 ms for ~215k objects with Moonlight-16B-A3B served, on a TPU
        v5e host); afterwards it walks only what serving allocated since.
        Frozen objects are still freed by reference counting (the engine
        itself holds no cycles)."""
        self._heap_frozen = True
        gc.collect()
        gc.freeze()

    def run(self, requests: List[Request]) -> Dict[int, RequestResult]:
        """Serve ``requests`` until every one reaches a terminal status;
        returns {rid: :class:`RequestResult`}.  Faults are absorbed into
        per-request statuses -- ``run`` itself raises only for caller
        bugs (duplicate rids)."""
        self.submit(requests)
        t0 = time.perf_counter()
        e0 = self._now()
        while self.queue or any(s is not None for s in self.slots):
            if self.cfg.max_wall_s is not None \
                    and self._now() - e0 >= self.cfg.max_wall_s:
                self._abort_remaining(
                    RequestStatus.TIMED_OUT,
                    f"run wall budget exhausted "
                    f"(max_wall_s={self.cfg.max_wall_s})")
                break
            if not self.step():
                break
        self.metrics.wall_s += time.perf_counter() - t0
        self.publish_metrics()
        return dict(self.results)

    # ------------------------------------------------------- observability
    def read_device_counters(self) -> None:
        """Read the step programs' on-device MoE counters (one sync, only
        when asked: never per tick) into :class:`EngineMetrics` and the
        registry counters ``engine_moe_*_total``.  The device sums are
        int32 and wrap past 2**31 picks."""
        if "moe_counters" not in self.cache:
            return
        with obs_trace.span("engine.sync", cat="engine",
                            what="moe_counters"):
            picks, held, rows = (int(v) for v in
                                 np.asarray(self.cache["moe_counters"]))
        m = self.metrics
        for c, old, new in zip(self._c_moe, (m.moe_picks, m.moe_picks_held,
                                             m.moe_rows_padded),
                               (picks, held, rows)):
            c.inc(max(0, new - (old or 0)))
        m.moe_picks, m.moe_picks_held, m.moe_rows_padded = picks, held, rows

    def publish_metrics(self) -> None:
        """Mirror the :class:`EngineMetrics` summary into the registry as
        ``engine_*`` gauges (throughput, mean/percentile latencies, peak
        depths).  The live counters/histograms are updated in-line as the
        engine runs; the summary-derived gauges are refreshed here --
        at the end of :meth:`run` and before :meth:`obs_snapshot`."""
        for k, v in self.metrics.summary().items():
            self.registry.gauge(f"engine_{k}").set(float(v))
        self.registry.gauge("engine_wall_s").set(self.metrics.wall_s)

    def obs_snapshot(self, audit=None) -> dict:
        """The whole-stack health snapshot (docs/observability.md).

        Publishes the engine summary gauges and the route-health dump
        into the engine's registry -- and the counting audit, when the
        caller ran one (``audit``: a ``ContractionCounter.summary()``
        dict, so the snapshot's square-routed fraction matches the
        audit's) -- then returns the registry snapshot augmented with the
        structured ``engine`` summary and ``route_health`` entries.
        ``launch/serve.py --metrics-file`` writes exactly this dict;
        ``scripts/obs_report.py`` renders it."""
        from repro.kernels import routing
        self.publish_metrics()
        health = routing.route_health().snapshot()
        obs_metrics.publish_route_health(health, self.registry)
        if audit is not None:
            obs_metrics.publish_contraction_audit(audit, self.registry)
        snap = self.registry.snapshot()
        snap["engine"] = dict(
            self.metrics.summary(), wall_s=self.metrics.wall_s,
            submitted=int(self._c_requests["submitted"].value))
        snap["route_health"] = health
        return snap
