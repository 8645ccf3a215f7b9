"""Unified route planner for the ``square_pallas`` dispatch mode.

``BENCH_kernels.json`` proves the best execution route for a square-form
contraction flips with shape: the fused window-streaming conv kernel wins
4-6x at batch 4, while at tiny-K single-channel shapes the two conv
routes sit near parity (im2col measured ~1.7x ahead there in interpret
mode; the regime rule encodes the patch-blowup asymptotics); tiny GEMMs
are dominated by pallas-call overhead where the MXU-routed ``square_virtual`` form is
strictly faster; and batched GEMMs with very small (M, N) per element
waste a grid step's fixed overhead on a few lane-ops.  Historically the
route was hard-coded per mode; this module makes it a *cost-model* choice,
resolved once per (shape, dtype) at dispatch time:

``matmul`` routes
    ``kernel``  -- the unbatched Pallas kernel;
    ``batched`` -- the leading-batch-grid-axis kernel (one element/step);
    ``fold``    -- batch folded into the row tile (``fb`` elements per
                   grid step -- small-(M, N), large-B regime);
    ``virtual`` -- the MXU-routed square-form fallback
                   (:func:`repro.core.matmul.pm_matmul_virtual`) below the
                   kernel-overhead floor.

``conv2d`` routes
    ``fused``   -- the window-streaming kernel (no patch tensor);
    ``im2col``  -- materialized patches through the matmul kernel (wins
                   when the patch matrix stays cache-resident and the
                   flattened K axis is tiny).

``paged_attn`` routes
    ``kernel``  -- the fused block-table-streaming Pallas kernel
                   (:mod:`repro.kernels.sq_paged_attn`): no gathered
                   window, traffic scales with the table walk;
    ``gather``  -- the dense ``jnp.take`` read path (wins for short
                   pools, where one gather beats a many-step grid, and
                   is the only route for integer-logits paths).

Precedence:

1. ``REPRO_ROUTE`` -- force a route globally (``REPRO_ROUTE=fused``) or
   per kind (``REPRO_ROUTE=matmul=kernel,conv2d=im2col``); ``auto`` (or
   unset) defers to the planner.  The repro escape hatch: pin the route a
   measurement was taken under.
2. The cost model -- the threshold rules above, built from the
   :mod:`repro.core.cost_model` tile-cost terms.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import os
from typing import Dict, List, Optional

import jax.numpy as jnp

from repro.core import cost_model as cm
from repro.core import squares as sq
from repro.kernels import tuning
from repro.obs import trace as obs_trace

__all__ = ["Route", "select_route", "select_matmul_route",
           "select_conv2d_route", "select_paged_attn_route",
           "MATMUL_ROUTES", "CONV2D_ROUTES", "PAGED_ATTN_ROUTES",
           "VIRTUAL_FLOOR_MULTS", "FOLD_STEP_LANE_OPS",
           "IM2COL_PATCH_BYTES_MAX", "IM2COL_K_MAX",
           "PAGED_KERNEL_MAX_S", "PAGED_KERNEL_MIN_T",
           "RouteHealth", "route_health", "reset_route_health",
           "route_epoch", "health_key"]

logger = logging.getLogger("repro.routing")

MATMUL_ROUTES = ("kernel", "batched", "fold", "virtual")
CONV2D_ROUTES = ("fused", "im2col")
PAGED_ATTN_ROUTES = ("kernel", "gather")

_KIND_ROUTES = {"matmul": MATMUL_ROUTES, "conv2d": CONV2D_ROUTES,
                "paged_attn": PAGED_ATTN_ROUTES}

# Contraction volume (B*M*K*N scalar multiplies) below which one
# pallas_call's fixed overhead (grid setup + a mandatory grid step,
# ~cm.TileCost's 4096-lane-op step charge) exceeds the whole contraction's
# PM work -- route to the MXU-form virtual fallback instead.
VIRTUAL_FLOOR_MULTS = 32768

# Per-batch-element PM lane-ops below which the batched kernel's
# one-element-per-grid-step schedule is overhead-bound (each step pays the
# ~4096-lane-op issue charge of cm.TileCost.weighted); folding ``fb``
# elements into the row tile amortizes it.  8 steps' worth of overhead is
# the measured crossover ballpark on interpret runs.
FOLD_STEP_LANE_OPS = 8 * 4096
FOLD_MIN_BATCH = 4

# The chunk width the fold threshold's per-element PM work is counted at.
FOLD_KC = 32

# im2col wins while its patch matrix stays cache-resident (a 2 MiB
# working set) AND the flattened K axis is below one lane group -- tiny-K
# windows give the fused kernel's shared-window machinery nothing to
# amortize (paper §5.1 regime boundary).
IM2COL_PATCH_BYTES_MAX = 2 * 1024 * 1024
IM2COL_K_MAX = tuning.LANE

# The fused paged-attention kernel streams a sequence's live pool blocks,
# at least 128 tokens and every KV head per grid step; its win condition
# is a long table walk amortizing a small query tile.
# Decode steps carry a handful of query rows (S <= chunk of new tokens,
# usually 1); above that the score tile rematerializes per block and the
# dense gather's single big contraction wins.
PAGED_KERNEL_MAX_S = 8
# Below this pool-length ceiling the gathered (B, T, KV, hd) window is
# small enough that one jnp.take + one einsum beats nb sequential grid
# steps' fixed overhead (same ~4096-lane-op step charge as the GEMM
# routes).  64 tokens ~ the measured interpret-mode crossover ballpark.
PAGED_KERNEL_MIN_T = 64


@dataclasses.dataclass(frozen=True)
class Route:
    """A resolved route choice plus why it was chosen (for logs/benches)."""
    name: str
    reason: str

    def __str__(self):
        return self.name


_ALL_ROUTES = frozenset().union(*_KIND_ROUTES.values())


def _traced_selector(kind: str):
    """Wrap a route selector so every resolved decision lands in the
    tracer as a ``route.decide`` instant event (chosen route + the
    cost-model rationale string).  Disabled tracing costs one global
    read per call -- the overhead contract in docs/observability.md."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            route = fn(*args, **kwargs)
            t = obs_trace.get_tracer()
            if t is not None:
                t.event("route.decide", cat="dispatch", kind=kind,
                        route=route.name, reason=route.reason)
            return route
        return wrapper
    return deco


def _env_route(kind: str, valid) -> Optional[str]:
    """Parse ``REPRO_ROUTE`` for ``kind``.

    A bare route name applies to every kind it is valid for -- most
    names pin exactly one kind (``REPRO_ROUTE=fused`` pins conv2d and
    leaves matmul on the planner), but ``kernel`` is shared by matmul
    and paged_attn and a bare pin applies to both; use a ``kind=route``
    comma list to scope explicitly.  ``auto`` defers.  Unknown route
    names raise."""
    v = os.environ.get("REPRO_ROUTE", "").strip()
    if not v or v == "auto":
        return None
    if "=" in v:
        for part in v.split(","):
            key, _, val = part.partition("=")
            if key.strip() == kind:
                val = val.strip()
                if val in ("", "auto"):
                    return None
                if val not in valid:
                    raise ValueError(
                        f"REPRO_ROUTE: unknown {kind} route {val!r}; "
                        f"expected one of {tuple(valid)} or 'auto'")
                return val
        return None
    if v in valid:
        return v
    if v in _ALL_ROUTES:
        return None                 # valid for the other kind only
    raise ValueError(f"REPRO_ROUTE: unknown route {v!r}; expected one of "
                     f"{tuple(sorted(_ALL_ROUTES))} or 'auto'")


@_traced_selector("matmul")
def select_matmul_route(m: int, n: int, k: int, *, batch: int = 1,
                        dtype=jnp.float32) -> Route:
    """Resolve the ``square_pallas`` route of a (possibly batched) GEMM."""
    env = _env_route("matmul", MATMUL_ROUTES)
    if env is not None:
        return Route(env, "REPRO_ROUTE override")
    mults = batch * m * n * k
    if mults < VIRTUAL_FLOOR_MULTS:
        return Route("virtual", f"volume {mults} below kernel-overhead "
                                f"floor {VIRTUAL_FLOOR_MULTS}")
    if batch == 1:
        return Route("kernel", "unbatched GEMM")
    step_ops = cm.pm_tile_vpu_ops(m, n, k, kc=FOLD_KC)
    if batch >= FOLD_MIN_BATCH and step_ops < FOLD_STEP_LANE_OPS:
        return Route("fold", f"per-element PM work {step_ops:.0f} lane-ops "
                             f"below the grid-step floor "
                             f"{FOLD_STEP_LANE_OPS}")
    return Route("batched", "per-element work amortizes its grid step")


@_traced_selector("conv2d")
def select_conv2d_route(oh: int, ow: int, kh: int, kw: int, cin: int,
                        cout: int, *, batch: int = 1,
                        dtype=jnp.float32) -> Route:
    """Resolve the ``square_pallas`` route of a 2D convolution."""
    env = _env_route("conv2d", CONV2D_ROUTES)
    if env is not None:
        return Route(env, "REPRO_ROUTE override")
    acc = sq.accum_dtype(dtype)
    kvol = cin * kh * kw
    patch = cm.conv2d_patch_bytes(oh, ow, kh, kw, cin, batch=batch,
                                  itemsize=jnp.dtype(acc).itemsize)
    if patch <= IM2COL_PATCH_BYTES_MAX and kvol <= IM2COL_K_MAX:
        return Route("im2col", f"patch matrix {patch}B cache-resident and "
                               f"K volume {kvol} below one lane group")
    return Route("fused", f"patch matrix {patch}B / K volume {kvol} in the "
                          f"window-streaming regime")


@_traced_selector("paged_attn")
def select_paged_attn_route(s: int, t: int, *, batch: int = 1,
                            kv_heads: int = 1, group: int = 1,
                            hd: int = 64, dtype=jnp.float32) -> Route:
    """Resolve the paged-KV attention read route of a decode/chunk step.

    ``s`` is the query-tile length (new tokens this step), ``t`` the
    logical pool length the block table spans (``blocks_per_seq *
    block_size``).  Integer dtypes always gather (the fused kernel's
    softmax path is float-only)."""
    if not jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
        return Route("gather", f"{jnp.dtype(dtype).name} operands: the "
                               f"fused softmax kernel is float-only")
    env = _env_route("paged_attn", PAGED_ATTN_ROUTES)
    if env is not None:
        return Route(env, "REPRO_ROUTE override")
    gbytes = cm.paged_attn_gather_bytes(t, kv_heads, hd, batch=batch)
    if s > PAGED_KERNEL_MAX_S:
        return Route("gather", f"query tile {s} > {PAGED_KERNEL_MAX_S}: "
                               f"per-block rematerialization outweighs "
                               f"the {gbytes}B gather")
    if t < PAGED_KERNEL_MIN_T:
        return Route("gather", f"pool length {t} < {PAGED_KERNEL_MIN_T}: "
                               f"gathered window ({gbytes}B) too small to "
                               f"amortize the block-walk grid")
    return Route("kernel", f"long table walk (T={t}, S={s}) streams past "
                           f"the {gbytes}B dense gather")


# --------------------------------------------------------------------------
# Route health: the per-(site, shape, dtype) circuit breaker.
#
# The numerics guard (repro.core.guards) checks square-routed contraction
# outputs for non-finite values; every trip is recorded here.  After
# ``trip_limit`` trips of one key, the key is DEMOTED: the dispatcher
# serves that call site on the standard (multiplier) route from then on.
# Demotion is logged exactly once per key and is visible in the
# contraction counter's square-fraction audit (the demoted contractions
# note ``mode="standard"`` with ``demoted=True``) -- degradation is
# observable, never silent.  State is per-process and resettable
# (:func:`reset_route_health`), mirroring how a serving deployment would
# re-arm breakers on model reload.
# --------------------------------------------------------------------------

def health_key(site: str, sizes, dtype) -> str:
    """Circuit-breaker key of one contraction call site.

    ``sizes`` is any shape-describing tuple (the dispatcher passes the
    canonical ``(B, M, K, N)``); dtype is the *operand* dtype -- the trip
    regime is set by the operand magnitudes entering ``(a+b)^2``.
    """
    sig = "x".join(str(int(s)) for s in sizes)
    return f"{site}|{sig}|{jnp.dtype(dtype).name}"


@dataclasses.dataclass
class RouteHealth:
    """Trip counts and demotions, keyed by :func:`health_key`.

    ``epoch`` increments on every routing-state change a cached trace
    could be stale against (a demotion, or a registry reset re-arming
    demoted keys).  Demotion is a trace-time Python branch, so compiled
    callers (``repro.train.step.GuardedStep``, the jitted serving
    engine) compare epochs to decide when a re-jit is needed -- and only
    then (see :func:`route_epoch`).
    """
    trips: Dict[str, int] = dataclasses.field(default_factory=dict)
    demotions: Dict[str, str] = dataclasses.field(default_factory=dict)
    epoch: int = 0
    # trip ordinals: every record_trip() gets a process-wide sequence
    # number; first/last per key date a breaker's history ("tripped once
    # at startup" vs "tripping right now") without storing timestamps
    trip_seq: int = 0
    first_trip: Dict[str, int] = dataclasses.field(default_factory=dict)
    last_trip: Dict[str, int] = dataclasses.field(default_factory=dict)

    def record_trip(self, key: str, limit: int,
                    reason: str = "non-finite square-route output") -> bool:
        """Record one guard trip; returns True when this trip demotes."""
        self.trips[key] = self.trips.get(key, 0) + 1
        self.trip_seq += 1
        self.first_trip.setdefault(key, self.trip_seq)
        self.last_trip[key] = self.trip_seq
        obs_trace.event("guard.trip", cat="guard", key=key,
                        trips=self.trips[key], reason=reason)
        if key not in self.demotions and self.trips[key] >= max(1, limit):
            self.demotions[key] = (f"{reason} ({self.trips[key]} trips)")
            self.epoch += 1
            obs_trace.event("guard.demote", cat="guard", key=key,
                            trips=self.trips[key])
            logger.warning(
                "route-health: demoting %s to the standard route after "
                "%d guard trips (%s)", key, self.trips[key], reason)
            return True
        return False

    def is_demoted(self, key: str) -> bool:
        return key in self.demotions

    def summary(self) -> Dict[str, object]:
        return {"trips": dict(self.trips),
                "demotions": dict(self.demotions)}

    def snapshot(self) -> List[Dict[str, object]]:
        """Registry dump, one entry per key that ever tripped: trip
        count, demoted flag + reason, and the first/last trip ordinals
        (:attr:`trip_seq` sequence numbers).  Surfaced in the engine's
        observability snapshot and ``launch/serve.py``'s summary line,
        and publishable as labeled gauges via
        :func:`repro.obs.metrics.publish_route_health`."""
        return [{"key": key,
                 "trips": n,
                 "demoted": key in self.demotions,
                 "reason": self.demotions.get(key),
                 "first_trip": self.first_trip.get(key, 0),
                 "last_trip": self.last_trip.get(key, 0)}
                for key, n in sorted(self.trips.items())]


_HEALTH = RouteHealth()


def route_health() -> RouteHealth:
    """The process-wide route-health registry."""
    return _HEALTH


def reset_route_health() -> None:
    """Re-arm every breaker (tests / model reload).  Bumps the route
    epoch: traces compiled while keys were demoted are stale now."""
    if _HEALTH.demotions:
        _HEALTH.epoch += 1
    _HEALTH.trips.clear()
    _HEALTH.demotions.clear()
    _HEALTH.first_trip.clear()
    _HEALTH.last_trip.clear()


def route_epoch() -> int:
    """Monotonic counter of routing-state changes (demotions/resets).
    Compiled callers snapshot it at trace time and re-jit only when it
    moved -- the cheap "is my cached trace stale?" probe."""
    return _HEALTH.epoch


def select_route(kind: str, sizes: dict, *, dtype=jnp.float32) -> Route:
    """Generic entry point: ``kind`` is ``"matmul"``, ``"conv2d"`` or
    ``"paged_attn"``, ``sizes`` the corresponding geometry dict (see the
    typed helpers)."""
    if kind == "matmul":
        return select_matmul_route(sizes["m"], sizes["n"], sizes["k"],
                                   batch=sizes.get("b", 1), dtype=dtype)
    if kind == "conv2d":
        return select_conv2d_route(sizes["oh"], sizes["ow"], sizes["kh"],
                                   sizes["kw"], sizes["ci"], sizes["co"],
                                   batch=sizes.get("b", 1), dtype=dtype)
    if kind == "paged_attn":
        return select_paged_attn_route(
            sizes["s"], sizes["t"], batch=sizes.get("b", 1),
            kv_heads=sizes.get("kv", 1), group=sizes.get("g", 1),
            hd=sizes.get("hd", 64), dtype=dtype)
    raise ValueError(f"unknown route kind {kind!r}; expected one of "
                     f"{tuple(_KIND_ROUTES)}")
