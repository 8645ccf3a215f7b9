"""train_step / prefill_step / decode_step builders.

These are the functions the launcher jits (and the dry-run lowers).  They
close over (model, train config) and take pytrees only, so the same builder
serves smoke tests (1 CPU device) and the 512-chip production mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp

from repro.core import counting, guards
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.optim import adamw
from repro.train import loss as loss_mod

__all__ = ["TrainConfig", "make_train_step", "make_prefill_step",
           "make_decode_step", "make_loss_fn", "audit_step", "GuardedStep"]


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    opt: adamw.AdamWConfig = adamw.AdamWConfig()
    aux_loss_weight: float = 0.01         # MoE load-balance
    microbatch: int = 0                   # 0 = no gradient accumulation
    grad_compression: bool = False        # int8 + error feedback (cross-pod)


def _batch_mask(model, batch):
    """Loss mask: next-token targets, zero on VLM patch prefix."""
    cfg = model.cfg
    tokens = batch["tokens"]
    B, S1 = tokens.shape
    return jnp.ones((B, S1 - 1), jnp.float32)


def make_loss_fn(model, tcfg: TrainConfig):
    cfg = model.cfg

    def loss_fn(params, batch):
        tokens = batch["tokens"]                      # (B, S+1)
        inp = dict(batch)
        inp["tokens"] = tokens[:, :-1]
        labels = tokens[:, 1:]
        hidden, aux, _ = model.forward(params, inp)
        if cfg.prefix_tokens:
            hidden = hidden[:, cfg.prefix_tokens:]    # only text positions
        loss, metrics = loss_mod.chunked_xent(
            hidden, labels, (params["head"] if "head" in params
                             else params["embed"])["table"],
            mask=_batch_mask(model, batch), chunk=cfg.loss_chunk,
            mode=cfg.matmul_mode, policy=cfg.contraction_policy)
        total = loss + tcfg.aux_loss_weight * aux
        metrics = dict(metrics, xent=loss, aux=aux)
        return total, metrics

    return loss_fn


def make_train_step(model, tcfg: TrainConfig):
    loss_fn = make_loss_fn(model, tcfg)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(params, opt_state, batch):
        if tcfg.microbatch and tcfg.microbatch < batch["tokens"].shape[0]:
            # gradient accumulation over microbatches (scan keeps HLO small)
            from repro.distributed import context as dctx
            from repro.distributed import sharding as shd
            B = batch["tokens"].shape[0]
            mb = tcfg.microbatch
            n = B // mb
            mesh = dctx.current_mesh()

            def to_micro(x):
                x = x.reshape(n, mb, *x.shape[1:])
                if mesh is not None:
                    # keep the batch shard on the microbatch axis -- without
                    # this GSPMD replicates the whole step (see §Perf log)
                    axes = (None, "batch") + (None,) * (x.ndim - 2)
                    x = shd.constrain(x, mesh, *axes)
                return x

            mbatch = jax.tree.map(to_micro, batch)

            def acc_body(carry, mb_batch):
                g_acc, l_acc = carry
                (l, met), g = grad_fn(params, mb_batch)
                g_acc = jax.tree.map(jnp.add, g_acc, g)
                return (g_acc, l_acc + l), met

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, lsum), mets = jax.lax.scan(
                acc_body, (g0, jnp.zeros(())), mbatch)
            grads = jax.tree.map(lambda g: g / n, grads)
            loss = lsum / n
            metrics = jax.tree.map(lambda m: m[-1], mets)
        else:
            (loss, metrics), grads = grad_fn(params, batch)
        if tcfg.grad_compression:
            opt_state = dict(opt_state)
            ef = opt_state.get("error_feedback")
            if ef is None:
                ef = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
            grads, ef = adamw.compressed_grad_tree(grads, ef)
            opt_state["error_feedback"] = ef
        new_params, new_opt, opt_metrics = adamw.adamw_update(
            tcfg.opt, params, grads,
            {k: opt_state[k] for k in ("step", "m", "v")})
        if tcfg.grad_compression:
            new_opt["error_feedback"] = opt_state["error_feedback"]
        metrics = dict(metrics, loss=loss, **opt_metrics)
        return new_params, new_opt, metrics

    return train_step


def audit_step(step_fn, params, opt_state, batch):
    """Run ONE train step under a contraction audit and return
    ``(step_outputs, ContractionCounter)``.

    With the fs_einsum custom VJP in place the counter covers forward AND
    backward contraction volume (sites ``<site>.bwd_x`` / ``<site>.bwd_w``),
    so ``ctr.fraction_square`` is the square-routed fraction of *total*
    train FLOPs and ``ctr.fraction_square_bwd`` gates backward coverage.
    Notes fire at trace time: pass the first (tracing) call of a jitted
    step or an eager step -- a cached re-execution warns and records
    nothing (:class:`repro.core.counting.EmptyAuditWarning`).
    """
    with counting.track_contractions() as ctr:
        out = step_fn(params, opt_state, batch)
    return out, ctr


class GuardedStep:
    """A jitted train step with the compiled numerics guard in the loop.

    Wraps a raw ``train_step(params, opt_state, batch)`` builder output
    so that every call runs under a :func:`repro.core.guards.guarded`
    scope -- the TRACE bakes a host-callback finite probe next to each
    square-routed contraction (see ``core/guards``) -- and, after the
    step, drains the pending-trip ledger:

    - **clean step** (no trips): the result is returned as-is; on the
      happy path the only overhead is the in-graph probe reduces plus
      one ``effects_barrier``.
    - **tripped step**: the output is *suspect* (the compiled program
      has no in-graph fallback -- a saturated ``(a+b)^2`` flowed through
      the optimizer update), so the result is DISCARDED and the step
      re-executed on the same inputs.  Each drain records trips into
      ``RouteHealth``; once a key demotes, the routing state is
      trace-time-visible only, so the wrapper re-jits (counted in
      ``rejits``) and the fresh trace serves that site on the standard
      route.  Retries are bounded by ``max_retries`` -- with a
      ``trip_limit``-trip breaker per key and a finite number of keys,
      a persistent saturation converges to full demotion well inside
      the bound; a step still tripping at the bound raises.

    The retry is DETERMINISTIC: the step function is pure and the inputs
    are unchanged, so a demoted retry computes exactly what an
    eagerly-guarded run would have (pinned bit-identical by
    ``tests/test_compiled_guard.py``).

    NOTE: do not pass a step jitted with donated arguments -- a retry
    re-uses the inputs.  ``GuardedStep`` owns the ``jax.jit`` call
    (``jit=False`` for an eager step, where the in-line dispatcher
    fallback makes the drain a no-op).
    """

    def __init__(self, step_fn, *, jit: bool = True,
                 trip_limit: int = guards.DEFAULT_TRIP_LIMIT,
                 max_retries: int = 8,
                 registry: obs_metrics.MetricsRegistry = None):
        self._raw = step_fn
        self._jit = jit
        self._fn = self._fresh_jit() if jit else step_fn
        self.trip_limit = trip_limit
        self.max_retries = max_retries
        self.guard_trips = 0          # probe trips drained (all keys)
        self.rejits = 0               # fresh traces forced by demotions
        self.retries = 0              # discarded-and-recomputed steps
        reg = registry if registry is not None else obs_metrics.default_registry()
        self.registry = reg
        self._c_trips = reg.counter("train_guard_trips_total")
        self._c_rejits = reg.counter("train_guard_rejits_total")
        self._c_retries = reg.counter("train_guard_retries_total")
        from repro.kernels import routing
        self._epoch = routing.route_epoch()

    def _fresh_jit(self):
        # jax.jit(self._raw) would HIT the shared trace cache (keyed on
        # the underlying callable) and silently keep the pre-demotion
        # program; a fresh closure forces a genuine retrace
        raw = self._raw
        return jax.jit(lambda *args: raw(*args))

    def stats(self) -> Dict[str, int]:
        return {"guard_trips": self.guard_trips, "rejits": self.rejits,
                "retries": self.retries}

    def __call__(self, params, opt_state, batch):
        from repro.kernels import routing
        for attempt in range(self.max_retries + 1):
            with guards.guarded(trip_limit=self.trip_limit):
                out = self._fn(params, opt_state, batch)
                jax.block_until_ready(out)
                trips = guards.drain_pending_trips(self.trip_limit)
            if not trips:
                return out
            n_trips = sum(trips.values())
            self.guard_trips += n_trips
            self._c_trips.inc(n_trips)
            if routing.route_epoch() != self._epoch:
                # a key demoted: cached traces still serve the square
                # route there -- only a fresh trace sees the demotion
                self._epoch = routing.route_epoch()
                if self._jit:
                    with obs_trace.span("train.rejit", cat="train",
                                        attempt=attempt):
                        self._fn = self._fresh_jit()
                    self.rejits += 1
                    self._c_rejits.inc()
            self.retries += 1
            self._c_retries.inc()
        raise RuntimeError(
            f"guarded train step still tripping after {self.max_retries} "
            f"retries (keys: {sorted(trips)}) -- the non-finite source is "
            f"not a square-routed contraction this guard can demote")


def make_prefill_step(model, cache_len: int):
    def prefill_step(params, batch):
        hidden, cache = model.prefill(params, batch, cache_len)
        # next-token logits for the last position (sampling seed)
        logits = model.logits(params, hidden[:, -1:])[:, 0]
        return logits, cache
    return prefill_step


def make_decode_step(model):
    def decode_step(params, cache, tokens, pos):
        return model.decode_step(params, cache, tokens, pos)
    return decode_step
