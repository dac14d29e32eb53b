"""Jitted FL round steps.

Two builders per DESIGN.md §3:

* ``make_cohort_round`` — the full paper round in one jitted program:
  ProbAlloc -> stochastic selection -> vmapped local training of the cohort
  (one mesh data-slice per client when ``spmd_axes`` is given) -> volatile
  success bits -> masked deadline aggregation -> E3CS weight update.
  The selection math runs over all K (replicated scalars) so the technique is
  part of the compiled program.

* ``make_silo_steps`` — for huge architectures: one client trains at a time
  on the entire mesh (FSDP+TP); returns (local_step, agg_step) jitted pieces
  the server loop time-multiplexes across the cohort.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.selection import E3CSState, e3cs_init, e3cs_probs, e3cs_update, fedcs_select, random_select, sample_selection, selection_mask, ucb_init, ucb_select, ucb_update
from repro.obs.trace import stage
from repro.optim import sgd

from .aggregation import aggregate, aggregate_async
from .client import make_local_update

__all__ = [
    "ServerState",
    "init_server_state",
    "make_alloc_fn",
    "make_select_fn",
    "make_cohort_round",
    "make_async_cohort_round",
    "make_silo_steps",
]


class ServerState(NamedTuple):
    params: object
    e3cs: E3CSState
    ucb: object
    loss_cache: jax.Array  # (K,) pow-d loss estimates
    vol_state: jax.Array
    t: jax.Array
    sel_counts: jax.Array  # (K,)
    cep: jax.Array  # scalar
    succ_hist: jax.Array  # scalar successes observed (for metrics)


def init_server_state(params, K: int, vol_state) -> ServerState:
    return ServerState(
        params=params,
        e3cs=e3cs_init(K),
        ucb=ucb_init(K),
        loss_cache=jnp.full((K,), 1e9, jnp.float32),  # unexplored => very lossy
        vol_state=vol_state,
        t=jnp.zeros((), jnp.int32),
        sel_counts=jnp.zeros((K,), jnp.float32),
        cep=jnp.zeros((), jnp.float32),
        succ_hist=jnp.zeros((), jnp.float32),
    )


def make_alloc_fn(fl_cfg):
    """Returns alloc(state, sigma) -> (p, capped): E3CS's ProbAlloc with the
    configured allocator, under the ``round.allocate`` scope."""
    k = fl_cfg.k
    allocator = getattr(fl_cfg, "allocator", "sort")
    if allocator not in ("sort", "bisect"):
        raise ValueError(f"unknown allocator {allocator!r} (want 'sort' or 'bisect')")

    def alloc(state: ServerState, sigma):
        with stage("round.allocate"):
            if allocator == "bisect":
                # sort-free fixed point (the shardable engine allocator);
                # lazy import — repro.engine depends on this module
                from repro.engine.sharded import masked_prob_alloc

                w = jnp.exp(state.e3cs.logw - jnp.max(state.e3cs.logw))
                return masked_prob_alloc(w, k, sigma)
            return e3cs_probs(state.e3cs, k, sigma)

    return alloc


def make_select_fn(fl_cfg, quota_fn, rho=None):
    """Returns jitted select(state, rng) -> (idx, p, capped, sigma)."""
    K, k = fl_cfg.K, fl_cfg.k
    alloc = make_alloc_fn(fl_cfg)

    def select(state: ServerState, rng: jax.Array):
        sigma = quota_fn(state.t)
        if fl_cfg.scheme == "e3cs":
            p, capped = alloc(state, sigma)
            with stage("round.sample"):
                idx = sample_selection(rng, p, k, fl_cfg.sampler)
        elif fl_cfg.scheme == "random":
            idx = random_select(rng, K, k)
            p = jnp.full((K,), k / K)
            capped = jnp.zeros((K,), bool)
        elif fl_cfg.scheme == "fedcs":
            idx = fedcs_select(jnp.asarray(rho), k, rng)
            p = selection_mask(idx, K)
            capped = jnp.zeros((K,), bool)
        elif fl_cfg.scheme == "ucb":
            idx = ucb_select(state.ucb, k)
            p = selection_mask(idx, K)
            capped = jnp.zeros((K,), bool)
        elif fl_cfg.scheme == "pow_d":
            from repro.core.selection import pow_d_select

            idx = pow_d_select(rng, state.loss_cache, k, fl_cfg.pow_d)
            p = selection_mask(idx, K)
            capped = jnp.zeros((K,), bool)
        else:
            raise ValueError(fl_cfg.scheme)
        return idx, p, capped, sigma

    return select


def _selector_update(state: ServerState, fl_cfg, idx, p, capped, mask, x_full, sigma, local_losses):
    new_e3cs = state.e3cs
    new_ucb = state.ucb
    if fl_cfg.scheme == "e3cs":
        new_e3cs = e3cs_update(state.e3cs, p, capped, mask, x_full, fl_cfg.k, sigma, fl_cfg.eta)
    elif fl_cfg.scheme == "ucb":
        new_ucb = ucb_update(state.ucb, idx, x_full)
    # participating successful clients refresh the pow-d loss cache
    loss_cache = state.loss_cache
    upd = jnp.zeros_like(loss_cache).at[idx].set(local_losses)
    got = jnp.zeros_like(loss_cache).at[idx].set(x_full[idx])
    loss_cache = jnp.where(got > 0, upd, loss_cache)
    return new_e3cs, new_ucb, loss_cache


def make_cohort_round(
    model,
    fl_cfg,
    quota_fn,
    volatility,
    rho=None,
    spmd_axes=None,
    aggregation: Optional[str] = None,
    donate: bool = True,
    select=None,
):
    """Full jitted round. Returns ``round_fn(state, idx, p, capped, sigma,
    batches, step_mask, data_sizes, epochs, rng) -> (state, metrics)`` plus
    the ``select`` fn (host calls select first to gather the cohort's data).
    ``select`` overrides the allocate+select stage — ``FLServer`` passes
    ``RoundProgram.select_fn()`` so the training loop shares the engine's
    knob resolution; the default builds the identical fn from the raw config.
    """
    opt = sgd(fl_cfg.lr, fl_cfg.momentum)
    local = make_local_update(model, opt, fl_cfg.local_update, fl_cfg.prox_coef)
    vlocal = jax.vmap(local, in_axes=(None, 0, 0, 0), spmd_axis_name=spmd_axes)
    agg_scheme = aggregation or fl_cfg.aggregation
    select = select if select is not None else make_select_fn(fl_cfg, quota_fn, rho)

    def round_fn(state: ServerState, idx, p, capped, sigma, batches, step_mask, data_sizes, total_data, epochs, rng):
        K = fl_cfg.K
        r_vol, r_local = jax.random.split(jax.random.fold_in(rng, 1))
        x_full, vol_state = volatility.sample(r_vol, state.vol_state)  # (K,)
        mask = selection_mask(idx, K)
        success = x_full[idx]

        cohort_params, stats = vlocal(state.params, batches, step_mask, jax.random.split(r_local, fl_cfg.k))
        new_params = aggregate(
            state.params,
            cohort_params,
            success,
            data_sizes,
            total_data,
            K,
            agg_scheme,
            epochs=epochs,
            sel_probs=p[idx],
        )
        new_e3cs, new_ucb, loss_cache = _selector_update(
            state, fl_cfg, idx, p, capped, mask, x_full, sigma, stats["local_loss"]
        )
        n_succ = jnp.sum(success)
        metrics = {
            "cep": state.cep + n_succ,
            "n_success": n_succ,
            "mean_local_loss": jnp.mean(stats["local_loss"]),
            "sigma": sigma,
        }
        new_state = ServerState(
            params=new_params,
            e3cs=new_e3cs,
            ucb=new_ucb,
            loss_cache=loss_cache,
            vol_state=vol_state,
            t=state.t + 1,
            sel_counts=state.sel_counts + mask,
            cep=state.cep + n_succ,
            succ_hist=state.succ_hist + n_succ,
        )
        return new_state, metrics

    return select, round_fn


def make_async_cohort_round(
    model,
    fl_cfg,
    quota_fn,
    lag_model,
    rho=None,
    spmd_axes=None,
    aggregation: Optional[str] = None,
    select=None,
):
    """Staleness-aware variant of ``make_cohort_round``.

    ``lag_model`` draws per-client completion lags (``repro.core.volatility``
    lag protocol: int32, 0 = on time, l>=1 = late, negative = dead).  The
    jitted ``round_fn`` aggregates on-time deltas immediately and returns the
    decayed late contributions as a third output — a pytree with a leading
    ``(S,)`` axis, slice ``s`` due ``s+1`` rounds later — which the host loop
    schedules and applies when they arrive (``FLServer.run``).  The selector
    keeps the paper's deadline-based feedback: it observes the on-time bits
    ``1{lag == 0}``, matching the async scan engine's semantics.
    """
    S = int(fl_cfg.staleness_rounds)
    alpha = float(fl_cfg.staleness_alpha)
    opt = sgd(fl_cfg.lr, fl_cfg.momentum)
    local = make_local_update(model, opt, fl_cfg.local_update, fl_cfg.prox_coef)
    vlocal = jax.vmap(local, in_axes=(None, 0, 0, 0), spmd_axis_name=spmd_axes)
    agg_scheme = aggregation or fl_cfg.aggregation
    select = select if select is not None else make_select_fn(fl_cfg, quota_fn, rho)

    def round_fn(state: ServerState, idx, p, capped, sigma, batches, step_mask, data_sizes, total_data, epochs, rng):
        K = fl_cfg.K
        r_vol, r_local = jax.random.split(jax.random.fold_in(rng, 1))
        lag_full, vol_state = lag_model.sample(r_vol, state.vol_state)  # (K,) int32
        x_full = (lag_full == 0).astype(jnp.float32)  # deadline-based feedback
        mask = selection_mask(idx, K)
        success = x_full[idx]
        lag_sel = lag_full[idx]

        cohort_params, stats = vlocal(state.params, batches, step_mask, jax.random.split(r_local, fl_cfg.k))
        new_params, late_deltas = aggregate_async(
            state.params,
            cohort_params,
            lag_sel,
            data_sizes,
            total_data,
            K,
            agg_scheme,
            alpha=alpha,
            staleness=S,
            epochs=epochs,
            sel_probs=p[idx],
        )
        new_e3cs, new_ucb, loss_cache = _selector_update(
            state, fl_cfg, idx, p, capped, mask, x_full, sigma, stats["local_loss"]
        )
        n_succ = jnp.sum(success)
        n_late = jnp.sum(((lag_sel >= 1) & (lag_sel <= S)).astype(jnp.float32))
        metrics = {
            "cep": state.cep + n_succ,
            "n_success": n_succ,
            "n_late": n_late,
            "mean_local_loss": jnp.mean(stats["local_loss"]),
            "sigma": sigma,
        }
        new_state = ServerState(
            params=new_params,
            e3cs=new_e3cs,
            ucb=new_ucb,
            loss_cache=loss_cache,
            vol_state=vol_state,
            t=state.t + 1,
            sel_counts=state.sel_counts + mask,
            cep=state.cep + n_succ,
            succ_hist=state.succ_hist + n_succ,
        )
        return new_state, metrics, late_deltas

    return select, round_fn


def make_silo_steps(model, fl_cfg):
    """Huge-arch path: one client at a time on the full mesh.

    ``local_step(params, opt_state, batch, step) -> (params, opt_state, loss)``
    ``agg_accum(acc, local, global, w) -> acc``   (delta accumulation)
    ``agg_apply(global, acc) -> new_global``
    """
    opt = sgd(fl_cfg.lr, fl_cfg.momentum)

    def local_step(params, opt_state, batch, step, rng):
        (loss, metrics), grads = jax.value_and_grad(model.loss, has_aux=True)(params, batch, rng)
        params, opt_state = opt.update(params, grads, opt_state, step)
        return params, opt_state, loss

    def agg_accum(acc, local_params, global_params, w):
        return jax.tree.map(
            lambda a, l, g: a + w * (l.astype(jnp.float32) - g.astype(jnp.float32)), acc, local_params, global_params
        )

    def agg_apply(global_params, acc):
        return jax.tree.map(lambda g, a: (g.astype(jnp.float32) + a).astype(g.dtype), global_params, acc)

    return local_step, opt.init, agg_accum, agg_apply
