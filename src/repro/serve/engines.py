"""Serving engines: the compiled selection backends behind the transport.

Two backends, one interface (``admit`` / ``retire`` / ``tick`` / ``meta`` /
``arrays``):

* :class:`SlotEngine` — the multi-tenant **streaming batcher** backend.  J
  tenant jobs live as padding-mask *slots* of one ``(J, K_max)``-packed
  vmapped ``repro.engine.multi_job`` step, so a whole fleet tick is ONE
  device dispatch.  Admitting and retiring jobs edit slot rows
  (``slot_admit`` / ``slot_retire``) — data changes, shapes don't, so
  join/leave never recompiles.  When every slot is occupied the batch grows
  along a fixed **bucket ladder** (4, 8, 16, ... slots): the compile cache
  holds at most one step per bucket size, bounding compilation no matter how
  many jobs churn through.  ``staleness=S`` adds the bounded ``(J, S,
  K_max)`` late-credit ring from ``repro.engine.round_program`` (selector
  feedback stays deadline-based, the paper's policy; the ring is CEP/credit
  accounting).
* :class:`ShardedEngine` — the fleet-scale backend: each job is a full
  ``RoundProgram`` with the K axis sharded over the host mesh
  (``mesh=D``), compiled as a donated-state single-round step
  (``build_runner(carry_key=True, scan_length=1)``) so successive ticks
  resume the horizon bit-identically — the same contract the chunk-streamed
  replay path pins.  ``staleness=S`` serves the sharded-*async* composition,
  rings carried per job.  Jobs with the same geometry share one compiled
  step.

Both backends derive each job's PRNG stream from the job's own ``seed`` and
its own round counter — never from wall-clock, server ticks, or co-tenants
— so a job's selection sequence is a pure function of (spec, feedback
history).  That is the property that makes three things fall out:

* **batching invariance** — a job's cohorts are bit-identical whether it
  ticks alone or coalesced with any set of co-tenants;
* **elastic restart** — ``arrays()`` / ``load_arrays`` round-trip the whole
  evolving state (selector weights, round counters, PRNG keys, staleness
  and late-credit rings) through ``repro.checkpoint``, and a restored
  server continues bit-identically mid-horizon (``tests/test_serve.py``);
* **replayability** — a client that logs its feedback can re-derive every
  cohort the server ever issued.

Feedback is the population availability vector for the round being issued
(the paper's volatility bits), as completion-lag codes: 0 = on time,
``1..S`` = late, ``DEAD_LAG`` = never.  See ``docs/serving.md``.

Both backends record the same host spans of a tick into ``spans`` (a
``repro.obs.trace.SpanTimer``: the process-wide ``SPANS``, or the recorder
of the ``SelectionServer`` that serves the engine): ``engine.copy_in`` (host row prep and
the host-to-device copy), ``engine.launch`` (the asynchronous dispatch of
the compiled step), ``engine.wait`` (the host blocked on the device: the
NaN/inf guard), ``engine.copy_out`` (device-to-host reads of the results)
and ``engine.cohort`` (host post-processing), and three counters:
``engine.ticks`` (jobs stepped), ``engine.syncs`` (blocking device-to-host
reads) and ``engine.copy_bytes`` (bytes copied between host and device).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.volatility import DEAD_LAG
from repro.engine.multi_job import (
    MultiJobConfig,
    MultiJobState,
    make_multi_job,
    pad_slots,
    slot_admit,
    slot_retire,
)
from repro.engine.round_program import staleness_ring_step
from repro.obs.trace import SPANS

from . import protocol

__all__ = [
    "JobSpec",
    "CapacityError",
    "NumericsError",
    "SlotEngine",
    "ShardedEngine",
    "engine_from_meta",
]

assert protocol.DEAD_LAG == DEAD_LAG, "wire and engine dead-lag sentinels drifted"


@dataclasses.dataclass(frozen=True)
class JobSpec:
    """One tenant job's declaration, as posted with the ``admit`` op.

    ``sigma_frac`` is the fairness floor as a fraction of the uniform rate
    ``k/K`` (``sigma = sigma_frac * k / K``); ``rounds`` is the job's
    declared horizon — the :class:`ShardedEngine` quota schedule spans it
    (the :class:`SlotEngine` holds sigma constant, the ``multi_job``
    semantics).  ``seed`` fully determines the job's PRNG stream.
    """

    K: int
    k: int
    rounds: int = 400
    sigma_frac: float = 0.5
    eta: float = 0.5
    quota: str = "const"
    seed: int = 0

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, obj: dict) -> "JobSpec":
        fields = {f.name for f in dataclasses.fields(cls)}
        unknown = set(obj) - fields
        if unknown:
            raise ValueError(f"unknown JobSpec fields {sorted(unknown)}")
        return cls(**{k: v for k, v in obj.items() if k in fields})


class CapacityError(RuntimeError):
    """No free slot and the bucket ladder is exhausted — shed the admit."""


class NumericsError(RuntimeError):
    """A selector update produced NaN/inf log-weights.  The update was
    **refused** — engine state is unchanged — so numerical blowup can never
    be silently checkpointed; the transport surfaces the refusal as an
    ``error: "numerics"`` response plus an alert."""


def _key_array(seed: int) -> jax.Array:
    return jax.random.PRNGKey(int(seed))


# ---------------------------------------------------------------------------
# SlotEngine — the streaming-batcher backend
# ---------------------------------------------------------------------------


class SlotEngine:
    """Multi-tenant vmapped engine with padding-mask slots (see module doc).

    ``buckets`` is the slot-count ladder; the engine starts at the smallest
    bucket and grows (``pad_slots``) when admits exceed capacity, paying one
    recompile per distinct bucket size ever reached.  ``k_cap`` bounds every
    job's cohort (the padded top-k width is static in the compiled step).
    """

    kind = "slots"

    def __init__(
        self,
        K_max: int = 4096,
        k_cap: Optional[int] = None,
        staleness: int = 0,
        alpha: float = 0.5,
        buckets: Sequence[int] = (4, 8, 16, 32, 64),
        n_iters: int = 48,
        tile: int = 8192,
    ):
        if not buckets or list(buckets) != sorted(set(int(b) for b in buckets)):
            raise ValueError(f"buckets must be a strictly increasing ladder, got {buckets!r}")
        self.K_max = int(K_max)
        self.k_cap = int(k_cap if k_cap is not None else max(8, K_max // 8))
        self.staleness = int(staleness)
        self.alpha = float(alpha)
        self.buckets = tuple(int(b) for b in buckets)
        self.n_iters, self.tile = int(n_iters), int(tile)
        self._steps: dict = {}  # J -> jitted step (bounded by the ladder)
        self._job_step = make_multi_job(self.k_cap, n_iters=self.n_iters, tile=self.tile)[0]
        J = self.buckets[0]
        self.cfg = MultiJobConfig(
            k=jnp.ones((J,), jnp.int32),
            sigma=jnp.zeros((J,), jnp.float32),
            eta=jnp.zeros((J,), jnp.float32),
            active=jnp.zeros((J, self.K_max), jnp.float32),
        )
        self.state = MultiJobState(
            logw=jnp.zeros((J, self.K_max), jnp.float32), t=jnp.zeros((J,), jnp.int32)
        )
        self.pending = jnp.zeros((J, self.staleness, self.K_max), jnp.float32)
        self.base_keys = jnp.stack([_key_array(0)] * J)
        self.jobs: Dict[int, dict] = {}  # uid -> {"slot": int, "spec": JobSpec}
        self._next_uid = 0
        self.faults = None  # chaos hook (repro.serve.faults.FaultPlan) or None
        self.spans = SPANS  # a SelectionServer hands its own recorder

    # -- capacity ---------------------------------------------------------

    @property
    def n_slots(self) -> int:
        return self.cfg.active.shape[0]

    def _free_slot(self) -> int:
        used = {j["slot"] for j in self.jobs.values()}
        for s in range(self.n_slots):
            if s not in used:
                return s
        self._grow()
        return len(used)

    def _grow(self) -> None:
        ladder = [b for b in self.buckets if b > self.n_slots]
        if not ladder:
            raise CapacityError(
                f"all {self.n_slots} slots occupied and the bucket ladder "
                f"{self.buckets} is exhausted"
            )
        new_J = ladder[0]
        pad = new_J - self.n_slots
        self.cfg, self.state = pad_slots(self.cfg, self.state, new_J)
        self.pending = jnp.pad(self.pending, ((0, pad), (0, 0), (0, 0)))
        self.base_keys = jnp.concatenate(
            [self.base_keys, jnp.stack([_key_array(0)] * pad)]
        )

    # -- lifecycle --------------------------------------------------------

    def admit(self, spec: JobSpec) -> int:
        if spec.K > self.K_max:
            raise ValueError(f"job K={spec.K} exceeds the server's K_max={self.K_max}")
        if spec.k > self.k_cap:
            raise ValueError(f"job k={spec.k} exceeds the server's cohort cap k_cap={self.k_cap}")
        slot = self._free_slot()
        uid = self._next_uid
        self._next_uid += 1
        self.cfg = slot_admit(self.cfg, slot, spec.K, spec.k, spec.sigma_frac, spec.eta)
        self.state = MultiJobState(
            logw=self.state.logw.at[slot].set(0.0),
            t=self.state.t.at[slot].set(0),
        )
        if self.staleness:
            self.pending = self.pending.at[slot].set(0.0)
        self.base_keys = self.base_keys.at[slot].set(_key_array(spec.seed))
        self.jobs[uid] = {"slot": slot, "spec": spec}
        return uid

    def retire(self, uid: int) -> None:
        job = self.jobs.pop(uid)
        self.cfg = slot_retire(self.cfg, job["slot"])

    def job_round(self, uid: int) -> int:
        """The round the job's NEXT tick will serve (the idempotency cursor
        the transport's retry cache compares request rounds against)."""
        t = np.asarray(self.state.t)
        self.spans.add("engine.syncs")
        self.spans.add("engine.copy_bytes", t.nbytes)
        return int(t[self.jobs[uid]["slot"]])

    # -- the batched serving step ----------------------------------------

    def _build_step(self, J: int):
        """One donated-state compiled dispatch for a J-slot batch: per-job
        keys derive from each job's own round counter, non-participating
        slots are gated back to their previous state (weights, counter and
        ring all unchanged — their ring must not shift on other tenants'
        ticks).  A non-finite updated log-weight anywhere gates the WHOLE
        batch back to its previous state (the NaN/inf guard — the gating
        must live inside the step because the inputs are donated) and is
        reported through the returned ``finite`` flag."""
        job_step, S, alpha = self._job_step, self.staleness, self.alpha

        def step(cfg, logw, t, pending, base_keys, lag, participate):
            keys = jax.vmap(jax.random.fold_in)(base_keys, t)
            x = (lag == 0).astype(jnp.float32) * cfg.active
            new_logw, new_t, out = jax.vmap(job_step)(cfg, logw, t, keys, x)
            # dead slots legitimately step to NaN (empty active mask) and are
            # gated out below — only participating slots can refuse the batch.
            # Only the PERSISTENT state (logw/t/pending) is gated on the
            # reduction: outputs are discarded on refusal anyway, and keeping
            # them off the reduction's critical path keeps the guard cheap.
            finite = jnp.all(jnp.isfinite(new_logw) | ~participate[:, None])
            pj = participate.astype(jnp.float32)
            keep = pj * finite.astype(jnp.float32)
            mask = out["mask"] * pj[:, None]
            arriving, new_pending = staleness_ring_step(pending, mask, lag, S, alpha)
            arriving = arriving * pj[:, None]
            logw = jnp.where(keep[:, None] > 0, new_logw, logw)
            t = jnp.where(participate & finite, new_t, t)
            if S:
                new_pending = jnp.where(keep[:, None, None] > 0, new_pending, pending)
            idx = jnp.where(participate[:, None], out["idx"], -1)
            on_time = jnp.sum(mask * x, axis=1)
            stale = jnp.sum(arriving, axis=1)
            return logw, t, new_pending, idx, on_time, stale, finite

        return jax.jit(step, donate_argnums=(1, 2, 3))

    def tick(self, items: List[Tuple[int, np.ndarray]]) -> Dict[int, dict]:
        """One batched dispatch: ``items`` maps job uid -> this round's lag
        codes ``(K_job,)`` (each uid at most once).  Returns per-uid results
        ``{"round", "cohort", "on_time", "stale"}``."""
        if self.faults is not None:
            self.faults.on_engine_step()
        spans = self.spans
        J = self.n_slots
        if len({u for u, _ in items}) != len(items):
            raise ValueError("duplicate job uid in one batch (coalesce across dispatches)")
        with spans.span("engine.copy_in"):
            participate = np.zeros((J,), bool)
            lag = np.zeros((J, self.K_max), np.int32)
            rounds_before = np.asarray(self.state.t)
            for uid, row in items:
                job = self.jobs[uid]
                slot, K = job["slot"], job["spec"].K
                row = np.asarray(row, np.int32).reshape(-1)
                if row.shape[0] != K:
                    raise ValueError(f"job {uid}: feedback has {row.shape[0]} entries, K={K}")
                participate[slot] = True
                lag[slot, :K] = row
            lag_d, participate_d = jnp.asarray(lag), jnp.asarray(participate)
        spans.add("engine.ticks", len(items))
        spans.add("engine.syncs")  # rounds_before
        spans.add("engine.copy_bytes", rounds_before.nbytes + lag.nbytes + participate.nbytes)
        with spans.span("engine.launch"):
            step = self._steps.get(J)
            if step is None:
                step = self._steps[J] = self._build_step(J)
            logw, t, pending, idx, on_time, stale, finite = step(
                self.cfg, self.state.logw, self.state.t, self.pending,
                self.base_keys, lag_d, participate_d,
            )
        # reassign before any raise: the step donated the old buffers, and
        # on a refused (non-finite) update the state outputs ARE the old state
        self.state = MultiJobState(logw=logw, t=t)
        self.pending = pending
        with spans.span("engine.wait"):
            jax.block_until_ready(finite)
        with spans.span("engine.copy_out"):
            # one host transfer for everything the response needs + the guard flag
            idx, on_time, stale, finite = jax.device_get((idx, on_time, stale, finite))
        spans.add("engine.syncs")
        spans.add("engine.copy_bytes", idx.nbytes + on_time.nbytes + stale.nbytes + finite.nbytes)
        if not bool(finite):
            raise NumericsError(
                "selector update produced non-finite log-weights; update refused"
            )
        with spans.span("engine.cohort"):
            results = {}
            for uid, _ in items:
                slot = self.jobs[uid]["slot"]
                cohort = idx[slot][idx[slot] >= 0]
                results[uid] = {
                    "round": int(rounds_before[slot]),
                    "cohort": cohort.tolist(),
                    "on_time": float(on_time[slot]),
                    "stale": float(stale[slot]),
                }
        return results

    # -- checkpoint surface ----------------------------------------------

    def meta(self) -> dict:
        """The static half of a checkpoint: everything needed to rebuild an
        identically-shaped engine (``engine_from_meta``) before restoring
        the array state into it."""
        return {
            "kind": self.kind,
            "K_max": self.K_max,
            "k_cap": self.k_cap,
            "staleness": self.staleness,
            "alpha": self.alpha,
            "buckets": list(self.buckets),
            "n_iters": self.n_iters,
            "tile": self.tile,
            "n_slots": self.n_slots,
            "next_uid": self._next_uid,
            "jobs": [
                {"uid": uid, "slot": j["slot"], "spec": j["spec"].to_json()}
                for uid, j in sorted(self.jobs.items())
            ],
        }

    def arrays(self):
        """The evolving array state (the checkpoint payload): weights, round
        counters, the staleness ring and the per-slot PRNG bases."""
        return {
            "logw": self.state.logw,
            "t": self.state.t,
            "pending": self.pending,
            "base_keys": self.base_keys,
        }

    def load_arrays(self, arrays) -> None:
        self.state = MultiJobState(logw=jnp.asarray(arrays["logw"]), t=jnp.asarray(arrays["t"]))
        self.pending = jnp.asarray(arrays["pending"])
        self.base_keys = jnp.asarray(arrays["base_keys"])

    @classmethod
    def from_meta(cls, meta: dict) -> "SlotEngine":
        eng = cls(
            K_max=meta["K_max"], k_cap=meta["k_cap"], staleness=meta["staleness"],
            alpha=meta["alpha"], buckets=meta["buckets"], n_iters=meta["n_iters"],
            tile=meta["tile"],
        )
        while eng.n_slots < meta["n_slots"]:
            eng._grow()
        for row in meta["jobs"]:
            spec = JobSpec.from_json(row["spec"])
            eng.cfg = slot_admit(eng.cfg, row["slot"], spec.K, spec.k, spec.sigma_frac, spec.eta)
            eng.jobs[row["uid"]] = {"slot": row["slot"], "spec": spec}
        eng._next_uid = meta["next_uid"]
        return eng


# ---------------------------------------------------------------------------
# ShardedEngine — fleet-scale jobs, one RoundProgram each
# ---------------------------------------------------------------------------


class ShardedEngine:
    """Each admitted job is one K-sharded ``RoundProgram`` stepped a round
    per tick (see module doc).  ``staleness=S`` serves sharded-async rounds
    with the ``(S, K/D)`` rings carried per job; ``feedback`` picks the
    selector policy (``"deadline"`` or ``"late_credit"``)."""

    kind = "sharded"

    def __init__(
        self,
        D: Optional[int] = None,
        staleness: int = 0,
        alpha: float = 0.5,
        block: int = 4,
        feedback: str = "deadline",
    ):
        from repro.launch.mesh import make_host_mesh

        self.mesh = make_host_mesh(D)
        self.D = int(self.mesh.devices.size)
        self.staleness = int(staleness)
        self.alpha = float(alpha)
        self.block = int(block)
        self.feedback = feedback
        self._runners: dict = {}  # geometry key -> (run, state0, program)
        self.jobs: Dict[int, dict] = {}
        self._next_uid = 0
        self.faults = None  # chaos hook (repro.serve.faults.FaultPlan) or None
        self.spans = SPANS  # a SelectionServer hands its own recorder

    def _runner(self, spec: JobSpec):
        from repro.configs.base import FLConfig
        from repro.engine.round_program import RoundProgram

        geom = (spec.K, spec.k, spec.rounds, spec.quota, spec.sigma_frac, spec.eta)
        hit = self._runners.get(geom)
        if hit is not None:
            return hit
        fl = FLConfig(
            K=spec.K, k=spec.k, rounds=spec.rounds, scheme="e3cs", quota=spec.quota,
            quota_frac=spec.sigma_frac, eta=spec.eta, allocator="bisect",
            staleness_rounds=self.staleness, staleness_alpha=self.alpha,
        )
        program = RoundProgram.from_config(
            fl, mesh=self.mesh, override="dense", feedback=self.feedback, block=self.block
        )
        run, state0 = program.build_runner(outputs="full", carry_key=True, scan_length=1)
        self._runners[geom] = (run, state0, program)
        return self._runners[geom]

    def admit(self, spec: JobSpec) -> int:
        # geometry bounds (k <= K_pad/D for the per-shard top-k) are
        # enforced by RoundProgram.from_config inside _runner
        run, state0, program = self._runner(spec)
        uid = self._next_uid
        self._next_uid += 1
        self.jobs[uid] = {
            "spec": spec,
            "state": state0,
            "key": _key_array(spec.seed),
            "rings": program.init_rings() if self.staleness else (),
            "t": 0,
        }
        return uid

    def retire(self, uid: int) -> None:
        del self.jobs[uid]

    def job_round(self, uid: int) -> int:
        """The round the job's NEXT tick will serve (the idempotency cursor
        the transport's retry cache compares request rounds against)."""
        return int(self.jobs[uid]["t"])

    def tick(self, items: List[Tuple[int, np.ndarray]]) -> Dict[int, dict]:
        """Advance each job one round (dispatched per job — the K axis is
        already device-parallel; there is no J axis to batch here)."""
        if self.faults is not None:
            self.faults.on_engine_step()
        spans = self.spans
        results = {}
        for uid, row in items:
            job = self.jobs[uid]
            spec: JobSpec = job["spec"]
            run, _, _ = self._runner(spec)
            with spans.span("engine.copy_in"):
                row = np.asarray(row, np.int32).reshape(-1)
                if row.shape[0] != spec.K:
                    raise ValueError(f"job {uid}: feedback has {row.shape[0]} entries, K={spec.K}")
                if self.staleness:
                    xs = jnp.asarray(row, jnp.int32)[None, :]
                else:
                    xs = jnp.asarray(row == 0, jnp.float32)[None, :]
            with spans.span("engine.launch"):
                if self.staleness:
                    state, key, rings, masks, lags, ps, sigmas, arrived = run(
                        job["state"], job["key"], job["rings"], xs
                    )
                else:
                    state, key, masks, xbits, ps, sigmas = run(job["state"], job["key"], xs)
                    rings = None
            spans.add("engine.ticks")
            spans.add("engine.syncs")  # the guard
            spans.add("engine.copy_bytes", xs.nbytes + 1)
            # NaN/inf guard: the runner does not donate, so the old state is
            # intact — refuse the update before assigning anything
            with spans.span("engine.wait"):
                finite = bool(jnp.all(jnp.isfinite(state.e3cs.logw)))
            if not finite:
                raise NumericsError(
                    f"job {uid}: selector update produced non-finite log-weights; "
                    "update refused"
                )
            if rings is not None:
                job["rings"] = rings
            job["state"], job["key"] = state, key
            with spans.span("engine.copy_out"):
                mask = np.asarray(masks[0][: spec.K])
                late = np.asarray(arrived[0][: spec.K]) if self.staleness else None
            reads = (mask,) if late is None else (mask, late)
            spans.add("engine.syncs", len(reads))
            spans.add("engine.copy_bytes", sum(a.nbytes for a in reads))
            with spans.span("engine.cohort"):
                stale = float(late.sum()) if late is not None else 0.0
                cohort = np.nonzero(mask > 0)[0]
                on_time = float((mask * (row == 0)).sum())
                results[uid] = {
                    "round": job["t"],
                    "cohort": cohort.tolist(),
                    "on_time": on_time,
                    "stale": stale,
                }
            job["t"] += 1
        return results

    # -- checkpoint surface ----------------------------------------------

    def meta(self) -> dict:
        return {
            "kind": self.kind,
            "D": self.D,
            "staleness": self.staleness,
            "alpha": self.alpha,
            "block": self.block,
            "feedback": self.feedback,
            "next_uid": self._next_uid,
            "jobs": [
                {"uid": uid, "t": j["t"], "spec": j["spec"].to_json()}
                for uid, j in sorted(self.jobs.items())
            ],
        }

    def arrays(self):
        """Per-job evolving state keyed by uid (string keys: the checkpoint
        container round-trips through msgpack): the full ``ServerState``
        pytree, the carried PRNG key, and the staleness/late-credit rings."""
        return {
            str(uid): {"state": j["state"], "key": j["key"], "rings": list(j["rings"])}
            for uid, j in self.jobs.items()
        }

    def load_arrays(self, arrays) -> None:
        for uid, job in self.jobs.items():
            blob = arrays[str(uid)]
            job["state"] = jax.tree.map(jnp.asarray, blob["state"])
            job["key"] = jnp.asarray(blob["key"])
            job["rings"] = tuple(jnp.asarray(r) for r in blob["rings"])

    @classmethod
    def from_meta(cls, meta: dict) -> "ShardedEngine":
        eng = cls(
            D=meta["D"], staleness=meta["staleness"], alpha=meta["alpha"],
            block=meta["block"], feedback=meta["feedback"],
        )
        for row in meta["jobs"]:
            spec = JobSpec.from_json(row["spec"])
            uid = eng.admit(spec)
            eng.jobs[uid]["t"] = row["t"]
            if uid != row["uid"]:  # preserve original uids across restarts
                eng.jobs[row["uid"]] = eng.jobs.pop(uid)
        eng._next_uid = meta["next_uid"]
        return eng


def engine_from_meta(meta: dict):
    """Rebuild an engine shell from its checkpoint meta (static config +
    job table); the caller then restores the array state into it
    (``repro.serve.state.load_server`` does both)."""
    kinds = {SlotEngine.kind: SlotEngine, ShardedEngine.kind: ShardedEngine}
    kind = meta.get("kind")
    if kind not in kinds:
        raise ValueError(f"unknown engine kind {kind!r} (want one of {sorted(kinds)})")
    return kinds[kind].from_meta(meta)
