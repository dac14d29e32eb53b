"""Serving front end tests: protocol, batcher, elastic restart, transport.

What is pinned here, and why it matters:

* **wire framing** — frames round-trip; truncation/oversize fail loudly,
  and fuzzed garbage (random bytes, truncated frames, oversized prefixes,
  mid-frame disconnects) never leaves a dead handler behind.
* **batching invariance** — a job ticked alone produces bit-identical
  cohorts to the same job ticked coalesced with co-tenants (the per-job
  PRNG contract the whole batcher rests on).
* **elastic restart** — a server checkpointed mid-horizon and restored
  into a fresh process continues bit-identically to an uninterrupted run,
  for both backends, sync and async (S=2).  This is the acceptance bar of
  ROADMAP item 2: the loopback test drives 2 jobs >= 50 rounds through the
  compiled sharded-async engine across a kill/restore.
* **failure modes** — full slot bucket sheds with ``capacity``; full
  admission queue sheds with ``shed``; expired requests fail with
  ``timeout``; draining servers answer what they accepted; a hung engine
  thread at close is surfaced, not silently leaked.
* **fault tolerance** — crash-safe checkpoints (sha256 walk-back past
  corrupt stems, retention), idempotent round-tagged ticks (replay answers
  from cache, desync carries the expected round), client retries with
  seeded backoff, the non-finite-update guard, and the supervised restart
  loop — capped by the seeded chaos run: engine crash + corrupted
  checkpoint + dropped connections on a sharded-async horizon, with every
  cohort bit-identical to a fault-free run.
"""
import json
import socket
import struct
import threading
import time

import numpy as np
import pytest

import jax

from repro.serve import (
    CapacityError,
    FaultPlan,
    JobSpec,
    SelectionServer,
    ServeClient,
    ServeError,
    ShardedEngine,
    SlotEngine,
    latest_server_checkpoint,
    load_server,
    save_server,
    validate_stem,
)
from repro.serve import protocol

needs8 = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8"
)


def _lags(rng, K, S=2):
    """A volatile round: most on time, some late (1..S), some never."""
    l = rng.integers(0, S + 2, K).astype(np.int32)
    return np.where(l > S, protocol.DEAD_LAG, l)


# ---------------------------------------------------------------------------
# protocol
# ---------------------------------------------------------------------------


def test_protocol_frame_roundtrip():
    a, b = socket.socketpair()
    try:
        msg = {"op": "tick", "job": 3, "xb": protocol.encode_bits(np.ones(17))}
        protocol.send_message(a, msg)
        assert protocol.recv_message(b) == msg
        a.close()
        with pytest.raises(protocol.ConnectionClosed):
            protocol.recv_message(b)
    finally:
        b.close()


def test_protocol_mid_frame_eof_is_error():
    a, b = socket.socketpair()
    try:
        a.sendall(b"\x00\x00\x00\x10{\"tru")  # announce 16 bytes, send 6
        a.close()
        with pytest.raises(protocol.ProtocolError):
            protocol.recv_message(b)
    finally:
        b.close()


def test_protocol_feedback_encodings():
    bits = np.asarray([1, 0, 1, 1, 0, 0, 1, 0, 1])
    out = protocol.decode_bits(protocol.encode_bits(bits), 9)
    np.testing.assert_array_equal(out, bits.astype(np.float32))
    lags = np.asarray([0, 1, 2, protocol.DEAD_LAG, 0])
    out = protocol.decode_lags(protocol.encode_lags(lags), 5)
    np.testing.assert_array_equal(out, lags)
    # sync bits normalise to {0, DEAD_LAG} lag codes
    req = {"xb": protocol.encode_bits(bits)}
    lag = protocol.feedback_lags(req, 9, staleness=0)
    np.testing.assert_array_equal(lag == 0, bits.astype(bool))
    assert set(np.unique(lag)) <= {0, protocol.DEAD_LAG}


# ---------------------------------------------------------------------------
# SlotEngine: batching invariance, bucket ladder, restart
# ---------------------------------------------------------------------------


def test_slot_engine_alone_vs_batched_bit_identical():
    """Co-tenancy must not perturb a job: same spec, same feedback, same
    cohorts whether the job ticks alone or batched with others."""
    rng = np.random.default_rng(0)
    spec = JobSpec(K=48, k=6, seed=13)
    feed = [_lags(rng, 48) for _ in range(8)]

    alone = SlotEngine(K_max=64, k_cap=8, staleness=2, buckets=(4,))
    ua = alone.admit(spec)
    solo = [alone.tick([(ua, f)])[ua]["cohort"] for f in feed]

    packed = SlotEngine(K_max=64, k_cap=8, staleness=2, buckets=(4,))
    u0 = packed.admit(JobSpec(K=64, k=8, seed=1))
    ub = packed.admit(spec)
    u2 = packed.admit(JobSpec(K=32, k=4, seed=2))
    both = []
    for f in feed:
        r = packed.tick([(u0, _lags(rng, 64)), (ub, f), (u2, _lags(rng, 32))])
        both.append(r[ub]["cohort"])
    assert solo == both


def test_slot_engine_bucket_ladder_and_capacity():
    eng = SlotEngine(K_max=16, k_cap=4, buckets=(2, 4))
    uids = [eng.admit(JobSpec(K=16, k=2, seed=i)) for i in range(2)]
    assert eng.n_slots == 2
    uids.append(eng.admit(JobSpec(K=16, k=2, seed=9)))  # grows 2 -> 4
    assert eng.n_slots == 4
    for i in range(3, 4):
        uids.append(eng.admit(JobSpec(K=16, k=2, seed=i)))
    with pytest.raises(CapacityError):
        eng.admit(JobSpec(K=16, k=2, seed=99))  # ladder exhausted
    # retire frees a slot for the next admit, ladder unchanged
    eng.retire(uids[1])
    eng.admit(JobSpec(K=16, k=2, seed=100))
    assert eng.n_slots == 4


def test_slot_engine_growth_preserves_streams():
    """Bucket growth is invisible to live jobs: their selection streams
    continue as if the batch had never been resized."""
    rng = np.random.default_rng(1)
    spec = JobSpec(K=24, k=3, seed=21)
    feed = [_lags(rng, 24, S=0) for _ in range(6)]

    ref = SlotEngine(K_max=32, k_cap=4, buckets=(2, 4))
    ur = ref.admit(spec)
    want = [ref.tick([(ur, f)])[ur]["cohort"] for f in feed]

    grow = SlotEngine(K_max=32, k_cap=4, buckets=(2, 4))
    ug = grow.admit(spec)
    got = [grow.tick([(ug, f)])[ug]["cohort"] for f in feed[:3]]
    grow.admit(JobSpec(K=32, k=4, seed=1))
    grow.admit(JobSpec(K=32, k=4, seed=2))  # triggers 2 -> 4 growth
    got += [grow.tick([(ug, f)])[ug]["cohort"] for f in feed[3:]]
    assert want == got


@pytest.mark.parametrize("staleness", [0, 2])
def test_slot_engine_restart_bit_identical(tmp_path, staleness):
    """Checkpoint mid-horizon, restore, continue: cohorts match an
    uninterrupted run exactly (sync and async S=2)."""
    rng = np.random.default_rng(2)
    specs = [JobSpec(K=40, k=5, seed=3), JobSpec(K=24, k=4, seed=4)]
    feed = [[_lags(rng, s.K, S=staleness) for _ in range(12)] for s in specs]

    def fresh():
        eng = SlotEngine(K_max=64, k_cap=8, staleness=staleness, buckets=(4,))
        return eng, [eng.admit(s) for s in specs]

    ref, uref = fresh()
    want = [ref.tick([(u, fr[t]) for u, fr in zip(uref, feed)]) for t in range(12)]

    eng, uids = fresh()
    for t in range(6):
        eng.tick([(u, fr[t]) for u, fr in zip(uids, feed)])
    stem = save_server(str(tmp_path), eng, step=6)
    assert latest_server_checkpoint(str(tmp_path)) == stem
    eng2, step = load_server(stem)
    assert step == 6
    for t in range(6, 12):
        got = eng2.tick([(u, fr[t]) for u, fr in zip(uids, feed)])
        for u in uids:
            assert got[u]["cohort"] == want[t][u]["cohort"]
            assert got[u]["round"] == want[t][u]["round"]


# ---------------------------------------------------------------------------
# transport: batcher, shed, timeout, drain
# ---------------------------------------------------------------------------


def _sync_server(**kw):
    return SelectionServer(SlotEngine(K_max=32, k_cap=4, buckets=(4,)), **kw)


def test_transport_roundtrip_and_errors():
    with _sync_server() as srv:
        with ServeClient.connect(srv.address) as c:
            assert c.hello()["engine"] == "slots"
            job = c.admit(K=32, k=4, seed=1)
            out = c.tick(job, bits=np.ones(32))
            assert out["round"] == 0 and len(out["cohort"]) == 4
            with pytest.raises(ServeError) as e:
                c.tick(999, bits=np.ones(32))
            assert e.value.code == "unknown_job"
            with pytest.raises(ServeError) as e:
                c.call(op="tick", job=job)  # no feedback field
            assert e.value.code == "bad_request"
            with pytest.raises(ServeError) as e:
                c.call(op="nonsense")
            assert e.value.code == "bad_request"
            c.retire(job)
            with pytest.raises(ServeError) as e:
                c.tick(job, bits=np.ones(32))
            assert e.value.code == "unknown_job"


TICK_SPANS = {"serve.parse", "serve.queue", "serve.decode", "serve.tick", "serve.reply",
              "engine.copy_in", "engine.launch", "engine.wait", "engine.copy_out", "engine.cohort"}


@pytest.mark.parametrize("kind", ["sharded", "slots"])
def test_served_tick_records_every_span_once(kind):
    """One served sync tick records each span of its path exactly once,
    all under the request id its frame got; the engine spans hang off the
    dispatch's ``serve.tick``; the counters count what crossed; the
    ``stats`` op reports the spans."""
    from repro.obs.trace import SpanTimer

    K = 4096
    engine = ShardedEngine(D=1) if kind == "sharded" else SlotEngine(K_max=K, k_cap=16, buckets=(4,))
    spans = SpanTimer()
    with SelectionServer(engine, spans=spans) as srv:
        assert engine.spans is spans
        with ServeClient.connect(srv.address) as c:
            job = c.admit(K=K, k=16, seed=3, rounds=10)
            before = dict(spans.counters)
            out = c.tick(job, bits=np.random.default_rng(0).random(K) < 0.7)
            assert out["round"] == 0 and len(out["cohort"]) == 16
            stats = c.stats()  # the tick's reply span is recorded before this request is read
    ev = spans.events()
    (rid,) = ev["rid"][ev["name"] == "serve.decode"]
    mine = ev["rid"] == rid
    names = sorted(ev["name"][mine])
    assert names == sorted(TICK_SPANS)
    (tick_id,) = ev["id"][mine & (ev["name"] == "serve.tick")]
    engine_spans = mine & np.array([n.startswith("engine.") for n in ev["name"]])
    assert np.all(ev["parent"][engine_spans] == tick_id)
    assert np.all(ev["t1_ns"][mine] >= ev["t0_ns"][mine])
    assert set(stats["spans"]) >= TICK_SPANS and stats["spans"]["serve.tick"]["count"] == 1
    assert {"count", "p50_ms", "p95_ms", "max_ms"} == set(stats["spans"]["serve.tick"])
    assert stats["counters"]["engine.ticks"] == 1 and stats["spans_dropped"] == 0
    delta = {k: v - before.get(k, 0) for k, v in spans.counters.items()}
    if kind == "sharded":
        # float32 row in, float32 mask out, the guard's one byte
        assert delta == {"engine.ticks": 1, "engine.syncs": 2, "engine.copy_bytes": 8 * K + 1}
    else:
        assert delta["engine.ticks"] == 1 and delta["engine.syncs"] >= 2


def test_transport_concurrent_clients_batch():
    """Two clients hammering concurrently: every response is consistent and
    per-job rounds stay strictly sequential no matter how dispatches
    coalesce."""
    with _sync_server() as srv:
        rounds = {0: [], 1: []}

        def drive(i):
            with ServeClient.connect(srv.address) as c:
                job = c.admit(K=32, k=4, seed=i)
                for _ in range(20):
                    out = c.tick(job, bits=np.ones(32))
                    rounds[i].append(out["round"])
                    assert len(out["cohort"]) == 4

        threads = [threading.Thread(target=drive, args=(i,)) for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert rounds[0] == list(range(20)) and rounds[1] == list(range(20))
        assert srv.stats["ticks"] == 40


def test_transport_shed_on_full_queue():
    """A stalled engine + a bounded queue => overflow requests shed
    immediately instead of queueing into unbounded latency."""
    srv = _sync_server(max_queue=2)
    gate = threading.Event()
    real_tick = srv.engine.tick

    def slow_tick(items):
        gate.wait(10.0)
        return real_tick(items)

    srv.engine.tick = slow_tick
    with srv:
        with ServeClient.connect(srv.address) as admitc:
            job = admitc.admit(K=32, k=4, seed=1)
            results = []

            def one():
                with ServeClient.connect(srv.address) as c:
                    try:
                        c.tick(job, bits=np.ones(32))
                        results.append("ok")
                    except ServeError as e:
                        results.append(e.code)

            # first request occupies the engine thread; the next floods the
            # 2-deep queue
            threads = [threading.Thread(target=one) for _ in range(6)]
            for t in threads:
                t.start()
            import time

            time.sleep(0.5)
            gate.set()
            for t in threads:
                t.join()
        assert "shed" in results, results
        assert srv.stats["shed"] >= 1


def test_transport_timeout_expired_requests():
    """Requests older than request_timeout when dequeued fail with
    ``timeout`` and never reach the engine."""
    srv = _sync_server(request_timeout=0.0)
    with srv:
        with ServeClient.connect(srv.address) as c:
            job = c.call(op="admit", spec={"K": 32, "k": 4})["job"]
            with pytest.raises(ServeError) as e:
                c.tick(job, bits=np.ones(32))
            assert e.value.code == "timeout"
        assert srv.stats["timeouts"] == 1
        assert srv.stats["ticks"] == 0


def test_transport_drain_and_final_checkpoint(tmp_path):
    """Graceful close answers accepted work and writes a final checkpoint;
    the checkpoint restores to the drained state."""
    srv = _sync_server(ckpt_dir=str(tmp_path))
    with srv:
        with ServeClient.connect(srv.address) as c:
            job = c.admit(K=32, k=4, seed=5)
            for _ in range(3):
                c.tick(job, bits=np.ones(32))
    stem = latest_server_checkpoint(str(tmp_path))
    assert stem is not None
    eng, step = load_server(stem)
    assert step == 3 and int(np.asarray(eng.state.t)[eng.jobs[job]["slot"]]) == 3


def test_transport_draining_rejects_new_requests():
    with _sync_server() as srv:
        with ServeClient.connect(srv.address) as c:
            c.admit(K=32, k=4)
            assert c.shutdown()["ok"]
            with pytest.raises((ServeError, protocol.ProtocolError, OSError)):
                c.call(op="hello")


# ---------------------------------------------------------------------------
# protocol fuzz: garbage on the wire never leaves a dead handler behind
# ---------------------------------------------------------------------------


def test_fuzz_random_bytes_never_kill_the_server():
    """Seeded random byte blasts: each connection dies alone (error response
    or clean close); the server keeps answering well-formed clients."""
    rng = np.random.default_rng(11)
    with _sync_server() as srv:
        for _ in range(12):
            s = socket.create_connection(srv.address, timeout=5.0)
            try:
                n = int(rng.integers(1, 256))
                s.sendall(rng.integers(0, 256, n, dtype=np.uint8).tobytes())
            finally:
                s.close()
        with ServeClient.connect(srv.address) as c:
            assert c.hello()["ok"]


def test_fuzz_oversized_length_prefix():
    """A frame announcing more than MAX_MESSAGE_BYTES: error response, then
    hang up — the stream cannot be resynced."""
    with _sync_server() as srv:
        s = socket.create_connection(srv.address, timeout=5.0)
        try:
            s.sendall(struct.pack("!I", protocol.MAX_MESSAGE_BYTES + 1))
            resp = protocol.recv_message(s)
            assert resp["ok"] is False and resp["error"] == "bad_request"
            with pytest.raises((protocol.ProtocolError, OSError)):
                protocol.recv_message(s)
        finally:
            s.close()
        with ServeClient.connect(srv.address) as c:
            assert c.hello()["ok"]


def test_fuzz_truncated_frame_and_midframe_disconnect():
    """A valid header with a partial payload, then disconnect: the handler
    exits; concurrent well-formed connections are unaffected."""
    with _sync_server() as srv:
        body = json.dumps({"op": "hello"}).encode()
        for cut in (0, len(body) // 2):
            s = socket.create_connection(srv.address, timeout=5.0)
            s.sendall(struct.pack("!I", len(body)) + body[:cut])
            s.close()
        body = json.dumps({"op": "hello"}).encode()  # not-JSON payloads too
        s = socket.create_connection(srv.address, timeout=5.0)
        try:
            junk = b"\xff" * len(body)
            s.sendall(struct.pack("!I", len(junk)) + junk)
            resp = protocol.recv_message(s)
            assert resp["ok"] is False and resp["error"] == "bad_request"
        finally:
            s.close()
        with ServeClient.connect(srv.address) as c:
            assert c.hello()["ok"]


# ---------------------------------------------------------------------------
# crash-safe checkpoints: sha walk-back, retention
# ---------------------------------------------------------------------------


def test_checkpoint_walkback_and_retention(tmp_path):
    """Corrupt stems (truncation or a bit flip) fail validation and the
    restore walk-back skips them; retention prunes to the newest N stems."""
    rng = np.random.default_rng(5)
    eng = SlotEngine(K_max=32, k_cap=4, buckets=(4,))
    uid = eng.admit(JobSpec(K=32, k=4, seed=3))
    stems = []
    for step in (1, 2, 3):
        eng.tick([(uid, _lags(rng, 32, S=0))])
        stems.append(save_server(str(tmp_path), eng, step=step))
    assert all(validate_stem(s) for s in stems)
    assert latest_server_checkpoint(str(tmp_path)) == stems[2]

    # truncate the newest payload: sha mismatch, walk back one stem
    with open(stems[2] + ".ckpt", "r+b") as f:
        f.seek(0, 2)
        f.truncate(f.tell() // 2)
    assert not validate_stem(stems[2])
    assert latest_server_checkpoint(str(tmp_path)) == stems[1]

    # flip one byte in the next stem via the chaos hook: walk back again
    plan = FaultPlan(corrupt_checkpoints=(0,), corrupt_mode="bitflip")
    plan.on_checkpoint(stems[1])
    assert plan.fired()["corrupt"] == 1
    assert not validate_stem(stems[1])
    assert latest_server_checkpoint(str(tmp_path)) == stems[0]
    restored, step = load_server(stems[0])
    assert step == 1 and restored.job_round(uid) == 1

    # retention: keep=2 prunes everything but the newest 2 stems
    eng.tick([(uid, _lags(rng, 32, S=0))])
    s4 = save_server(str(tmp_path), eng, step=4, keep=2)
    import os

    left = sorted(f for f in os.listdir(str(tmp_path)) if f.endswith(".json"))
    assert len(left) == 2 and left[-1] == os.path.basename(s4) + ".json"


# ---------------------------------------------------------------------------
# idempotent ticks, client retries, numerics guard, hung engine
# ---------------------------------------------------------------------------


def test_idempotent_tick_replay_and_desync():
    """A replayed round answers from the cache (feedback NOT re-applied); a
    round that disagrees with the engine's cursor fails with the expected
    round attached."""
    with _sync_server() as srv:
        with ServeClient.connect(srv.address) as c:
            job = c.admit(K=32, k=4, seed=2)
            xb = protocol.encode_bits(np.ones(32))
            out0 = c.call(op="tick", job=job, round=0, xb=xb)
            # replay round 0 with DIFFERENT feedback: the cached response
            # comes back and the engine state is untouched
            again = c.call(op="tick", job=job, round=0,
                           xb=protocol.encode_bits(np.zeros(32)))
            assert again == out0
            assert srv.stats["replayed"] == 1
            with pytest.raises(ServeError) as e:
                c.call(op="tick", job=job, round=5, xb=xb)
            assert e.value.code == "round_desync"
            assert e.value.response["expected"] == 1
            assert c.call(op="tick", job=job, round=1, xb=xb)["round"] == 1


def test_client_retries_through_dropped_responses():
    """Fault-injected connection drops lose responses after execution; the
    retrying client reconnects, resends the same round, and the idempotency
    cache answers — the feedback stream lands exactly once."""
    plan = FaultPlan(drop_responses=(3, 5))
    srv = _sync_server(faults=plan)
    with srv:
        with ServeClient.connect(srv.address, retries=4, seed=0) as c:
            job = c.admit(K=32, k=4, seed=1)  # response 0; ticks follow
            got = [c.tick(job, bits=np.ones(32))["cohort"] for _ in range(8)]
    ref = SlotEngine(K_max=32, k_cap=4, buckets=(4,))
    u = ref.admit(JobSpec(K=32, k=4, seed=1))
    want = [ref.tick([(u, np.zeros(32, np.int32))])[u]["cohort"] for _ in range(8)]
    assert got == want
    assert plan.fired()["drop"] == 2
    assert srv.stats["replayed"] == 2 and srv.stats["ticks"] == 8


def test_numerics_guard_refuses_update():
    """A non-finite selector update is refused inside the compiled step
    (donation makes host-side rollback impossible): the request fails with
    ``numerics``, the round cursor does not advance, an alert is raised."""
    with _sync_server() as srv:
        with ServeClient.connect(srv.address) as c:
            job = c.admit(K=32, k=4, seed=1)
            c.tick(job, bits=np.ones(32))
            slot = srv.engine.jobs[job]["slot"]
            srv.engine.state = srv.engine.state._replace(
                logw=srv.engine.state.logw.at[slot, 0].set(np.nan)
            )
            with pytest.raises(ServeError) as e:
                c.tick(job, bits=np.ones(32))
            assert e.value.code == "numerics"
            stats = c.stats()["stats"]
            assert stats["numerics"] == 1
        assert srv.engine.job_round(job) == 1  # cursor did not advance
    assert any(a.rule == "numerics" for a in srv.alerts)


def test_close_surfaces_hung_engine():
    """A join that outlives stop_timeout is reported (``hung_engine`` stat),
    not silently leaked."""
    srv = _sync_server(stop_timeout=0.3)
    gate = threading.Event()
    real_tick = srv.engine.tick

    def stuck(items):
        gate.wait(30.0)
        return real_tick(items)

    srv.engine.tick = stuck
    srv.start()
    c = ServeClient.connect(srv.address)
    job = c.admit(K=32, k=4, seed=1)

    def one():
        try:
            c.tick(job, bits=np.ones(32))
        except (ServeError, protocol.ProtocolError, OSError):
            pass

    t = threading.Thread(target=one)
    t.start()
    time.sleep(0.3)  # let the engine thread block inside the tick
    srv.close(checkpoint=False)
    assert srv.stats["hung_engine"] == 1
    gate.set()
    t.join(timeout=10.0)
    c.close()


# ---------------------------------------------------------------------------
# supervised recovery
# ---------------------------------------------------------------------------


def _drive_with_replay(c, job, feed, *, rounds):
    """Round-cursor driver that survives retries, cache replay, and
    recovery rollback: on ``round_desync`` it rewinds to the server's
    expected round and replays the (deterministic) feedback from there."""
    got = {}
    t = 0
    while t < rounds:
        try:
            out = c.tick(job, lags=feed[t], round=t)
        except ServeError as e:
            if e.code == "round_desync":
                t = int(e.response["expected"])
                continue
            raise
        got[out["round"]] = out["cohort"]
        t = out["round"] + 1
    return [got[i] for i in range(rounds)]


def test_supervisor_restart_from_checkpoint(tmp_path):
    """A fault-injected engine crash: the supervisor restores the newest
    valid checkpoint, clients rewind on ``round_desync`` and replay — the
    full cohort stream is bit-identical to a fault-free run."""
    ROUNDS = 12
    plan = FaultPlan(crash_steps=(7,))
    rng = np.random.default_rng(3)
    feed = [_lags(rng, 32, S=0) for _ in range(ROUNDS)]

    ref = SlotEngine(K_max=32, k_cap=4, buckets=(4,))
    u = ref.admit(JobSpec(K=32, k=4, seed=9))
    want = [ref.tick([(u, f)])[u]["cohort"] for f in feed]

    srv = SelectionServer(
        SlotEngine(K_max=32, k_cap=4, buckets=(4,)),
        ckpt_dir=str(tmp_path), ckpt_every=3, faults=plan, restart_backoff=0.01,
    )
    with srv:
        with ServeClient.connect(srv.address, retries=6, seed=1) as c:
            job = c.admit(K=32, k=4, seed=9)
            got = _drive_with_replay(c, job, feed, rounds=ROUNDS)
            stats = c.stats()["stats"]
    assert got == want
    assert plan.fired()["crash"] == 1
    assert stats["restarts"] == 1
    assert stats["degraded"] == 0  # cleared by the first clean dispatch
    assert len(srv.recoveries) == 1
    assert any(a.rule == "engine_restart" for a in srv.alerts)
    assert srv.serve_series()["restarts"].sum() == 1


def test_restart_budget_exhaustion_answers_engine_down(tmp_path):
    """Past max_restarts the server stops restarting and answers
    ``engine_down`` instead of looping forever."""
    plan = FaultPlan(crash_steps=(0, 1, 2, 3))
    srv = SelectionServer(
        SlotEngine(K_max=32, k_cap=4, buckets=(4,)),
        ckpt_dir=str(tmp_path), faults=plan, max_restarts=2, restart_backoff=0.0,
    )
    with srv:
        with ServeClient.connect(srv.address, retries=8, seed=2) as c:
            job = c.admit(K=32, k=4, seed=1)
            with pytest.raises(ServeError) as e:
                _drive_with_replay(c, job, [_lags(np.random.default_rng(0), 32, S=0)], rounds=1)
            assert e.value.code in ("retry", "engine_down")
            with pytest.raises(ServeError) as e:
                c.call(op="tick", job=job, round=0,
                       xb=protocol.encode_bits(np.ones(32)))
            assert e.value.code == "engine_down"
    assert srv.stats["restarts"] == 3  # 2 allowed + the one that broke the budget


# ---------------------------------------------------------------------------
# acceptance: loopback client, 2 jobs, sharded-async engine, kill + restore
# ---------------------------------------------------------------------------


@needs8
def test_acceptance_sharded_async_kill_restore(tmp_path):
    """ROADMAP item 2's acceptance bar, end to end over the wire:

    admit 2 jobs into a D=8 sharded-async (S=2) server, drive >= 50 rounds
    through the compiled engine, checkpoint + kill mid-horizon, restore a
    fresh server from disk, finish the horizon — and every post-restore
    selection is bit-identical to an uninterrupted reference run.
    """
    ROUNDS, SPLIT = 52, 26
    rng = np.random.default_rng(7)
    specs = [
        dict(K=64, k=8, rounds=ROUNDS, seed=17),
        dict(K=48, k=4, rounds=ROUNDS, seed=23),
    ]
    feed = [[_lags(rng, s["K"]) for _ in range(ROUNDS)] for s in specs]

    # uninterrupted reference, same backend, straight through the engine
    ref = ShardedEngine(D=8, staleness=2)
    ruid = [ref.admit(JobSpec(**s)) for s in specs]
    want = [ref.tick([(u, f[t]) for u, f in zip(ruid, feed)]) for t in range(ROUNDS)]

    ckpt_dir = str(tmp_path / "ckpt")
    srv = SelectionServer(ShardedEngine(D=8, staleness=2), ckpt_dir=ckpt_dir)
    got = {0: [], 1: []}
    with srv:
        c = ServeClient.connect(srv.address)
        jobs = [c.admit(**s) for s in specs]
        for t in range(SPLIT):
            for i, j in enumerate(jobs):
                out = c.tick(j, lags=feed[i][t])
                got[i].append((out["round"], out["cohort"]))
        c.checkpoint()
        c.close()
        srv.kill()  # crash: no drain, no extra checkpoint

    stem = latest_server_checkpoint(ckpt_dir)
    assert stem is not None
    engine, step = load_server(stem)
    assert step == 2 * SPLIT
    with SelectionServer(engine, ckpt_dir=ckpt_dir) as srv2:
        c = ServeClient.connect(srv2.address)
        for t in range(SPLIT, ROUNDS):
            for i, j in enumerate(jobs):
                out = c.tick(j, lags=feed[i][t])
                got[i].append((out["round"], out["cohort"]))
        c.close()

    for i, u in enumerate(ruid):
        assert [r for r, _ in got[i]] == list(range(ROUNDS))
        for t in range(ROUNDS):
            assert got[i][t][1] == want[t][u]["cohort"], f"job {i} diverged at round {t}"


@needs8
def test_acceptance_chaos_bit_identical(tmp_path):
    """ISSUE 9's acceptance bar: a seeded chaos schedule — ≥1 engine crash,
    ≥1 corrupted checkpoint stem, ≥2 dropped connections, a slow dispatch —
    against a 2-tenant sharded-async (D=8, S=2) horizon.  The horizon
    completes, recovery restores from the newest *valid* stem (the corrupt
    one is walked past), retrying clients rewind and replay on
    ``round_desync`` — and every selection is cohort-for-cohort
    bit-identical to a fault-free run.
    """
    ROUNDS = 30
    rng = np.random.default_rng(29)
    specs = [dict(K=64, k=8, rounds=ROUNDS, seed=31),
             dict(K=48, k=4, rounds=ROUNDS, seed=37)]
    feed = [[_lags(rng, s["K"]) for _ in range(ROUNDS)] for s in specs]

    # fault-free reference, straight through the engine
    ref = ShardedEngine(D=8, staleness=2)
    ruid = [ref.admit(JobSpec(**s)) for s in specs]
    want = [ref.tick([(u, f[t]) for u, f in zip(ruid, feed)]) for t in range(ROUNDS)]

    # sequential driver => 1 tick per dispatch: checkpoints land at rounds
    # 6/12/18/24 (write indices 0..3); corrupting index 3 kills the NEWEST
    # stem before the crash at dispatch 25, so recovery MUST walk back to
    # step 18 (not just reload the latest file)
    plan = FaultPlan(
        crash_steps=(25,), corrupt_checkpoints=(3,), drop_responses=(12, 31),
        slow_steps={5: 0.02},
    )
    ckpt_dir = str(tmp_path / "ckpt")
    srv = SelectionServer(
        ShardedEngine(D=8, staleness=2),
        ckpt_dir=ckpt_dir, ckpt_every=6, faults=plan, restart_backoff=0.01,
    )
    with srv:
        with ServeClient.connect(srv.address, retries=6, seed=5) as c:
            jobs = [c.admit(**s) for s in specs]
            cursors = {i: 0 for i in range(len(jobs))}
            got = {i: {} for i in range(len(jobs))}
            while any(t < ROUNDS for t in cursors.values()):
                for i, j in enumerate(jobs):
                    t = cursors[i]
                    if t >= ROUNDS:
                        continue
                    try:
                        out = c.tick(j, lags=feed[i][t], round=t)
                    except ServeError as e:
                        if e.code == "round_desync":
                            cursors[i] = int(e.response["expected"])
                            continue
                        raise
                    got[i][out["round"]] = out["cohort"]
                    cursors[i] = out["round"] + 1
            stats = c.stats()["stats"]

    # the schedule really ran
    fired = plan.fired()
    assert fired["crash"] == 1 and fired["corrupt"] == 1
    assert fired["drop"] == 2 and fired["slow"] == 1
    assert stats["restarts"] == 1 and stats["replayed"] >= 1
    # recovery walked back PAST the corrupt step-24 stem (the newest at
    # crash time) to step 18 — recorded in the restart alert; the corrupt
    # file itself is later overwritten by a valid post-replay checkpoint
    restart = [a for a in srv.alerts if a.rule == "engine_restart"]
    assert len(restart) == 1
    assert restart[0].detail["restored_step"] == 18
    assert restart[0].detail["checkpoint"].endswith("ckpt_00000018")
    assert srv.serve_series()["restarts"].sum() == 1
    assert srv.serve_series()["recovery_s"].sum() > 0

    # and the horizon is cohort-for-cohort bit-identical to the clean run
    for i, u in enumerate(ruid):
        assert sorted(got[i]) == list(range(ROUNDS))
        for t in range(ROUNDS):
            assert got[i][t] == want[t][u]["cohort"], f"job {i} diverged at round {t}"
