"""apex_tpu.serving.transport + ChaosProxy — the cross-host wire (ISSUE 14).

The PR 10 fleet contracts were proven transport-agnostic over in-memory
fakes; this file proves them over REAL loopback TCP with injected
network faults.  A ``ServedFake`` puts the deterministic
``test_fleet.FakeReplica`` engine behind a real
:class:`~apex_tpu.serving.transport.TransportServer`, the router drives
it through :class:`~apex_tpu.serving.transport.SocketTransport`, and a
:class:`~apex_tpu.testing.faults.ChaosProxy` sits on the wire injecting
partition, half-open, slow-link, torn-frame, crc-corruption, and
reconnect churn — each stream still bitwise identical to the
uninterrupted reference.  Framing units at the top; the real-engine
socket leg is ``scripts/fleet_smoke.sh`` phase D.
"""

import queue
import socket
import threading
import time

import pytest

from apex_tpu.serving.fleet import FleetRouter
from apex_tpu.serving.scheduler import RequestState
from apex_tpu.serving.transport import (
    FRAME_VERSION,
    FrameDecoder,
    FrameError,
    SocketTransport,
    TransportError,
    TransportServer,
    encode_frame,
)
from apex_tpu.testing.faults import ChaosProxy

from test_fleet import FakeReplica, make_router, reference

# ------------------------------------------------------------- framing


def test_frame_round_trip_incremental():
    payloads = [("token", 3, 42), ("state", {"free_blocks": 7}),
                ("evt", 1, ("ready", {"pid": 1})), ("ping", 9)]
    wire = b"".join(encode_frame(p) for p in payloads)
    dec = FrameDecoder()
    got = []
    for i in range(0, len(wire), 3):      # drip 3 bytes at a time
        got.extend(dec.feed(wire[i:i + 3]))
    assert got == payloads
    assert not dec.partial


def test_frame_partial_flags_torn_state():
    frame = encode_frame(("token", 1, 2))
    dec = FrameDecoder()
    assert dec.feed(frame[:len(frame) - 2]) == []
    assert dec.partial                    # EOF now would tear a frame
    assert dec.feed(frame[len(frame) - 2:]) == [("token", 1, 2)]
    assert not dec.partial


def test_frame_version_mismatch_raises():
    frame = bytearray(encode_frame(("x",)))
    frame[0] = FRAME_VERSION + 1
    with pytest.raises(FrameError, match="version"):
        FrameDecoder().feed(bytes(frame))


def test_frame_crc_mismatch_raises():
    frame = bytearray(encode_frame(("token", 1, 2)))
    frame[-1] ^= 0x10                     # body bit flip
    with pytest.raises(FrameError, match="crc32"):
        FrameDecoder().feed(bytes(frame))


def test_frame_length_bound_raises():
    with pytest.raises(FrameError, match="bound"):
        FrameDecoder(max_frame_bytes=16).feed(
            encode_frame(("x" * 64,)))


# ---------------------------------------------------- harness plumbing


class ServedFake:
    """A deterministic FakeReplica engine behind a real
    TransportServer: the hermetic socket replica.  ``tick()`` plays the
    replica host's loop — apply wire commands, one decode step, relay
    events; the server closes with ``bye`` on a clean drain and without
    it on a kill (the crash shape)."""

    def __init__(self, name, event_ring=8192, **fake_kw):
        self.fake = FakeReplica(name, **fake_kw)
        self.name = name
        self.cmd_q = queue.Queue()
        self.evt_q = queue.Queue()
        self.server = TransportServer(self.cmd_q, self.evt_q,
                                      event_ring=event_ring)
        self.address = self.server.address
        self._closed = False
        self._relay()

    def _relay(self):
        for ev in self.fake.poll():
            self.evt_q.put(ev)

    def tick(self):
        if self._closed:
            return
        while True:
            try:
                cmd = self.cmd_q.get_nowait()
            except queue.Empty:
                break
            try:
                if cmd[0] == "submit":
                    self.fake.submit(*cmd[1:])
                elif cmd[0] == "submit_many":
                    for item in cmd[1]:
                        self.fake.submit(*item)
                elif cmd[0] == "drain":
                    self.fake.begin_drain()
                elif cmd[0] == "export_kv":
                    self.fake.export_kv(cmd[1])
                elif cmd[0] == "kv_ack":
                    self.fake.kv_ack(cmd[1], cmd[2])
                elif cmd[0] == "import_kv":
                    self.fake.import_kv(cmd[1], cmd[2])
                elif cmd[0] == "kv_block":
                    self.fake.kv_block(cmd[1], cmd[2], cmd[3])
                elif cmd[0] == "import_commit":
                    self.fake.import_commit(cmd[1], cmd[2], cmd[3])
                elif cmd[0] == "kv_abort":
                    self.fake.kv_abort(cmd[1])
                elif cmd[0] == "stop":
                    self._relay()
                    self._shutdown(bye=True)
                    return
            except BrokenPipeError:
                pass                      # command raced the death
        self.fake.tick()
        self._relay()
        if not self.fake.alive():
            # drained exit says goodbye; a crash just goes dark
            self._shutdown(bye=self.fake.draining)

    def kill(self):
        self.fake.kill()
        self._shutdown(bye=False)

    def _shutdown(self, bye):
        if not self._closed:
            self._closed = True
            self.server.close(bye=bye)

    def close(self):
        self._shutdown(bye=False)


def make_client(served_or_addr, name=None, **kw):
    addr = getattr(served_or_addr, "address", served_or_addr)
    name = name or getattr(served_or_addr, "name", "r")
    kw.setdefault("backoff_initial_s", 0.01)
    kw.setdefault("backoff_max_s", 0.2)
    kw.setdefault("ping_every_s", 0.05)
    return SocketTransport(name, addr, **kw)


def wait_states(router, *, tries=2000):
    """Pump until every non-down view has a state heartbeat (placement
    over the wire needs free_blocks to have ARRIVED, where the hermetic
    fakes delivered it synchronously)."""
    for _ in range(tries):
        router.pump()
        if all(v.state is not None
               for v in router._views.values() if not v.down):
            return
        time.sleep(0.001)
    raise AssertionError("state heartbeats never arrived")


def sock_drive(router, served, *, clock=None, step=0.05, max_iters=4000,
               sleep_s=0.001, tick_every=1):
    """Pump router + tick served fakes until idle; optionally advance
    an injected router clock per iteration (the failure-detection
    ladder's deterministic driver).  ``tick_every`` throttles replica
    ticks to one per N iterations: on a slowed link every tick's state
    heartbeat costs a full proxy delay on the wire, so un-throttled
    ticking floods the session ring ahead of the token events and
    starves them behind hours of queued heartbeats."""
    for i in range(max_iters):
        router.pump()
        if router.idle():
            return
        if i % tick_every == 0:
            for s in served:
                s.tick()
        if clock is not None:
            clock[0] += step
        time.sleep(sleep_s)
    raise AssertionError(
        f"not idle after {max_iters} iters: "
        f"{[(r.rid, r.state) for r in router.requests.values() if not r.done]}")


def pump_until(router, served, cond, what, *, hold=None, clock=None,
               deadline_s=60.0):
    """Pump the router and tick the replicas until ``cond()`` holds, under
    a wall-clock deadline of its own: on a loaded box an iteration lasts
    as long as the scheduler likes, so a count of them bounds nothing.
    ``hold`` is ticked only until it has emitted its first token: the
    same loop generates tokens and looks for them, and a replica ticked
    at the loop's pace finishes the whole stream while the socket threads
    wait for a core, so that one pump delivers it all and no look ever
    finds it mid-stream."""
    end = time.monotonic() + deadline_s
    while not cond():
        assert time.monotonic() < end, f"{what}: not within {deadline_s:.0f} s"
        router.pump()
        for s in served:
            if s is not hold or s.fake.tokens_emitted == 0:
                s.tick()
        if clock is not None:
            clock[0] += 0.05              # drive the detection ladder
        time.sleep(0.001)


def cleanup(router, served, proxies=()):
    router.close()
    for s in served:
        s.close()
    for p in proxies:
        p.close()


# ------------------------------------------------------ basic round trip


def test_socket_round_trip_token_identity():
    served = ServedFake("a")
    client = make_client(served)
    meta = client.wait_ready(timeout=30)
    assert meta["name"] == "a"
    router = make_router([client])
    try:
        wait_states(router, tries=4000)
        req = router.submit([3, 5, 7], 5)
        sock_drive(router, [served])
        assert req.state is RequestState.FINISHED
        assert req.output_tokens == reference([3, 5, 7], 5)
        assert served.fake.submissions[0][0] == req.rid
        # the command outbox drained through acks — nothing pending
        assert not client._outbox
    finally:
        cleanup(router, [served])


def test_socket_batched_submit_many():
    served = ServedFake("a", max_batch=8)
    client = make_client(served)
    client.wait_ready(timeout=30)
    router = make_router([client], replica_queue_limit=8)
    try:
        wait_states(router, tries=4000)
        prompts = [[3, 5, 7], [2, 4], [9, 9, 1], [6]]
        reqs = [router.submit(p, 4) for p in prompts]
        router.pump()                     # one pump seats all four
        sock_drive(router, [served])
        for req, p in zip(reqs, prompts):
            assert req.state is RequestState.FINISHED
            assert req.output_tokens == reference(p, 4)
        assert int(router.registry.counter(
            "fleet/batched_submits").value) >= 1
    finally:
        cleanup(router, [served])


def test_socket_link_rtt_measured():
    served = ServedFake("a")
    client = make_client(served, ping_every_s=0.02)
    client.wait_ready(timeout=30)
    deadline = time.monotonic() + 10
    while client.link_rtt_s is None and time.monotonic() < deadline:
        client.poll()
        time.sleep(0.005)
    assert client.link_rtt_s is not None and client.link_rtt_s < 5.0
    client.close()
    served.close()


# ------------------------------------------------- reconnect (churn)


def test_reconnect_churn_is_lossless_no_failover():
    """Connections severed at frame boundaries mid-stream: the session
    seq-replay resumes without losing an event — the stream is bitwise
    intact, ``fleet/reconnects`` counts, and NO failover fired."""
    served = ServedFake("a")
    proxy = ChaosProxy(served.address)
    client = make_client(proxy, name="a")
    client.wait_ready(timeout=30)
    router = make_router([client])
    try:
        wait_states(router, tries=4000)
        req = router.submit([9, 1, 4], 8)
        drops = 0
        for _ in range(6000):
            router.pump()
            if router.idle():
                break
            if client._hello_done and served.server._active is not None \
                    and len(req.output_tokens) == served.fake.tokens_emitted:
                # tick the replica only over a live session AND in
                # lockstep with delivery — BOTH ends' view: tokens
                # generated into a severed link pile up in the server
                # ring and the reconnect replays them as one burst that
                # can blow past the next drop window entirely (the
                # observed flake; the client alone is not enough — it
                # learns of the cut ~20ms after the server does).  The
                # lockstep clause keeps at most one token in flight, so
                # a single replay burst cannot finish the stream and
                # each drop window must earn its own reconnect.
                served.tick()
            if drops < 2 and len(req.output_tokens) >= 2 * (drops + 1):
                proxy.drop_connections()   # ≥4 tokens still outstanding
                drops += 1
            time.sleep(0.001)
        assert drops == 2, "churn never engaged mid-stream"
        assert req.state is RequestState.FINISHED
        assert req.output_tokens == reference([9, 1, 4], 8)
        assert client.reconnects >= drops
        snap = router.registry.snapshot()
        assert snap.get("fleet/reconnects") == float(client.reconnects)
        assert snap.get("fleet/failovers", 0.0) == 0.0
        assert client.frames_corrupt == 0
    finally:
        cleanup(router, [served], [proxy])


# ---------------------------------------- torn / corrupt frame verdicts


@pytest.mark.parametrize("fault,reason", [
    ("corrupt_next_frame", "corrupt"),
    ("tear_next_frame", "torn"),
])
def test_bad_frame_counted_and_classified_replica_failure(fault, reason):
    """A crc-corrupt or torn frame is NEVER deserialized: the client
    counts it (``frames_corrupt``), fails the replica, and the router
    recovers through the ordinary down-verdict → replay path — the
    stitched stream bitwise identical to the uninterrupted one."""
    victim = ServedFake("victim", free_blocks=1000)
    survivor = ServedFake("survivor", free_blocks=10)
    proxy = ChaosProxy(victim.address)
    c_victim = make_client(proxy, name="victim")
    c_survivor = make_client(survivor)
    for c in (c_victim, c_survivor):
        c.wait_ready(timeout=30)
    router = make_router([c_victim, c_survivor])
    try:
        wait_states(router, tries=4000)
        req = router.submit([9, 1, 4], 6)
        pump_until(router, [victim, survivor], lambda: req.output_tokens,
                   "first token seen", hold=victim)
        assert len(req.output_tokens) == 1 and not req.done  # mid-stream
        getattr(proxy, fault)()           # next replica→router frame
        pump_until(router, [victim, survivor], router.idle,
                   "stream finished after the bad frame")
        assert req.state is RequestState.FINISHED
        assert req.output_tokens == reference([9, 1, 4], 6)
        view = router._views["victim"]
        assert view.down and reason in view.down_reason
        assert c_victim.frames_corrupt == 1
        snap = router.registry.snapshot()
        assert snap.get("fleet/frames_corrupt") == 1.0
        assert snap.get("fleet/failovers") == 1.0
        assert req.replays == 1
    finally:
        cleanup(router, [victim, survivor], [proxy])


# ------------------------------------------------- partition / half-open


def test_partition_failover_replay_token_identity():
    """A partitioned replica goes silent; the heartbeat→probe ladder
    produces the down verdict and its in-flight requests replay on the
    survivor, streams bitwise intact."""
    clock = [0.0]
    victim = ServedFake("victim", free_blocks=1000)
    survivor = ServedFake("survivor", free_blocks=10)
    proxy = ChaosProxy(victim.address)
    c_victim = make_client(proxy, name="victim")
    c_survivor = make_client(survivor)
    for c in (c_victim, c_survivor):
        c.wait_ready(timeout=30)
    router = make_router(
        [c_victim, c_survivor], heartbeat_timeout_s=0.5,
        probe_retries=2, probe_backoff_s=0.1, clock=lambda: clock[0])
    try:
        wait_states(router, tries=4000)
        req = router.submit([9, 1, 4], 6)
        pump_until(router, [victim, survivor], lambda: req.output_tokens,
                   "first token seen", hold=victim)
        assert len(req.output_tokens) == 1 and not req.done  # mid-stream
        proxy.partition()                 # total silence from here
        pump_until(router, [victim, survivor], router.idle,
                   "failed over and finished on the survivor", clock=clock)
        assert req.state is RequestState.FINISHED
        assert req.output_tokens == reference([9, 1, 4], 6)
        assert router._views["victim"].down
        assert router.registry.snapshot().get("fleet/failovers") == 1.0
    finally:
        cleanup(router, [victim, survivor], [proxy])


def test_half_open_link_recovers_on_survivor():
    """Accept-then-silence: reconnects complete TCP but the session
    hello never answers.  The client churns through it with backoff
    (bounded, never wedged) and the router's ladder fails the replica
    over — streams intact."""
    clock = [0.0]
    victim = ServedFake("victim", free_blocks=1000)
    survivor = ServedFake("survivor", free_blocks=10)
    proxy = ChaosProxy(victim.address)
    c_victim = make_client(proxy, name="victim", send_timeout_s=0.1)
    c_survivor = make_client(survivor)
    for c in (c_victim, c_survivor):
        c.wait_ready(timeout=30)
    router = make_router(
        [c_victim, c_survivor], heartbeat_timeout_s=0.5,
        probe_retries=2, probe_backoff_s=0.1, clock=lambda: clock[0])
    try:
        wait_states(router, tries=4000)
        req = router.submit([9, 1, 4], 6)
        pump_until(router, [victim, survivor], lambda: req.output_tokens,
                   "first token seen", hold=victim)
        assert len(req.output_tokens) == 1 and not req.done  # mid-stream
        proxy.half_open()                 # future accepts: black hole
        proxy.drop_connections()          # force it onto them
        pump_until(router, [victim, survivor], router.idle,
                   "failed over and finished on the survivor", clock=clock)
        assert req.state is RequestState.FINISHED
        assert req.output_tokens == reference([9, 1, 4], 6)
        view = router._views["victim"]
        assert view.down and "missed heartbeat" in view.down_reason
    finally:
        cleanup(router, [victim, survivor], [proxy])


def test_all_unreachable_sheds_typed_rejected_after_deadline():
    """Graceful degradation, pinned with an injected clock: with every
    replica unreachable, pending requests wait a BOUNDED deadline —
    not forever, not zero — then shed in the typed REJECTED state."""
    clock = [0.0]
    served = ServedFake("a")
    proxy = ChaosProxy(served.address)
    client = make_client(proxy, name="a")
    client.wait_ready(timeout=30)
    router = make_router(
        [client], heartbeat_timeout_s=0.5, probe_retries=2,
        probe_backoff_s=0.1, dispatch_deadline_s=2.0,
        clock=lambda: clock[0])
    try:
        wait_states(router, tries=4000)
        # long enough that it cannot have finished when its first token is
        # seen, however many ticks' tokens a starved proxy delivers at once
        req = router.submit([5, 5], 200)
        cut = False
        for _ in range(6000):
            router.pump()
            served.tick()
            if not cut and req.output_tokens:
                proxy.partition()
                cut = True
            if cut:
                clock[0] += 0.05
            if router._views["a"].down:
                break
            time.sleep(0.001)
        assert cut and router._views["a"].down
        # the replayed request waits — inside the deadline it is NOT
        # shed (a blip must not refuse work the fleet could still do)
        router.pump()
        start = clock[0]
        clock[0] = start + 1.0
        router.pump()
        assert req.state is RequestState.WAITING
        late = router.submit([1, 2], 3)   # joins the bounded wait
        # past the deadline: both shed with the typed terminal state
        clock[0] = start + 2.6
        router.pump()
        assert req.state is RequestState.REJECTED
        assert late.state is RequestState.REJECTED
        snap = router.registry.snapshot()
        assert snap.get("serving/requests_rejected") == 2.0
        assert router.idle()
        # the stream API surfaces the shed as a clean close, not a hang
        assert list(router.stream(late, poll_s=0)) == []
    finally:
        cleanup(router, [served], [proxy])


# ------------------------------------------------------- slow link


def test_slow_link_demoted_in_placement_not_failed():
    """A degraded link (RTT past ``link_degraded_rtt_s``) loses
    placement even against better pool shape — but is NOT failed: no
    failover, not down, still visible in introspect with its RTT."""
    slow = ServedFake("slow", free_blocks=1000)
    fast = ServedFake("fast", free_blocks=10)
    proxy = ChaosProxy(slow.address)
    c_slow = make_client(proxy, name="slow", ping_every_s=0.05)
    c_fast = make_client(fast)
    for c in (c_slow, c_fast):
        c.wait_ready(timeout=30)
    router = make_router([c_slow, c_fast], link_degraded_rtt_s=0.1)
    try:
        wait_states(router, tries=4000)
        proxy.slow(0.2)                   # one-way per frame ≈ 0.4s RTT
        deadline = time.monotonic() + 15
        while not router._views["slow"].link_degraded and \
                time.monotonic() < deadline:
            router.pump()
            time.sleep(0.01)
        view = router._views["slow"]
        assert view.link_degraded and view.link_rtt_s > 0.1
        # demoted: the fast link wins despite 100x fewer free blocks
        req = router.submit([4, 2], 3)
        sock_drive(router, [slow, fast])
        assert req.replica == "fast"
        assert req.output_tokens == reference([4, 2], 3)
        # ...but never hard-failed
        assert not view.down
        snap = router.registry.snapshot()
        assert snap.get("fleet/failovers", 0.0) == 0.0
        assert snap.get("fleet/link_degraded") == 1.0
        intro = router.introspect()["replicas"]["slow"]
        assert intro["link_degraded"] is True
        assert intro["link_rtt_ms"] > 100.0
    finally:
        cleanup(router, [slow, fast], [proxy])


def test_sole_slow_replica_still_serves():
    """Demotion is a preference, not an exclusion: a fleet whose only
    replica has a degraded link still serves every request."""
    served = ServedFake("a")
    proxy = ChaosProxy(served.address)
    client = make_client(proxy, name="a", ping_every_s=0.05)
    client.wait_ready(timeout=30)
    router = make_router([client], link_degraded_rtt_s=0.05)
    try:
        wait_states(router, tries=4000)
        proxy.slow(0.1)
        deadline = time.monotonic() + 15
        while not router._views["a"].link_degraded and \
                time.monotonic() < deadline:
            router.pump()
            time.sleep(0.01)
        assert router._views["a"].link_degraded
        req = router.submit([7, 7], 2)
        # throttle ticks well below the 10-frames/s drain the 0.1s link
        # sustains, or per-tick state heartbeats flood the ring ahead
        # of the token events (see sock_drive)
        sock_drive(router, [served], max_iters=8000, sleep_s=0.02,
                   tick_every=10)
        assert req.state is RequestState.FINISHED
        assert req.output_tokens == reference([7, 7], 2)
    finally:
        cleanup(router, [served], [proxy])


# ------------------------------------- the PR 10 matrix over the socket


@pytest.mark.parametrize("k", [0, 1, 3, 6])   # 0, 1, mid, last
def test_socket_failover_replay_kill_at_k(k):
    """The PR 10 kill-at-k bitwise-replay matrix, through the socket
    transport: the replica host dies (server gone, connects refused),
    the ladder detects, the stitched stream equals the uninterrupted
    reference bitwise."""
    clock = [0.0]
    n_new, prompt = 6, [9, 1, 4]
    victim = ServedFake("victim", free_blocks=1000, die_after_tokens=k)
    survivor = ServedFake("survivor", free_blocks=10)
    c_victim = make_client(victim)
    c_survivor = make_client(survivor)
    for c in (c_victim, c_survivor):
        c.wait_ready(timeout=30)
    router = make_router(
        [c_victim, c_survivor], heartbeat_timeout_s=0.5,
        probe_retries=2, probe_backoff_s=0.1, clock=lambda: clock[0])
    try:
        wait_states(router, tries=4000)
        req = router.submit(prompt, n_new)
        sock_drive(router, [victim, survivor], clock=clock)
        assert req.state is RequestState.FINISHED
        assert req.output_tokens == reference(prompt, n_new)
        assert req.replays == (0 if k >= n_new else 1)
        if 0 < k < n_new:
            frid, wire_prompt, wire_budget, _, _ = \
                survivor.fake.submissions[0]
            assert frid == req.rid
            assert wire_prompt == prompt + reference(prompt, k)
            assert wire_budget == n_new - k
    finally:
        cleanup(router, [victim, survivor])


def test_socket_flood_sheds_typed_and_admitted_finish():
    served = ServedFake("a", max_batch=1)
    client = make_client(served)
    client.wait_ready(timeout=30)
    router = make_router([client], max_queue_depth=3,
                         replica_queue_limit=1)
    try:
        wait_states(router, tries=4000)
        reqs = [router.submit([1], 4) for _ in range(6)]
        shed = [r for r in reqs if r.state is RequestState.REJECTED]
        kept = [r for r in reqs if r.state is not RequestState.REJECTED]
        assert len(shed) == 3 and len(kept) == 3
        assert router.registry.snapshot()[
            "serving/requests_rejected"] == 3.0
        sock_drive(router, [served])
        for r in kept:
            assert r.state is RequestState.FINISHED
            assert r.output_tokens == reference([1], 4)
    finally:
        cleanup(router, [served])


def test_socket_rollout_drains_over_the_wire():
    """Zero-downtime rollout cross-host: ``begin_drain`` rides the wire
    (no SIGTERM reaches a remote host), the drained replica says
    goodbye (``bye`` → ``alive() == False``), the replacement joins
    over a fresh connection, nothing is lost."""
    a = ServedFake("a", free_blocks=1000, max_batch=1)
    b = ServedFake("b", free_blocks=10, max_batch=1)
    c_a = make_client(a)
    c_b = make_client(b)
    for c in (c_a, c_b):
        c.wait_ready(timeout=30)
    router = make_router([c_a, c_b], replica_queue_limit=4)
    served = [a, b]
    try:
        wait_states(router, tries=4000)
        reqs = [router.submit([i + 1], 3) for i in range(4)]
        router.pump()

        def factory(name):
            rep = ServedFake(name, free_blocks=1000, max_batch=1)
            served.append(rep)
            return make_client(rep)

        def on_tick():
            for rep in served:
                rep.tick()

        rolled = router.rollout(factory, names=["a"], on_tick=on_tick,
                                drain_timeout_s=30, ready_timeout_s=30)
        assert rolled == ["a"]
        assert not c_a.alive()            # bye honoured: clean exit
        sock_drive(router, served)
        for i, req in enumerate(reqs):
            assert req.state is RequestState.FINISHED, (req.rid, req.state)
            assert req.output_tokens == reference([i + 1], 3)
        snap = router.registry.snapshot()
        assert snap["fleet/rollouts"] == 1.0
        assert snap.get("serving/requests_rejected", 0.0) == 0.0
    finally:
        cleanup(router, served)


@pytest.mark.parametrize("survivor_fault", ["slow", "churn"])
def test_kill_failover_composes_with_faulty_survivor_wire(survivor_fault):
    """Fault classes compose: the victim dies mid-decode while the
    SURVIVOR's own wire is degraded (slow link) or churning
    (reconnect drops) — the replay still lands and the stitched stream
    is bitwise the uninterrupted reference."""
    clock = [0.0]
    n_new, prompt = 6, [9, 1, 4]
    victim = ServedFake("victim", free_blocks=1000, die_after_tokens=3)
    survivor = ServedFake("survivor", free_blocks=10)
    proxy = ChaosProxy(survivor.address)
    c_victim = make_client(victim)
    c_survivor = make_client(proxy, name="survivor")
    for c in (c_victim, c_survivor):
        c.wait_ready(timeout=30)
    router = make_router(
        [c_victim, c_survivor], heartbeat_timeout_s=2.0,
        probe_retries=2, probe_backoff_s=0.1, clock=lambda: clock[0])
    try:
        wait_states(router, tries=4000)
        if survivor_fault == "slow":
            proxy.slow(0.02)
        req = router.submit(prompt, n_new)
        since_drop = 0
        for _ in range(8000):
            router.pump()
            if router.idle():
                break
            for s in (victim, survivor):
                s.tick()
            clock[0] += 0.05
            since_drop += 1
            if survivor_fault == "churn" and since_drop >= 50:
                proxy.drop_connections(wait_s=1.0)
                since_drop = 0
            time.sleep(0.001)
        assert req.state is RequestState.FINISHED
        assert req.output_tokens == reference(prompt, n_new)
        assert req.replays == 1
        assert not router._views["survivor"].down
    finally:
        cleanup(router, [victim, survivor], [proxy])


# ------------------------------------------- client-side bounds


def test_outbox_backpressure_raises_bounded():
    """The send queue is bounded: past ``max_outbox`` unacked commands,
    submit raises — the router's dead-pipe class — instead of buffering
    without bound into a partition."""
    client = SocketTransport("a", ("127.0.0.1", 1), max_outbox=4,
                             backoff_initial_s=10.0)   # never connects
    for i in range(4):
        client.submit(i, [1, 2], 4)
    with pytest.raises(TransportError, match="backpressure"):
        client.submit(99, [1, 2], 4)
    client.close()


def test_send_timeout_raises_when_wire_wedges(monkeypatch):
    """A connected-but-not-reading peer (zero-window stall) trips the
    per-command send deadline on the injected clock instead of wedging
    the router's pump forever."""
    clock = [0.0]
    lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    lsock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 16384)
    lsock.bind(("127.0.0.1", 0))
    lsock.listen(1)
    held = []

    def acceptor():
        conn, _ = lsock.accept()
        held.append(conn)
        dec = FrameDecoder()
        while True:                       # answer the hello, then stall
            msgs = dec.feed(conn.recv(4096))
            if any(m[0] == "hello" for m in msgs):
                conn.sendall(encode_frame(("hello", 0, False, 0)))
                return                    # never reads again

    t = threading.Thread(target=acceptor, daemon=True)
    t.start()
    real_finish = SocketTransport._finish_connect

    def small_buf_finish(self, sock, now):
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 8192)
        real_finish(self, sock, now)

    monkeypatch.setattr(SocketTransport, "_finish_connect",
                        small_buf_finish)
    client = SocketTransport("a", lsock.getsockname(),
                             send_timeout_s=0.5, ping_every_s=1e9,
                             clock=lambda: clock[0])
    try:
        deadline = time.monotonic() + 10
        while not client._hello_done and time.monotonic() < deadline:
            client.poll()
            time.sleep(0.005)
        assert client._hello_done
        client.submit(1, [7] * 500_000, 4)   # ~MBs: wedges the wire
        clock[0] += 1.0
        with pytest.raises(TransportError, match="send timeout"):
            for _ in range(200):
                client.poll()
                time.sleep(0.005)
    finally:
        client.close()
        for c in held:
            c.close()
        lsock.close()


def test_fresh_router_reattaches_to_long_lived_daemon():
    """A restarted router — a brand-new client session against a
    long-lived daemon — must neither be black-holed by the OLD
    session's command-dedupe watermark nor reset by an event ring that
    no longer reaches back to seq 0: the fresh hello resets the
    server's command-dedupe watermark, fast-forwards the client's event
    cursor, and re-emits the sticky ready/state, so the new router
    serves immediately."""
    served = ServedFake("a", event_ring=4)   # seq-0 history long gone
    c1 = make_client(served)
    c1.wait_ready(timeout=30)
    router1 = make_router([c1])
    try:
        wait_states(router1, tries=4000)
        req1 = router1.submit([3, 5, 7], 5)
        sock_drive(router1, [served])
        assert req1.output_tokens == reference([3, 5, 7], 5)
        c1._close_socks()                 # router host dies, no goodbye
        c2 = make_client(served, name="a")
        meta = c2.wait_ready(timeout=30)  # sticky ready re-emitted
        assert meta["name"] == "a"
        router2 = make_router([c2])
        wait_states(router2, tries=4000)  # sticky state re-emitted
        req2 = router2.submit([2, 4], 3)
        sock_drive(router2, [served])
        assert req2.state is RequestState.FINISHED
        assert req2.output_tokens == reference([2, 4], 3)
        assert c2.frames_corrupt == 0 and c2.alive()
        cleanup(router2, [])
    finally:
        cleanup(router1, [served])


# ------------------------------------------- server-side bounds


def test_server_mark_sent_tracks_frame_boundaries():
    """The server's partial-send bookkeeping: ``head_rem`` counts the
    un-flushed remainder of a half-sent head frame, and returns to 0
    exactly at frame boundaries — the only points where a deliberate
    stall-drop is allowed to sever the connection."""
    from apex_tpu.serving.transport import TransportServer, _ServerConn

    conn = _ServerConn(1 << 20)
    f1, f2 = encode_frame(("a", 1)), encode_frame(("bb", [2, 3, 4]))
    conn.out.extend(f1)
    conn.out.extend(f2)
    TransportServer._mark_sent(conn, 5)            # mid-f1
    assert conn.head_rem == len(f1) - 5
    del conn.out[:5]
    TransportServer._mark_sent(conn, conn.head_rem)  # f1 boundary
    del conn.out[:len(f1) - 5]
    assert conn.head_rem == 0
    TransportServer._mark_sent(conn, len(f2))      # whole f2 in one go
    del conn.out[:len(f2)]
    assert conn.head_rem == 0 and not conn.out
    # spanning a boundary in one send: finish nothing, start f2 mid-way
    conn.out.extend(f1)
    conn.out.extend(f2)
    TransportServer._mark_sent(conn, len(f1) + 3)
    assert conn.head_rem == len(f2) - 3


def test_stalled_connection_drop_severs_at_frame_boundary():
    """A live-but-stalled peer is dropped once its un-flushed backlog
    passes ``max_buffered_bytes`` — but the sever must land on a frame
    boundary: every byte the peer DID receive parses as whole frames,
    so the client classifies the cut as a connection loss (lossless
    seq-replay reconnect), never as a torn frame / corruption."""
    cmd_q, evt_q = queue.Queue(), queue.Queue()
    server = TransportServer(cmd_q, evt_q, max_buffered_bytes=4096)
    sock = None
    try:
        sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        # tiny receive window: the server's sends back up quickly
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
        sock.settimeout(10)
        sock.connect(server.address)
        sock.sendall(encode_frame(("hello", 0, 0, True)))
        dec = FrameDecoder()
        got = []
        while not got:                    # read the hello reply only
            got.extend(dec.feed(sock.recv(4096)))
        assert got[0][0] == "hello"
        # flood far past every kernel buffer while never reading: the
        # server must stall-drop this connection
        big_evt = ("token", 0, list(range(1024)))
        for _ in range(4000):             # ~16 MB of frames
            evt_q.put(big_evt)
        saw_eof = False
        try:
            while True:                   # drain what was delivered
                data = sock.recv(65536)
                if data == b"":
                    saw_eof = True
                    break
                dec.feed(data)
        except OSError:
            saw_eof = True                # reset also ends the stream
        assert saw_eof, "server never dropped the stalled connection"
        assert not dec.partial, \
            "stall-drop severed mid-frame: the client would count " \
            "frames_corrupt for a wire that was never corrupted"
    finally:
        if sock is not None:
            sock.close()
        server.close(bye=False, timeout=1.0)


# ------------- ISSUE 16: KV migration over the wire, under ChaosProxy


def _disagg_served(proxy_on=None, **router_kw):
    """1-prefill/1-decode fleet over real sockets; optionally a
    ChaosProxy on one replica's link ("p" or "d").  Returns
    (p, d, proxy, clients, router)."""
    p = ServedFake("p", meta={"role": "prefill"}, free_blocks=1000)
    d = ServedFake("d", meta={"role": "decode"}, free_blocks=1000)
    proxy = None
    endpoints = {"p": p, "d": d}
    clients = []
    for name, s in endpoints.items():
        target = s
        if proxy_on == name:
            proxy = ChaosProxy(s.address)
            target = proxy
        c = make_client(target, name=name)
        c.wait_ready(timeout=30)
        clients.append(c)
    router = make_router(clients, **router_kw)
    wait_states(router, tries=4000)
    return p, d, proxy, clients, router


def test_socket_disagg_migration_identity_and_counters():
    """The tentpole over the real wire, fault-free: greedy AND seeded
    streams prefill on ``p``, migrate block-by-block through the framed
    transport, finish on ``d`` — bitwise the uninterrupted streams."""
    from apex_tpu.serving import SamplingParams

    sp = SamplingParams(temperature=0.8, seed=5)
    p, d, proxy, clients, router = _disagg_served()
    try:
        # long streams: over the real wire tokens surface in relay
        # bursts, so the trigger can fire several tokens in — the
        # budget must comfortably outlast it
        r1 = router.submit([9, 1, 4], 16)
        r2 = router.submit([3, 5], 12, sampling=sp)
        sock_drive(router, [p, d], max_iters=8000)
        assert r1.state is RequestState.FINISHED
        assert r1.output_tokens == reference([9, 1, 4], 16)
        from test_fleet import seeded_reference
        assert r2.state is RequestState.FINISHED
        assert r2.output_tokens == seeded_reference([3, 5], 12, sp)
        assert r1.replica == "d" and r2.replica == "d"
        assert d.fake.imports_committed == 2
        for _ in range(200):               # let the kv_ack land on p
            router.pump()
            p.tick()
            if p.fake.exports == {} and len(p.fake.export_acks) == 2:
                break
            time.sleep(0.001)
        assert p.fake.exports == {}
        assert sorted(ok for _, ok in p.fake.export_acks) == [True, True]
        snap = router.registry.snapshot()
        assert snap.get("fleet/kv_migrate_started") == 2.0
        assert snap.get("fleet/kv_migrate_completed") == 2.0
        assert snap.get("fleet/kv_migrate_failed", 0.0) == 0.0
        assert snap.get("fleet/kv_migrate_blocks", 0.0) >= 2.0
        assert snap.get("fleet/kv_migrate_bytes", 0.0) >= 2.0
        assert snap.get("fleet/failovers", 0.0) == 0.0
        body = router.fleet_statusz()
        assert set(body["roles"]) == {"prefill", "decode"}
        assert body["migrations"]["completed"] == 2
    finally:
        cleanup(router, [p, d], [proxy] if proxy else [])


def test_migration_link_partition_degrades_to_replay():
    """Partition on the decode replica's link mid-handoff: the import
    verdict can never arrive, the probe ladder downs the destination,
    and the request re-prefills on the source — bitwise intact."""
    clock = [0.0]
    p, d, proxy, clients, router = _disagg_served(
        proxy_on="d", heartbeat_timeout_s=0.5, probe_retries=2,
        probe_backoff_s=0.1, clock=lambda: clock[0])
    try:
        req = router.submit([9, 1, 4], 16)
        cut = False
        for _ in range(8000):
            router.pump()
            if router.idle():
                break
            for s in (p, d):
                s.tick()
            if not cut and req.output_tokens:
                proxy.partition()          # silence before the verdict
                cut = True
            if cut:
                clock[0] += 0.05           # drive the detection ladder
            time.sleep(0.001)
        assert cut, "partition never engaged mid-stream"
        assert req.state is RequestState.FINISHED
        assert req.output_tokens == reference([9, 1, 4], 16)
        assert req.replica == "p"
        assert router._views["d"].down
        assert router._migrations == {}
        snap = router.registry.snapshot()
        assert snap.get("fleet/kv_migrate_started", 0.0) >= 1.0
        assert snap.get("fleet/kv_migrate_completed", 0.0) == 0.0
        assert snap.get("fleet/kv_migrate_failed", 0.0) >= 1.0
        # the source's pin released into its prefix cache (not-ok ack)
        assert p.fake.exports == {}
    finally:
        cleanup(router, [p, d], [proxy])


def test_migration_survives_dst_reconnect_churn():
    """Per-block resumability: the connection to the decode replica is
    severed while the block stream is in flight — the session outbox
    resends exactly the unacked tail on reconnect, the import commits,
    and no re-prefill ever fires."""
    p, d, proxy, clients, router = _disagg_served(proxy_on="d")
    c_d = clients[1]
    try:
        req = router.submit([9, 1, 4, 2, 6, 8, 1, 3], 16)
        dropped = False
        for _ in range(8000):
            router.pump()
            if router.idle():
                break
            if not dropped and router._migrations:
                proxy.drop_connections()   # blocks mid-flight
                dropped = True
            for s in (p, d):
                s.tick()
            time.sleep(0.001)
        assert dropped, "migration never started"
        assert req.state is RequestState.FINISHED
        assert req.output_tokens == reference([9, 1, 4, 2, 6, 8, 1, 3], 16)
        assert req.replica == "d"
        assert d.fake.imports_committed == 1
        assert c_d.reconnects >= 1
        snap = router.registry.snapshot()
        assert snap.get("fleet/kv_migrate_completed") == 1.0
        assert snap.get("fleet/kv_migrate_failed", 0.0) == 0.0
        assert snap.get("fleet/failovers", 0.0) == 0.0
    finally:
        cleanup(router, [p, d], [proxy])


def test_migration_torn_frame_degrades_to_replay():
    """A frame torn mid-migration on the source's event leg: the
    decoder refuses the partial frame, the source verdicts, and the
    stream recovers through the ordinary replay — never a corrupt
    cache, never a divergent token."""
    clock = [0.0]
    p, d, proxy, clients, router = _disagg_served(
        proxy_on="p", heartbeat_timeout_s=0.5, probe_retries=2,
        probe_backoff_s=0.1, clock=lambda: clock[0])
    try:
        req = router.submit([9, 1, 4], 16)
        armed = False
        for _ in range(8000):
            router.pump()
            if router.idle():
                break
            if not armed and router._migrations:
                proxy.tear_next_frame()    # tears meta/block mid-export
                armed = True
            for s in (p, d):
                s.tick()
            if armed:
                clock[0] += 0.05
            time.sleep(0.001)
        assert armed, "migration never started"
        assert req.state is RequestState.FINISHED
        assert req.output_tokens == reference([9, 1, 4], 16)
        assert router._migrations == {}
        snap = router.registry.snapshot()
        started = snap.get("fleet/kv_migrate_started", 0.0)
        done = snap.get("fleet/kv_migrate_completed", 0.0)
        failed = snap.get("fleet/kv_migrate_failed", 0.0)
        assert started >= 1.0 and started == done + failed
    finally:
        cleanup(router, [p, d], [proxy])


def test_migration_slow_link_still_completes():
    """A slowed (not dead) migration link: the handoff takes longer but
    completes — block frames trickle through, the commit lands, and
    the stream stays bitwise identical."""
    p, d, proxy, clients, router = _disagg_served(proxy_on="d")
    try:
        proxy.slow(0.02)
        req = router.submit([9, 1, 4], 16)
        sock_drive(router, [p, d], max_iters=8000, sleep_s=0.02,
                   tick_every=2)
        assert req.state is RequestState.FINISHED
        assert req.output_tokens == reference([9, 1, 4], 16)
        assert req.replica == "d"
        snap = router.registry.snapshot()
        assert snap.get("fleet/kv_migrate_completed") == 1.0
        assert snap.get("fleet/kv_migrate_failed", 0.0) == 0.0
    finally:
        cleanup(router, [p, d], [proxy])


def test_migration_dst_sigkill_mid_migration_reprefills():
    """Decode-replica SIGKILL with the handoff in flight: the crash
    shape (no bye), the ladder downs it, and the request re-prefills on
    the surviving prefill replica — the ISSUE 16 torn-transfer
    contract: degrade to re-prefill, never a corrupt cache."""
    clock = [0.0]
    p, d, proxy, clients, router = _disagg_served(
        heartbeat_timeout_s=0.5, probe_retries=2,
        probe_backoff_s=0.1, clock=lambda: clock[0])
    try:
        req = router.submit([9, 1, 4], 16)
        killed = False
        for _ in range(8000):
            router.pump()
            if router.idle():
                break
            if not killed and router._migrations:
                d.kill()                   # SIGKILL shape: no goodbye
                killed = True
            for s in (p, d):
                s.tick()
            if killed:
                clock[0] += 0.05
            time.sleep(0.001)
        assert killed, "migration never started"
        assert req.state is RequestState.FINISHED
        assert req.output_tokens == reference([9, 1, 4], 16)
        assert req.replica == "p"
        assert router._views["d"].down
        assert router._migrations == {}
        snap = router.registry.snapshot()
        assert snap.get("fleet/kv_migrate_completed", 0.0) == 0.0
        assert snap.get("fleet/kv_migrate_failed", 0.0) >= 1.0
        assert p.fake.exports == {}        # pin released on resolve
    finally:
        cleanup(router, [p, d], [])
