"""Self-healing fleet chaos suite (ISSUE 11): retry budget, hedged
requests, mutable router rotation, staleness, concurrent polling,
supervision (respawn/backoff/quarantine), autoscaling, the new
Prometheus families.  The CLI kill-and-heal acceptance smokes are in
`test_fleet_cli.py` and `test_fleet_multihost_cli.py`.

Tier-1: CPU-only; the pieces are driven deterministically in-process
(parked pollers, `tick()`/`evaluate_once()` by hand, injectable clocks
and backoff)."""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.models.zoo import mlp
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.reliability import RetryBudget, faults
from deeplearning4j_tpu.serving import (AgentClient, Autoscaler,
                                        CacheFetcher, CacheServer,
                                        CircuitBreaker, FleetSupervisor,
                                        ReplicaAgent, Router,
                                        parse_prometheus_text,
                                        router_metrics)
from fleet_helpers import (N_IN, N_OUT, _clean_faults, _http, _net,   # noqa: F401
                           _x)


def _start_fleet(n=2, poll_interval_s=3600.0, **router_kw):
    """N warmed in-process replicas behind a router whose background
    poller is parked (huge interval): health transitions are driven by
    poll_once(), deterministically."""
    servers = [_net(seed=0).serve(max_delay_ms=1.0) for _ in range(n)]
    router = Router([s.url for s in servers],
                    poll_interval_s=poll_interval_s, **router_kw).start()
    return servers, router


def _stop_all(router, servers):
    router.stop()
    for s in servers:
        s.stop()


class _FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


class _Handle:
    """In-process stand-in for `ReplicaProcess`: a real `ModelServer`
    with a settable exit code, so supervisor tests reap/respawn without
    subprocess spawn cost."""

    def __init__(self):
        self.server = _net(seed=0).serve(max_delay_ms=1.0)
        self._rc = None
        self.summary = {"url": self.server.url, "fresh_compiles": 0}

    @property
    def url(self):
        return self.server.url

    def wait_ready(self):
        return self.summary

    def poll(self):
        return self._rc

    def die(self, rc=-9):
        """SIGKILL equivalent: the server vanishes, the exit code shows
        up at the next supervisor poll."""
        self.server.stop()
        self._rc = rc

    def terminate(self):
        self.server.stop()  # ModelServer.stop == graceful drain
        self._rc = 0

    def kill(self):
        self.die(-9)

    def wait(self, timeout=None):
        return self._rc if self._rc is not None else 0


# -- retry budget ------------------------------------------------------------

def test_retry_budget_min_tokens_and_window():
    clk = _FakeClock()
    b = RetryBudget(ratio=0.1, min_tokens=2, window_s=10.0, clock=clk)
    # no traffic at all: the floor still allows min_tokens spends
    assert b.try_spend() and b.try_spend()
    assert not b.try_spend()
    assert b.stats()["exhausted_total"] == 1
    # the window slides: old spends age out and tokens come back
    clk.t += 11.0
    assert b.remaining() == 2.0
    assert b.try_spend()


def test_retry_budget_ratio_scales_with_traffic():
    clk = _FakeClock()
    b = RetryBudget(ratio=0.1, min_tokens=1, window_s=10.0, clock=clk)
    for _ in range(100):
        b.note_request()
    # 10% of 100 requests = 10 tokens
    assert b.remaining() == 10.0
    for _ in range(10):
        assert b.try_spend()
    assert not b.try_spend()
    st = b.stats()
    assert st["requests_in_window"] == 100
    assert st["spent_in_window"] == 10
    assert st["remaining"] == 0.0


# -- mutable rotation --------------------------------------------------------

def test_router_add_remove_replica_rotation_safe():
    servers, router = _start_fleet(n=1)
    extra = _net(seed=0).serve(max_delay_ms=1.0)
    try:
        assert router.healthy_count() == 1
        rep = router.add_replica(extra.url)
        assert rep.ready and router.healthy_count() == 2
        for i in range(4):
            code, _ = _http(router.url + "/v1/predict",
                            {"features": _x(1, seed=i).tolist()})
            assert code == 200
        router.poll_once()
        per = [r["stats"]["requests"] if r["stats"] else 0
               for r in router.stats()["replicas"]]
        assert all(n >= 1 for n in per), per  # both replicas served
        # removal is by URL and immediate; traffic keeps flowing
        assert router.remove_replica(servers[0].url) is not None
        assert len(router.replicas) == 1
        for i in range(2):
            code, _ = _http(router.url + "/v1/predict",
                            {"features": _x(1, seed=i).tolist()})
            assert code == 200
        assert router.remove_replica("http://127.0.0.1:1/none") is None
    finally:
        _stop_all(router, servers)
        extra.stop()


# -- hedging + budget --------------------------------------------------------

def test_hedge_fires_on_slow_replica_and_wins():
    servers, router = _start_fleet(n=2, hedge=True, hedge_floor_ms=20.0,
                                   hedge_ceil_ms=120.0)
    try:
        # first proxy attempt (the primary) stalls well past the hedge
        # delay; the hedge lands on the sibling and answers first
        faults.arm("router.proxy", "delay", delay_s=1.0)
        t0 = time.monotonic()
        code, body = _http(router.url + "/v1/predict",
                           {"features": _x(1).tolist()})
        elapsed = time.monotonic() - t0
        assert code == 200, body
        assert elapsed < 0.9, elapsed  # did NOT wait out the slow primary
        st = router.stats()
        assert st["hedges"] == 1
        assert st["hedge_wins"] == 1
        assert st["retry_budget"]["spent_total"] == 1  # the hedge paid
    finally:
        _stop_all(router, servers)


def test_hedge_respects_exhausted_budget():
    servers, router = _start_fleet(n=2, hedge=True, hedge_floor_ms=20.0,
                                   hedge_ceil_ms=60.0,
                                   retry_budget_ratio=0.0,
                                   retry_budget_min=0)
    try:
        faults.arm("router.proxy", "delay", delay_s=0.4)
        t0 = time.monotonic()
        code, _ = _http(router.url + "/v1/predict",
                        {"features": _x(1).tolist()})
        elapsed = time.monotonic() - t0
        # no token -> no hedge: the request rides out the slow primary
        assert code == 200
        assert elapsed >= 0.4
        st = router.stats()
        assert st["hedges"] == 0
        assert st["retry_budget"]["exhausted_total"] >= 1
    finally:
        _stop_all(router, servers)


def test_budget_exhaustion_degrades_to_single_attempt():
    """A dead replica still in rotation + zero budget: requests that
    draw the corpse get its 502 back (clean, single-attempt, no storm);
    requests that draw the live replica succeed — and the router's
    counters reconcile exactly with what the client saw."""
    servers, router = _start_fleet(n=2, retry_budget_ratio=0.0,
                                   retry_budget_min=0)
    try:
        router.poll_once()
        servers[0].stop()  # dead, but NOT re-polled: stays in rotation
        codes = []
        for i in range(4):
            code, _ = _http(router.url + "/v1/predict",
                            {"features": _x(1, seed=i).tolist()})
            codes.append(code)
        # round-robin alternates primaries: half hit the corpse
        assert sorted(codes) == [200, 200, 502, 502]
        st = router.stats()
        assert st["retries"] == 0                  # budget never allowed one
        assert st["unroutable"] == 2               # == client-observed 5xx
        assert st["retry_budget"]["exhausted_total"] == 2
        ok = sum(p["latency_hist_s"]["count"]
                 for p in st["priorities"].values())
        total = sum(p["requests"] for p in st["priorities"].values())
        assert ok == 2 and total == 4              # ok + unroutable == total
    finally:
        _stop_all(router, servers)


def test_default_budget_allows_failover_retry():
    servers, router = _start_fleet(n=2)
    try:
        router.poll_once()
        servers[0].stop()
        for i in range(4):
            code, body = _http(router.url + "/v1/predict",
                               {"features": _x(1, seed=i).tolist()})
            assert code == 200, body  # fail-over retry absorbed the corpse
        st = router.stats()
        assert st["retries"] >= 1
        assert st["unroutable"] == 0
    finally:
        _stop_all(router, servers)


# -- staleness ----------------------------------------------------------------

def test_stale_replica_excluded_from_fleet_aggregates():
    servers, router = _start_fleet(n=2, stats_staleness_s=0.25)
    try:
        for i in range(4):
            code, _ = _http(router.url + "/v1/predict",
                            {"features": _x(1, seed=i).tolist()})
            assert code == 200
        router.poll_once()
        st = router.stats()
        total_rows = st["rows_by_policy"]["f32"]
        assert total_rows == 4
        assert all(not r["stale"] for r in st["replicas"])
        servers[0].stop()
        time.sleep(0.3)        # replica 0's last good poll ages past bound
        router.poll_once()     # refreshes replica 1, fails on replica 0
        st = router.stats()
        by_idx = {r["index"]: r for r in st["replicas"]}
        assert by_idx[0]["stale"] is True
        assert by_idx[0]["last_ok_poll_age_s"] > 0.25
        assert by_idx[1]["stale"] is False
        # the dead replica's cached rows are history, not fleet state
        assert st["rows_by_policy"]["f32"] == (
            by_idx[1]["stats"]["rows"])
        assert st["rows_by_policy"]["f32"] < total_rows
        # ...and its serving families are gone from the /metrics page,
        # while the staleness age itself IS exported
        parsed = parse_prometheus_text(router_metrics(st))
        reps = {dict(lbl).get("replica")
                for lbl in parsed["dl4j_serving_rows_total"]}
        assert reps == {"1"}
        ages = {dict(lbl)["replica"]: v for lbl, v in
                parsed["dl4j_router_replica_stats_age_seconds"].items()}
        assert ages["0"] > 0.25
    finally:
        _stop_all(router, servers)


# -- concurrent polling -------------------------------------------------------

def test_concurrent_poll_is_not_serialized_by_a_wedged_replica():
    servers, router = _start_fleet(n=3)
    try:
        servers[2].stop()  # one dead sibling that must still get ejected
        # EVERY poll hangs 0.5s (router.poll fires once per replica):
        # serial polling would cost >= 3 x 0.5s, concurrent ~0.5s
        faults.arm("router.poll", "delay", delay_s=0.5, times=99)
        t0 = time.monotonic()
        healthy = router.poll_once()
        elapsed = time.monotonic() - t0
        assert elapsed < 1.2, f"polls serialized: {elapsed:.2f}s"
        assert healthy == 2  # the wedge did not mask the dead sibling
        assert faults.hits("router.poll") >= 3
    finally:
        _stop_all(router, servers)


def test_poll_raise_counts_as_unready():
    servers, router = _start_fleet(n=1)
    try:
        assert router.poll_once() == 1
        faults.arm("router.poll", "raise")
        assert router.poll_once() == 0   # injected failure = not ready
        assert router.poll_once() == 1   # one-shot plan: recovers after
    finally:
        _stop_all(router, servers)


# -- supervision --------------------------------------------------------------

def _fleet_with_supervisor(n=2, **kw):
    handles = [_Handle() for _ in range(n)]
    router = Router([h.url for h in handles],
                    poll_interval_s=3600.0).start()
    kw.setdefault("backoff_fn", lambda attempt: 0.0)
    sup = FleetSupervisor(spawn_fn=_Handle, router=router, initial=handles,
                          min_replicas=n, max_replicas=n, **kw)
    # not started: tests call tick() by hand for determinism
    return handles, router, sup


def test_supervisor_reaps_and_respawns_with_rereg():
    handles, router, sup = _fleet_with_supervisor(n=2)
    try:
        handles[0].die(rc=-9)
        sup.tick()                       # reap: out of rotation, backoff@0
        assert len(router.replicas) == 1
        st = sup.stats()
        assert st["states"]["running"] == 1
        sup.tick()                       # respawn due: new URL registered
        assert len(router.replicas) == 2
        assert router.poll_once() == 2
        st = sup.stats()
        assert st["restarts_total"] == 1
        assert st["states"]["running"] == 2
        # the healed slot re-registered its NEW ephemeral-port URL
        respawned = [s for s in st["slots"] if s["restarts"] == 1]
        assert respawned and router.find_replica(
            respawned[0]["url"]) is not None
        # traffic lands on the healed fleet
        for i in range(4):
            code, _ = _http(router.url + "/v1/predict",
                            {"features": _x(1, seed=i).tolist()})
            assert code == 200
    finally:
        sup.stop()
        router.stop()
        for h in sup.handles():
            h.terminate()


def test_supervisor_quarantines_crash_loop_then_probes():
    handles, router, sup = _fleet_with_supervisor(
        n=1, max_restarts=2, restart_window_s=100.0, quarantine_s=0.15)
    try:
        # every respawn fails at the spawn fault point: a deterministic
        # crash-loop. death 1 -> backoff; failed spawn = death 2 ->
        # quarantined (2 deaths in window), NOT hot-looped.
        faults.arm("supervisor.spawn", "raise", times=1)
        handles[0].die(rc=1)
        sup.tick()                       # reap -> backoff(0)
        sup.tick()                       # respawn attempt fails
        st = sup.stats()
        assert st["spawn_failures_total"] == 1
        assert st["states"]["quarantined"] == 1
        assert st["quarantines_total"] == 1
        sup.tick()                       # quarantine holds: no spawn yet
        assert sup.stats()["states"]["quarantined"] == 1
        time.sleep(0.2)                  # quarantine elapses
        sup.tick()                       # probe respawn (fault disarmed)
        st = sup.stats()
        assert st["states"]["running"] == 1
        assert st["restarts_total"] == 1
        assert router.poll_once() == 1
    finally:
        sup.stop()
        router.stop()
        for h in sup.handles():
            h.terminate()


def test_scale_down_drains_without_dropping_requests():
    handles, router, sup = _fleet_with_supervisor(n=2)
    sup.min_replicas = 1
    results = {"codes": [], "errors": 0}
    stop_load = threading.Event()

    def loader():
        i = 0
        while not stop_load.is_set():
            try:
                code, _ = _http(router.url + "/v1/predict",
                                {"features": _x(1, seed=i).tolist()},
                                timeout=10)
                results["codes"].append(code)
            except Exception:
                results["errors"] += 1
            i += 1

    threads = [threading.Thread(target=loader) for _ in range(3)]
    try:
        for t in threads:
            t.start()
        time.sleep(0.2)                  # load in flight
        assert sup.scale_down() is True  # drain-then-stop the emptiest
        time.sleep(0.2)                  # load continues on the survivor
        stop_load.set()
        for t in threads:
            t.join(timeout=15.0)
        assert results["errors"] == 0
        assert results["codes"] and all(c == 200 for c in results["codes"])
        st = sup.stats()
        assert st["states"]["running"] == 1
        assert st["states"]["stopped"] == 1
        assert sup.scale_down() is False  # refuses below min_replicas
    finally:
        stop_load.set()
        sup.stop()
        router.stop()
        for h in sup.handles():
            h.terminate()


def test_scale_up_bounded_by_max():
    handles, router, sup = _fleet_with_supervisor(n=1)
    sup.max_replicas = 2
    try:
        assert sup.scale_up() is True
        assert len(router.replicas) == 2
        assert sup.stats()["states"]["running"] == 2
        assert sup.scale_up() is False   # at max
    finally:
        sup.stop()
        router.stop()
        for h in sup.handles():
            h.terminate()


# -- autoscaler ---------------------------------------------------------------

class _SupProbe:
    def __init__(self):
        self.min_replicas, self.max_replicas = 1, 4
        self.ups = 0
        self.downs = 0
        self.running = 2

    def scale_up(self):
        self.ups += 1
        self.running += 1
        return True

    def scale_down(self):
        self.downs += 1
        self.running -= 1
        return True

    def running_count(self):
        return self.running


class _RepProbe:
    def __init__(self, queue_depth=0, p99=10.0, breaker="closed",
                 degraded=0):
        self.ready = True
        self._st = {"priorities": {"interactive":
                                   {"queue_depth": queue_depth}},
                    "latency_ms": {"p99": p99},
                    "degraded_batches": degraded,
                    "breaker": {"state": breaker}}

    def stale(self, s):
        return False

    @property
    def last_stats(self):
        return self._st


class _RouterProbe:
    stats_staleness_s = 10.0

    def __init__(self, reps):
        self.replicas = reps


def test_autoscaler_hysteresis_and_cooldown():
    clk = _FakeClock()
    sup = _SupProbe()
    hot = _RouterProbe([_RepProbe(queue_depth=100), _RepProbe()])
    a = Autoscaler(hot, sup, slo_p99_ms=500.0, consecutive=3,
                   cooldown_s=30.0, clock=clk)
    # one spiky evaluation does nothing; the streak must persist
    assert a.evaluate_once() == "hold"
    assert a.evaluate_once() == "hold"
    assert a.evaluate_once() == "scale_up"
    assert sup.ups == 1
    # cooldown: the same raw signal cannot act again yet
    for _ in range(5):
        assert a.evaluate_once() == "hold"
    assert sup.ups == 1
    clk.t += 31.0                       # cooldown over; streak rebuilds
    assert a.evaluate_once() == "hold"
    assert a.evaluate_once() == "hold"
    assert a.evaluate_once() == "scale_up"
    assert sup.ups == 2
    st = a.stats()
    assert st["decisions"]["scale_up"] == 2
    assert st["signals"]["queue_depth"] == 100


def test_autoscaler_scales_down_idle_fleet_and_p99_breach_up():
    clk = _FakeClock()
    sup = _SupProbe()
    idle = _RouterProbe([_RepProbe(queue_depth=0, p99=5.0),
                         _RepProbe(queue_depth=0, p99=5.0)])
    a = Autoscaler(idle, sup, slo_p99_ms=500.0, consecutive=2,
                   cooldown_s=0.0, clock=clk)
    assert a.evaluate_once() == "hold"
    assert a.evaluate_once() == "scale_down"
    assert sup.downs == 1
    # p99 over the SLO is an up signal even with empty queues
    slow = _RouterProbe([_RepProbe(queue_depth=0, p99=900.0)])
    a2 = Autoscaler(slow, sup, slo_p99_ms=500.0, consecutive=1,
                    cooldown_s=0.0, clock=clk)
    assert a2.evaluate_once() == "scale_up"


class _PartSupProbe(_SupProbe):
    """Supervisor probe that also reports partitioned slots."""

    def __init__(self, partitioned=1):
        super().__init__()
        self.partitioned = partitioned

    def stats(self):
        return {"states": {"partitioned": self.partitioned}}


def test_autoscaler_holds_partitioned_capacity():
    clk = _FakeClock()
    sup = _PartSupProbe(partitioned=1)
    hot = _RouterProbe([_RepProbe(queue_depth=100), _RepProbe()])
    a = Autoscaler(hot, sup, slo_p99_ms=500.0, consecutive=2,
                   cooldown_s=30.0, clock=clk)
    assert a.evaluate_once() == "hold"            # streak building
    # streak satisfied, but partitioned capacity still exists on the far
    # side of the partition: the scale-up is REFUSED, not just delayed
    assert a.evaluate_once() == "hold_partitioned"
    assert sup.ups == 0
    # no cooldown was taken — the moment the partition resolves, the
    # already-built streak acts immediately
    sup.partitioned = 0
    assert a.evaluate_once() == "scale_up"
    assert sup.ups == 1
    assert a.stats()["decisions"]["hold_partitioned"] == 1


# -- replica agent: the per-host control plane (ISSUE 20) ---------------------

def _start_agents(n_agents=1, max_replicas=4):
    """In-process agents whose spawn_fn makes real in-process replicas
    (`_Handle` wraps a warmed `ModelServer`); returns (agents, spawned)."""
    spawned = []

    def spawn_fn(argv):
        assert argv and argv[0] == "serve"
        h = _Handle()
        spawned.append(h)
        return h

    agents = [ReplicaAgent(spawn_fn, max_replicas=max_replicas).start()
              for _ in range(n_agents)]
    return agents, spawned


def _stop_agents(agents):
    for a in agents:
        a.stop(terminate_children=True, drain_timeout_s=5.0)


def test_agent_control_plane_spawn_stop_and_clean_errors():
    agents, spawned = _start_agents(max_replicas=1)
    agent = agents[0]
    try:
        client = AgentClient(agent.url, timeout_s=5.0)
        h = client.spawn(["serve"])
        assert h.url and h.poll() is None
        assert h.wait_ready()["url"] == h.url
        assert agent.health()["replicas"] == 1
        # capacity bound: the agent is a bounded nursery, not a fork bomb
        with pytest.raises(RuntimeError, match="409"):
            client.spawn(["serve"])
        # only `serve` argv is accepted — the agent is not a remote shell
        code, text = _http(agent.url + "/a/spawn", {"argv": ["rm", "-rf"]})
        assert code == 400 and "error" in json.loads(text)
        # malformed JSON body -> clean 400, not a handler crash
        req = urllib.request.Request(
            agent.url + "/a/spawn", data=b"{not json",
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=10)
        assert e.value.code == 400
        # unknown replica id -> 404
        code, _ = _http(agent.url + "/a/stop", {"id": 99})
        assert code == 404
        # unknown path -> 404 JSON
        code, text = _http(agent.url + "/a/nope")
        assert code == 404 and "error" in json.loads(text)
        # graceful stop reports the drained exit code; the snapshot and
        # the remote handle's poll() see it
        out = client.stop(h.rid, wait=True)
        assert out["exit_code"] == 0
        assert h.poll() == 0
        recs = client.refresh()
        assert [r["alive"] for r in recs] == [False]
        assert agent.health()["replicas"] == 0
        # a vacated slot frees capacity again
        h2 = client.spawn(["serve"])
        assert h2.rid != h.rid
        assert agent.health()["spawns_total"] == 2
    finally:
        _stop_agents(agents)


def test_agent_serves_cache_entries_with_counters(tmp_path):
    (tmp_path / "deadbeef.jxp").write_bytes(b"jxp-bytes")
    agent = ReplicaAgent(lambda argv: _Handle(), cache_dir=str(tmp_path),
                         max_replicas=1).start()
    try:
        code, text = _http(agent.url + "/a/cache/deadbeef.jxp")
        assert code == 200 and text == "jxp-bytes"
        code, _ = _http(agent.url + "/a/cache/cafecafe.jxp")   # absent
        assert code == 404
        code, _ = _http(agent.url + "/a/cache/..%2Fetc%2Fpasswd")
        assert code == 404                                      # bad name
        h = agent.health()
        assert h["cache_requests_total"] == 3
        assert h["cache_hits_total"] == 1
    finally:
        agent.stop()


# -- lease-based remote supervision ------------------------------------------

def _remote_fleet(client, handle, **kw):
    router = Router([handle.url], poll_interval_s=3600.0).start()
    kw.setdefault("backoff_fn", lambda attempt: 0.0)
    sup = FleetSupervisor(spawn_fn=None, router=router, initial=[handle],
                          min_replicas=1, max_replicas=1,
                          agents=[client] if not isinstance(client, list)
                          else client,
                          remote_argv=["serve"], **kw)
    return router, sup


def test_remote_replica_death_respawns_through_agent():
    agents, spawned = _start_agents()
    agent = agents[0]
    router = sup = None
    try:
        client = AgentClient(agent.url, timeout_s=5.0)
        h = client.spawn(["serve"])
        router, sup = _remote_fleet(client, h)
        spawned[0].die(rc=-9)
        sup.tick()        # heartbeat refreshes the snapshot; reap
        st = sup.stats()
        assert st["states"]["backoff"] == 1
        assert st["slots"][0]["last_exit"] == -9
        assert len(router.replicas) == 0
        sup.tick()        # respawn goes THROUGH the agent
        st = sup.stats()
        assert st["states"]["running"] == 1
        assert st["restarts_total"] == 1
        assert st["slots"][0]["agent"] == client.url
        assert agent.health()["spawns_total"] == 2
        assert len(router.replicas) == 1
    finally:
        if sup:
            sup.stop()
        if router:
            router.stop()
        _stop_agents(agents)


def test_lease_partition_holds_slots_then_heal_adopts_no_double_spawn():
    agents, spawned = _start_agents()
    agent = agents[0]
    router = sup = None
    try:
        client = AgentClient(agent.url, timeout_s=5.0)
        h = client.spawn(["serve"])
        router, sup = _remote_fleet(client, h, lease_misses=2,
                                    agent_failover_s=1e9)
        sup.tick()                              # healthy lease
        assert sup.stats()["states"]["running"] == 1
        faults.arm("agent.partition", "raise", times=3)
        sup.tick()                              # miss 1: lease holds
        assert sup.stats()["states"]["running"] == 1
        sup.tick()                              # miss 2: partitioned
        st = sup.stats()
        assert st["states"]["partitioned"] == 1
        assert st["partitions_total"] == 1
        assert len(router.replicas) == 0        # out of rotation...
        sup.tick()                              # miss 3: held, no respawn
        assert sup.stats()["states"]["partitioned"] == 1
        assert agent.health()["spawns_total"] == 1   # ...but NOT respawned
        sup.tick()                              # plan exhausted: heal
        st = sup.stats()
        assert st["states"]["running"] == 1
        assert st["adopted_total"] == 1
        assert len(router.replicas) == 1
        # zero double-spawns: reconcile ADOPTED the live replica
        assert agent.health()["spawns_total"] == 1
        assert agent.health()["replicas"] == 1
        ag = st["agents"][0]
        assert ag["state"] == "leased" and ag["reconciles_total"] == 1
    finally:
        if sup:
            sup.stop()
        if router:
            router.stop()
        _stop_agents(agents)


class _FlakyClient(AgentClient):
    """AgentClient whose heartbeat can be switched off: a partition
    between supervisor and ONE healthy agent, injected per-client."""

    offline = False

    def refresh(self):
        if self.offline:
            raise OSError("injected partition")
        return super().refresh()


def test_partition_failover_lands_on_survivor_then_heal_stops_orphan():
    agents, spawned = _start_agents(n_agents=2)
    a0, a1 = agents
    router = sup = None
    try:
        clients = [_FlakyClient(a.url, timeout_s=5.0) for a in agents]
        clk = _FakeClock()
        h = clients[0].spawn(["serve"])
        router, sup = _remote_fleet(clients, h, lease_misses=1,
                                    agent_failover_s=30.0, clock=clk)
        clients[0].offline = True
        sup.tick()                      # 1 miss -> partitioned, held
        assert sup.stats()["states"]["partitioned"] == 1
        assert len(router.replicas) == 0
        assert a1.health()["spawns_total"] == 0
        clk.t += 31.0
        sup.tick()                      # past failover: respawn on survivor
        st = sup.stats()
        assert st["states"]["running"] == 1
        assert st["failovers_total"] == 1
        assert st["slots"][0]["agent"] == clients[1].url
        assert a1.health()["spawns_total"] == 1
        assert len(router.replicas) == 1
        # partition heals: the old child on agent0 is no longer intended
        # (its slot failed over) — reconcile stops the orphan
        clients[0].offline = False
        sup.tick()
        st = sup.stats()
        ag0 = next(a for a in st["agents"] if a["url"] == clients[0].url)
        assert ag0["state"] == "leased"
        assert ag0["orphans_stopped_total"] == 1
        assert a0.health()["replicas"] == 0
        # intent stayed at one replica: exactly one spawn per agent, ever
        assert a0.health()["spawns_total"] == 1
        assert a1.health()["spawns_total"] == 1
        assert st["states"]["running"] == 1
    finally:
        if sup:
            sup.stop()
        if router:
            router.stop()
        _stop_agents(agents)


# -- compile-cache distribution (serving/cachesync.py) ------------------------

def _warmed_net_with_store(cache_dir, shapes=(1, 2)):
    net = MultiLayerNetwork(mlp(n_in=N_IN, hidden=[8], n_out=N_OUT,
                                lr=0.05), seed=0).init()
    store = net.set_compile_cache(str(cache_dir))
    net.warmup(list(shapes))
    return net, store


def test_cold_store_warms_over_the_wire_and_corrupt_fetch_is_counted(
        tmp_path):
    warm_net, warm_store = _warmed_net_with_store(tmp_path / "warm")
    server = CacheServer(str(tmp_path / "warm")).start()
    try:
        # cold host, clean wire: every program arrives by fetch, zero
        # fresh compiles, and the answers match the warm host bitwise
        cold_net, cold_store = (
            MultiLayerNetwork(mlp(n_in=N_IN, hidden=[8], n_out=N_OUT,
                                  lr=0.05), seed=0).init(), None)
        cold_store = cold_net.set_compile_cache(str(tmp_path / "cold"))
        cold_store.set_remote(CacheFetcher([server.url], timeout_s=5.0))
        cold_net.warmup([1, 2])
        assert cold_store.fetch_hits > 0
        assert cold_store.fetch_corrupt == 0
        x = _x(2, seed=3)
        np.testing.assert_array_equal(np.asarray(cold_net.output(x)),
                                      np.asarray(warm_net.output(x)))
        # corrupted fetch: checksum validation rejects it, counts it,
        # and falls back to compiling — never a crash, never bad bytes
        cold2 = MultiLayerNetwork(mlp(n_in=N_IN, hidden=[8], n_out=N_OUT,
                                      lr=0.05), seed=0).init()
        store2 = cold2.set_compile_cache(str(tmp_path / "cold2"))
        fetcher = CacheFetcher([server.url], timeout_s=5.0)
        store2.set_remote(fetcher)
        faults.arm("agent.cache_fetch", "corrupt", times=1)
        cold2.warmup([1])
        assert store2.fetch_corrupt == 1
        np.testing.assert_array_equal(np.asarray(cold2.output(_x(1))),
                                      np.asarray(warm_net.output(_x(1))))
    finally:
        server.stop()


# -- failure-domain-aware hedging ---------------------------------------------

def test_hedge_and_retry_prefer_a_different_host():
    r1 = Router.__new__(Router)  # only _prefer_other_hosts is exercised
    mk = lambda host: type("R", (), {"host": host})()  # noqa: E731
    a, b, c, d = mk("h1"), mk("h1"), mk("h2"), mk("h2")
    # tail reordered: different-host replicas first, same-host last
    out = Router._prefer_other_hosts([a, b, c, d])
    assert [r.host for r in out] == ["h1", "h2", "h2", "h1"]
    # single-host fleet (or a 2-replica rotation): untouched
    assert Router._prefer_other_hosts([a, b]) == [a, b]
    same = [mk("h1"), mk("h1"), mk("h1")]
    assert Router._prefer_other_hosts(same) == same
    assert r1 is not None


def test_hedge_under_half_open_breaker_counts_probe_outcome_once():
    """Satellite 4: a hedge fired while the primary's breaker is
    HALF_OPEN must count the probe outcome exactly once — the hedge's
    outcome lands on the hedge replica's breaker, the slow probe's own
    success lands on the primary's, and neither double-transitions."""
    servers, router = _start_fleet(n=2, hedge=True, hedge_floor_ms=1.0,
                                   hedge_ceil_ms=50.0)
    try:
        assert router.poll_once() == 2
        primary = router.replicas[0]
        primary.breaker = CircuitBreaker(failure_threshold=3,
                                         reset_timeout_s=0.0,
                                         probe_prob=1.0)
        for _ in range(3):
            primary.breaker.record_failure()
        # reset_timeout 0: tripped, and already reporting HALF_OPEN
        assert primary.breaker.stats()["state"] == "half_open"
        assert primary.breaker.stats()["opens"] == 1
        # reset_timeout 0 + probe_prob 1: the next allow() is a half-open
        # probe, so the primary re-enters rotation exactly as a probe
        faults.arm("router.proxy", "delay", delay_s=0.4, nth=1, times=1)
        code, text = _http(router.url + "/v1/predict",
                           {"features": _x(1, seed=5).tolist()}, timeout=30)
        assert code == 200          # the hedge answered while the probe ran
        st = router.stats()
        assert st["hedges"] == 1 and st["hedge_wins"] == 1
        # the delayed probe eventually completes against its replica
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            bs = primary.breaker.stats()
            if bs["successes"] == 1:
                break
            time.sleep(0.02)
        bs = primary.breaker.stats()
        assert bs["successes"] == 1      # counted exactly once
        assert bs["state"] == "closed"   # probe success closes it...
        assert bs["opens"] == 1          # ...with no second transition
    finally:
        _stop_all(router, servers)


# -- Prometheus conformance ---------------------------------------------------

def test_new_metric_families_parse_and_stay_monotonic():
    handles, router, sup = _fleet_with_supervisor(n=2)
    a = Autoscaler(router, sup, clock=time.monotonic)
    router.attach_fleet(sup, a)
    try:
        a.evaluate_once()
        text1 = router_metrics(router.stats())
        parsed1 = parse_prometheus_text(text1)  # strict: raises on junk
        for fam in ("dl4j_router_hedges_total",
                    "dl4j_router_hedge_wins_total",
                    "dl4j_router_retry_budget_remaining",
                    "dl4j_router_retry_budget_exhausted_total",
                    "dl4j_fleet_restarts_total",
                    "dl4j_fleet_spawn_failures_total"):
            assert fam in parsed1, fam
        states = {dict(lbl)["state"]
                  for lbl in parsed1["dl4j_fleet_replicas"]}
        assert {"running", "backoff", "quarantined", "stopped"} <= states
        assert parsed1["dl4j_fleet_replicas"][(("state", "running"),)] == 2
        decisions = {dict(lbl)["decision"]
                     for lbl in parsed1["dl4j_autoscaler_decisions_total"]}
        assert decisions == {"scale_up", "scale_down", "hold",
                             "hold_partitioned"}
        assert "dl4j_autoscaler_target_replicas" in parsed1
        # traffic + a restart move the counters the right way only
        for i in range(2):
            _http(router.url + "/v1/predict",
                  {"features": _x(1, seed=i).tolist()})
        handles[0].die()
        sup.tick()
        sup.tick()
        a.evaluate_once()
        parsed2 = parse_prometheus_text(router_metrics(router.stats()))
        for fam, series in parsed1.items():
            if not fam.endswith("_total"):
                continue
            for lbl, v1 in series.items():
                v2 = parsed2.get(fam, {}).get(lbl)
                if v2 is not None:
                    assert v2 >= v1, (fam, lbl, v1, v2)
        assert parsed2["dl4j_fleet_restarts_total"][()] == 1
    finally:
        sup.stop()
        router.stop()
        for h in sup.handles():
            h.terminate()
