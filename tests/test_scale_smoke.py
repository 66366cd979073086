"""Scale smoke: the control-plane protocol at 64 concurrent agents.

64 threads, a client each, drive the real rendezvous protocol (join ->
close -> result fan-out), the store barrier and the counter-based checkpoint
consensus (``store_sync_fn``: one ADD per rank and one read per poll) against
one store server.  What is asserted is the protocol's outcome — every agent
joined the one round and got the same world, every barrier caller released,
every consensus call completed — and, as a guard against a hang or an
order-of-magnitude regression, a loose bound on the host's clock.  No speed
is read off this: the control plane's share of a fault episode is
``PERF.md``'s to state.
"""

import threading
import time

from tpu_resiliency.checkpointing.async_ckpt.core import store_sync_fn
from tpu_resiliency.fault_tolerance.rendezvous import (
    NodeDesc,
    RendezvousHost,
    RendezvousJoiner,
)
from tpu_resiliency.store import StoreClient, barrier


def _clients(port: int, n: int) -> list:
    return [StoreClient("127.0.0.1", port, timeout=120.0) for _ in range(n)]


def rendezvous(port: int, n: int) -> tuple:
    """Seconds to the round's close and to the last agent's result."""
    host_client = StoreClient("127.0.0.1", port, timeout=120.0)
    host = RendezvousHost(host_client, min_nodes=n, max_nodes=n, settle_time=0.1)
    host.bootstrap()
    round_num = host.open_round()
    clients = _clients(port, n)
    results: list = [None] * n
    errors: list = []

    def agent(i: int) -> None:
        desc = NodeDesc.create(node_id=f"node-{i}", slots=1)
        joiner = RendezvousJoiner(clients[i], desc, open_poll_interval=0.05)
        try:
            results[i] = joiner.join(timeout=180.0)
        except Exception as exc:  # noqa: BLE001
            errors.append(repr(exc))

    threads = [threading.Thread(target=agent, args=(i,)) for i in range(n)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    closed = host.close_round_when_ready(timeout=180.0)
    close_latency = time.monotonic() - t0
    for t in threads:
        t.join(timeout=180)
    total_latency = time.monotonic() - t0
    for c in clients:
        c.close()
    host_client.close()
    assert not errors, errors[:3]
    assert closed == round_num
    worlds = {r.group_world_size for r in results if r is not None}
    assert worlds == {n}, worlds
    return close_latency, total_latency


def barrier_fanin(port: int, n: int) -> float:
    clients = _clients(port, n)
    t0 = time.monotonic()
    threads = [
        threading.Thread(
            target=barrier,
            args=(clients[i], f"fanin-{n}", n),
            kwargs={"timeout": 180.0, "poll_interval": 0.02},
        )
        for i in range(n)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    elapsed = time.monotonic() - t0
    assert not any(t.is_alive() for t in threads), "a caller never released"
    for c in clients:
        c.close()
    return elapsed


def consensus(port: int, n: int, calls: int) -> float:
    """Seconds a call, publish from every rank to rank 0 seeing it whole."""
    clients = _clients(port, n)
    syncs = [
        store_sync_fn(clients[i], rank=i, world_size=n, namespace=f"consensus{n}")
        for i in range(n)
    ]
    t0 = time.monotonic()
    for idx in range(calls):
        def publish(i: int) -> None:
            syncs[i](idx, True)

        threads = [threading.Thread(target=publish, args=(i,)) for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        # rank 0 polls to global completion: counter scheme = 1 read/poll
        while not syncs[0](idx, True):
            time.sleep(0.001)
    elapsed = time.monotonic() - t0
    for c in clients:
        c.close()
    return elapsed / calls


def test_rendezvous_64_agents(store_server):
    round_close_s, result_fanout_s = rendezvous(store_server.port, 64)
    assert round_close_s < 20.0
    assert result_fanout_s < 20.0


def test_barrier_and_consensus_64_agents(store_server):
    assert barrier_fanin(store_server.port, 64) < 5.0
    assert consensus(store_server.port, 64, calls=2) < 5.0
