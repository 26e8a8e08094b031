"""Single-decree Paxos as a plain state machine.

Upstream stateright's `examples/paxos.rs`: three servers that run one
instance of Paxos per client `Put`, `c` register clients (`register.py`),
and an unordered network that delivers each message once. A delivery that
changes no actor and sends nothing is not a step, as in upstream's actor
model. Upstream's test pins 16,668 unique states for two clients.

A state is `(servers, clients, net)`. A server is `(ballot, proposal,
prepares, accepts, accepted, decided)` with ballots `(round, server)`,
proposals `(request_id, requester, value)`, `prepares` a sorted tuple of
`(server, last_accepted)` and `accepts` a frozenset. `net` is a frozenset of
`(envelope, copies)`, so equal multisets are equal states.

    python benchmark/reference/paxos.py 2   # prints the count and discoveries

This file imports nothing of the system under test.
"""

from __future__ import annotations

import sys
from collections import Counter

from .register import NO_READ, client_init, linearizable, snapshot

SERVERS = 3
QUORUM = 2


def _net(envelopes):
    return frozenset(Counter(envelopes).items())


def init_state(c: int):
    server = ((0, 0), None, (), frozenset(), None, False)
    puts = [(SERVERS + i, (SERVERS + i) % SERVERS, ("Put", SERVERS + i, i)) for i in range(c)]
    return ((server,) * SERVERS, tuple(client_init() for _ in range(c)), _net(puts))


def _accepted_key(entry):
    return (0,) if entry is None else (1, entry)


def _server_step(me, state, src, msg):
    """(new state or None when unchanged, sends)."""
    ballot, proposal, prepares, accepts, accepted, decided = state
    peers = [p for p in range(SERVERS) if p != me]
    kind = msg[0]
    if decided:
        if kind == "Get":
            return None, [(me, src, ("GetOk", msg[1], accepted[1][2]))]
        return None, []
    if kind == "Put" and proposal is None:
        b = (ballot[0] + 1, me)
        sends = [(me, p, ("Prepare", b)) for p in peers]
        new = (b, (msg[1], src, msg[2]), ((me, accepted),), frozenset(), accepted, decided)
        return new, sends
    if kind == "Prepare" and ballot < msg[1]:
        b = msg[1]
        return (b, proposal, prepares, accepts, accepted, decided), [
            (me, src, ("Prepared", b, accepted))
        ]
    if kind == "Prepared" and msg[1] == ballot:
        got = dict(prepares)
        got[src] = msg[2]
        new_prepares = tuple(sorted(got.items(), key=lambda kv: kv[0]))
        if len(got) == QUORUM:
            best = max(got.values(), key=_accepted_key)
            chosen = best[1] if best is not None else proposal
            new = (ballot, chosen, new_prepares, frozenset({me}), (ballot, chosen), decided)
            return new, [(me, p, ("Accept", ballot, chosen)) for p in peers]
        return (ballot, proposal, new_prepares, accepts, accepted, decided), []
    if kind == "Accept" and ballot <= msg[1]:
        b = msg[1]
        return (b, proposal, prepares, accepts, (b, msg[2]), decided), [
            (me, src, ("Accepted", b))
        ]
    if kind == "Accepted" and msg[1] == ballot:
        new_accepts = accepts | {src}
        if len(new_accepts) == QUORUM:
            sends = [(me, p, ("Decided", ballot, proposal)) for p in peers]
            sends.append((me, proposal[1], ("PutOk", proposal[0])))
            return (ballot, proposal, prepares, new_accepts, accepted, True), sends
        return (ballot, proposal, prepares, new_accepts, accepted, decided), []
    if kind == "Decided":
        b = msg[1]
        return (b, proposal, prepares, accepts, (b, msg[2]), True), []
    return None, []


def successors(state, c: int):
    servers, clients, net = state
    out = set()
    bag = dict(net)
    for env in bag:
        src, dst, msg = env
        rest = Counter(bag)
        rest[env] -= 1
        if not rest[env]:
            del rest[env]
        if dst < SERVERS:
            new, sends = _server_step(dst, servers[dst], src, msg)
            if new is None and not sends:
                continue
            new_servers = servers if new is None else servers[:dst] + (new,) + servers[dst + 1 :]
            new_clients = clients
        else:
            i = dst - SERVERS
            phase = clients[i][0]
            if msg[0] == "PutOk" and phase == 0 and msg[1] == dst:
                new_client = (1, NO_READ, snapshot(clients, i))
                sends = [(dst, (dst + 1) % SERVERS, ("Get", 2 * dst))]
            elif msg[0] == "GetOk" and phase == 1 and msg[1] == 2 * dst:
                new_client = (2, msg[2], clients[i][2])
                sends = []
            else:
                continue
            new_servers = servers
            new_clients = clients[:i] + (new_client,) + clients[i + 1 :]
        for e in sends:
            rest[e] += 1
        out.add((new_servers, new_clients, frozenset(rest.items())))
    return out


def value_chosen(state) -> bool:
    return any(msg[0] == "GetOk" and msg[2] is not None for (_s, _d, msg), _n in state[2])


def explore(c: int):
    """(unique count, names of the properties with a discovery)."""
    start = init_state(c)
    seen = {start}
    frontier = [start]
    chosen = False
    broken = False
    while frontier:
        nxt = []
        for state in frontier:
            chosen = chosen or value_chosen(state)
            broken = broken or not linearizable(state[1])
            for t in successors(state, c):
                if t not in seen:
                    seen.add(t)
                    nxt.append(t)
        frontier = nxt
    found = (["linearizable"] if broken else []) + (["value chosen"] if chosen else [])
    return len(seen), found


if __name__ == "__main__":
    count, found = explore(int(sys.argv[1]))
    print(count, found)
