"""The single-copy register as a plain state machine.

Upstream stateright's `examples/single-copy-register.rs`: `s` servers that
each keep one value and never talk to each other, `c` register clients
(`register.py`), an unordered network that delivers each message once. A
client writes to server `id % s` and reads from server `(id + 1) % s`, so with
two servers a read can miss the client's own write: the history is then not
linearizable. A delivery that changes no actor and sends nothing is not a
step, as in upstream's actor model.

A state is `(servers, clients, net)`: `servers[j]` is the stored value (the
writer's index, `None` for empty); `clients` as `register.py` has them; `net`
is the sorted tuple of messages in flight, each `(kind, src, dst, request_id,
value)`.

`decode_row` reads a state row of the system under test by the lane layout
its model documents, so that a counterexample it returns can be replayed
here step by step.

This file imports nothing of the system under test.
"""

from __future__ import annotations

from .register import NO_READ, client_init, linearizable, snapshot

PUT, GET, PUT_OK, GET_OK = "Put", "Get", "PutOk", "GetOk"


def init_state(c: int, s: int):
    net = tuple(sorted((PUT, s + i, (s + i) % s, s + i, i) for i in range(c)))
    return ((None,) * s, tuple(client_init() for _ in range(c)), net)


def _remove(net, k):
    return net[:k] + net[k + 1 :]


def successors(state, c: int, s: int):
    servers, clients, net = state
    out = set()
    for k, (kind, src, dst, rid, value) in enumerate(net):
        rest = _remove(net, k)
        if dst < s:
            if kind == PUT:
                new_servers = servers[:dst] + (value,) + servers[dst + 1 :]
                sent = (PUT_OK, dst, src, rid, None)
                out.add((new_servers, clients, tuple(sorted(rest + (sent,)))))
            elif kind == GET:
                sent = (GET_OK, dst, src, rid, servers[dst])
                out.add((servers, clients, tuple(sorted(rest + (sent,)))))
            continue
        i = dst - s
        phase, _read, _snap = clients[i]
        if kind == PUT_OK and phase == 0 and rid == dst:
            new = (1, NO_READ, snapshot(clients, i))
            new_clients = clients[:i] + (new,) + clients[i + 1 :]
            sent = (GET, dst, (dst + 1) % s, 2 * dst, None)
            out.add((servers, new_clients, tuple(sorted(rest + (sent,)))))
        elif kind == GET_OK and phase == 1 and rid == 2 * dst:
            new = (2, value, clients[i][2])
            new_clients = clients[:i] + (new,) + clients[i + 1 :]
            out.add((servers, new_clients, rest))
    return out


def is_violation(state) -> bool:
    return not linearizable(state[1])


def shortest_counterexample_depth(c: int, s: int):
    """Depth of the shallowest non-linearizable state, or None."""
    frontier = {init_state(c, s)}
    seen = set(frontier)
    depth = 0
    while frontier:
        if any(is_violation(t) for t in frontier):
            return depth
        nxt = set()
        for state in frontier:
            for t in successors(state, c, s):
                if t not in seen:
                    seen.add(t)
                    nxt.add(t)
        frontier = nxt
        depth += 1
    return None


def explore(c: int, s: int) -> int:
    frontier = [init_state(c, s)]
    seen = set(frontier)
    while frontier:
        nxt = []
        for state in frontier:
            for t in successors(state, c, s):
                if t not in seen:
                    seen.add(t)
                    nxt.append(t)
        frontier = nxt
    return len(seen)


# -- the system under test's row layout --------------------------------------
# Lanes: s server values (0 empty, v = client v-1's value), c client words
# (bits 0-1 phase, 2-5 read code, 6+2p peer p's phase at the read's
# invocation), then the network's envelope words, 0 for an empty slot:
# kind(4) << 28 | src(4) << 24 | dst(4) << 20 | payload(20).

_KINDS = {1: PUT, 2: GET, 3: PUT_OK, 4: GET_OK}


def _value_code(code):
    """Read code or GetOk payload value: 1 = empty register, 2 + k = client
    k's value."""
    return None if code == 1 else code - 2


def decode_row(row, c: int, s: int):
    row = [int(v) for v in row]
    servers = tuple(None if v == 0 else v - 1 for v in row[:s])
    clients = []
    for i in range(c):
        word = row[s + i]
        phase = word & 3
        code = (word >> 2) & 15
        read = NO_READ if code == 0 else _value_code(code)
        snap = (
            tuple(0 if p == i else (word >> (6 + 2 * p)) & 3 for p in range(c))
            if phase >= 1
            else ()
        )
        clients.append((phase, read, snap))
    net = []
    for word in row[s + c :]:
        if word == 0:
            continue
        kind = _KINDS[word >> 28]
        src, dst, pay = (word >> 24) & 15, (word >> 20) & 15, word & 0xFFFFF
        rid = pay & 15
        if kind == PUT:
            value = ((pay >> 4) & 7) - 1
        elif kind == GET_OK:
            value = _value_code((pay >> 4) & 15)
        else:
            value = None
        net.append((kind, src, dst, rid, value))
    return (servers, tuple(clients), tuple(sorted(net)))


def judge_path(rows, c: int, s: int) -> dict:
    """Replay a counterexample given as state rows. Returns the number of
    steps that are no step of this machine (the first row counts as one
    unless it is the initial state), whether the last state is linearizable
    after all, and the path's depth."""
    states = [decode_row(r, c, s) for r in rows]
    bad = int(states[0] != init_state(c, s))
    for a, b in zip(states, states[1:]):
        if b not in successors(a, c, s):
            bad += 1
    return {
        "invalid_steps": bad,
        "final_holds": not is_violation(states[-1]),
        "depth": len(states) - 1,
    }
