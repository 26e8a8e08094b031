"""Register clients and a brute-force linearizability check.

The clients of upstream stateright's `RegisterActor::Client` with
`put_count = 1`: client `i` (actor id `servers + i`) writes its own value
`i`, and once the write is acknowledged it reads once. The history the
linearizability tester keeps is carried as each client's
`(phase, read, snapshot)`:

- `phase`: 0 while the write is in flight, 1 while the read is in flight,
  2 when both have returned;
- `read`: what the read returned, `NO_READ` before then, `None` for the empty
  register, else the writer's index;
- `snapshot`: the phase of every other client at the moment this client's
  read was invoked, which is all the real-time order that a history of one
  write then one read per client has. `()` before the read.

`linearizable` searches every order of the operations (and every subset of
the writes still in flight) for one that respects real time and register
semantics. It is exponential and meant for a handful of clients.

This file imports nothing of the system under test.
"""

from __future__ import annotations

from itertools import permutations

NO_READ = "no-read"


def client_init():
    return (0, NO_READ, ())


def linearizable(clients) -> bool:
    c = len(clients)
    ops = []  # (kind, client); completed ones must appear
    pending_writes = []
    for i, (phase, _read, _snap) in enumerate(clients):
        if phase >= 1:
            ops.append(("w", i))
        else:
            pending_writes.append(("w", i))
        if phase == 2:
            ops.append(("r", i))

    def before(a, b) -> bool:
        """True when `a` returned before `b` was invoked."""
        kind_b, j = b
        if kind_b == "w":
            return False  # every write is invoked at the start
        phase_j, _read_j, snap = clients[j]
        kind_a, i = a
        if i == j:
            return kind_a == "w"
        peer = snap[i]
        return peer >= 1 if kind_a == "w" else peer == 2

    def legal(order) -> bool:
        value = None
        for pos, op in enumerate(order):
            for earlier in order[pos + 1 :]:
                if before(earlier, op):
                    return False
            kind, i = op
            if kind == "w":
                value = i
            elif clients[i][1] != value:
                return False
        return True

    for mask in range(1 << len(pending_writes)):
        chosen = ops + [w for k, w in enumerate(pending_writes) if mask >> k & 1]
        # A read of a value whose write is not chosen can never be legal.
        if any(
            op[0] == "r"
            and clients[op[1]][1] is not None
            and ("w", clients[op[1]][1]) not in chosen
            for op in chosen
        ):
            continue
        if any(legal(order) for order in permutations(chosen)):
            return True
    return c == 0


def snapshot(clients, i):
    return tuple(p if k != i else 0 for k, (p, _r, _s) in enumerate(clients))
