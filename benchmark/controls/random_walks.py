"""Control for counterexample cells, whose guarantee is the least depth:
switch the program to its own random-walk engine (`spawn_simulation`, a new
walk seed for every check, drawn from the seed). Its counterexamples are
real but need not be shortest. (Its depth-first engine happens to meet the
shortest one first on these specs, so it would separate nothing.)
"""

from __future__ import annotations

import itertools


def install(seed: int) -> None:
    from stateright_tpu.checker import CheckerBuilder

    seeds = itertools.count(seed)
    CheckerBuilder.spawn_tpu_bfs = lambda self, **_kw: self.spawn_simulation(next(seeds))
