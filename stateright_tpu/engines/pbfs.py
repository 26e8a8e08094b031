"""Parallel host BFS for RICH Python models: multiprocessing ownership shards.

Closes the reference's `.threads(n)`-for-any-model capability
(job_market.rs:59-182 + bfs.rs:90-164). The reference parallelizes with
OS threads over a shared DashMap; under CPython the GIL makes that shape
worthless, so this engine re-designs it the same way the device mesh
engine re-designed multi-chip checking: N worker PROCESSES, each OWNING
the fingerprint range `fp % N == w` — its own visited dict (fp -> parent
fp) and pending queue — exchanging candidate batches over pipes. Each
candidate crosses process boundaries exactly once, to its owner; dedup is
a plain dict lookup in the owner (no cross-process synchronization at
all). This is the job market's work-distribution role with ownership
routing in place of work stealing — the same trade the sharded device
engine makes (parallel/mesh.py), for the same reason: cheap local dedup
beats migrating shared state.

Semantics match the reference BFS state-for-state: property evaluation at
visit time, eventually-bit propagation along paths, the terminal rule,
boundary filtering, depth accounting, parent-pointer path reconstruction
(bfs.rs:196-334, 380-409). Like the reference's multithreaded BFS, visit
order differs run to run, discovery RACES are benign (first reported
wins), and `state_count` totals are exact.

Termination is the classic double-count protocol: the coordinator polls
(sent, received, idle) from every worker and stops when all are idle with
equal global sent/received counts on two consecutive polls.

Requirements: the model and its states must be picklable. Visitors are
not supported (they would serialize every path across processes).
"""

from __future__ import annotations

import multiprocessing as mp
import os
import queue as queue_mod
import time
from typing import Any, Dict, List, Optional, Tuple

from ..checker import CheckerBuilder
from ..core import Expectation
from ..path import Path
from .common import HostEngineBase

_BLOCK = 1500  # states per pop block, reference bfs.rs:130


def _worker(
    wid: int,
    n_workers: int,
    model_blob: bytes,
    depth_limit: Optional[int],
    coverage_enabled: bool,
    in_q,
    out_qs,
    ctl_q,
    res_q,
):
    """One ownership shard: own visited dict + pending queue + expansion."""
    import cloudpickle

    model = cloudpickle.loads(model_blob)
    visited: Dict[int, int] = {}  # fp -> parent fp (0 = init)
    pending: List[Tuple[Any, int, int, int]] = []  # (state, fp, ebits, depth)
    discoveries: Dict[str, int] = {}  # name -> fp
    properties = model.properties()
    state_count = 0
    max_depth = 0
    sent = 0
    received = 0
    stop = False
    last_report = 0.0
    # Shard-local coverage tallies (obs/coverage.py); shipped once with the
    # final report and merged into the coordinator's accumulator.
    cov_actions: Dict[str, int] = {}
    cov_depths: Dict[int, int] = {}
    cov_prop_evals: Dict[str, int] = {}
    cov_prop_hits: Dict[str, int] = {}
    label_memo: Dict[Any, str] = {}

    def action_label(action) -> str:
        try:
            label = label_memo.get(action)
            if label is None:
                label = model.format_action(action)
                label_memo[action] = label
            return label
        except TypeError:
            return model.format_action(action)

    def accept(batch):
        nonlocal received
        received += len(batch)
        for state, fp, parent_fp, ebits, depth in batch:
            if fp in visited:
                continue
            visited[fp] = parent_fp
            if coverage_enabled:
                cov_depths[depth] = cov_depths.get(depth, 0) + 1
            pending.append((state, fp, ebits, depth))

    def flush_out(buckets):
        # Local handoffs go through accept() too, so every candidate is
        # counted once in `sent` and once in `received` globally — the
        # invariant the quiescence protocol relies on.
        nonlocal sent
        for w, batch in enumerate(buckets):
            if not batch:
                continue
            sent += len(batch)
            if w == wid:
                accept(batch)
            else:
                out_qs[w].put(batch)

    def report(kind, epoch=0):
        nonlocal last_report
        if kind == "progress":
            now = time.monotonic()
            if now - last_report < 0.05:
                return
            last_report = now
        cov = (
            {
                "actions": cov_actions,
                "depths": cov_depths,
                "prop_evals": cov_prop_evals,
                "prop_hits": cov_prop_hits,
            }
            if kind == "final" and coverage_enabled
            else None
        )
        res_q.put(
            (
                kind,
                wid,
                epoch,
                state_count,
                len(visited),
                max_depth,
                sent,
                received,
                not pending,
                dict(discoveries),
                cov,
            )
        )

    while True:
        # Drain control messages (stop / termination-poll epochs). Poll
        # replies are the ONLY input to the coordinator's quiescence
        # decision: they carry counts sampled at reply time, tagged with
        # the epoch, so the coordinator never reasons from stale
        # unsolicited snapshots (a stale-pair race ended runs early in
        # round-5 verification).
        try:
            while True:
                msg = ctl_q.get_nowait()
                if msg == "stop":
                    stop = True
                elif isinstance(msg, tuple) and msg[0] == "poll":
                    # Answer AFTER draining the inbox so "idle" reflects
                    # everything already delivered to us.
                    try:
                        while True:
                            accept(in_q.get_nowait())
                    except queue_mod.Empty:
                        pass
                    report("poll_reply", msg[1])
        except queue_mod.Empty:
            pass
        if stop:
            break

        # Drain incoming candidates.
        drained = False
        try:
            while True:
                batch = in_q.get_nowait()
                accept(batch)
                drained = True
        except queue_mod.Empty:
            pass

        if not pending:
            if not drained:
                time.sleep(0.002)
            continue

        block = pending[-_BLOCK:]
        del pending[-len(block):]
        buckets: List[List] = [[] for _ in range(n_workers)]
        for state, fp, ebits, depth in block:
            state_count += 1
            if depth > max_depth:
                max_depth = depth
            if depth_limit is not None and depth >= depth_limit:
                continue

            is_awaiting = False
            for i, prop in enumerate(properties):
                if prop.name in discoveries:
                    continue
                if coverage_enabled:
                    cov_prop_evals[prop.name] = (
                        cov_prop_evals.get(prop.name, 0) + 1
                    )
                if prop.expectation == Expectation.ALWAYS:
                    if not prop.condition(model, state):
                        discoveries[prop.name] = fp
                        if coverage_enabled:
                            cov_prop_hits[prop.name] = (
                                cov_prop_hits.get(prop.name, 0) + 1
                            )
                    else:
                        is_awaiting = True
                elif prop.expectation == Expectation.SOMETIMES:
                    if prop.condition(model, state):
                        discoveries[prop.name] = fp
                        if coverage_enabled:
                            cov_prop_hits[prop.name] = (
                                cov_prop_hits.get(prop.name, 0) + 1
                            )
                    else:
                        is_awaiting = True
                else:  # EVENTUALLY
                    is_awaiting = True
                    if prop.condition(model, state):
                        ebits &= ~(1 << i)

            actions: List[Any] = []
            model.actions(state, actions)
            n_children = 0
            for action in actions:
                child = model.next_state(state, action)
                if child is None:
                    continue
                n_children += 1
                if not model.within_boundary(child):
                    continue
                if coverage_enabled:
                    label = action_label(action)
                    cov_actions[label] = cov_actions.get(label, 0) + 1
                cfp = model.fingerprint_state(child)
                buckets[cfp % n_workers].append((child, cfp, fp, ebits, depth + 1))
            if n_children == 0 and ebits:
                # Terminal eventually-counterexamples (bfs.rs:326-333).
                for i, prop in enumerate(properties):
                    if (ebits >> i) & 1 and prop.name not in discoveries:
                        discoveries[prop.name] = fp
                        if coverage_enabled:
                            cov_prop_hits[prop.name] = (
                                cov_prop_hits.get(prop.name, 0) + 1
                            )
        flush_out(buckets)
        report("progress")

    # Final: one last exact report, then the visited table for path
    # reconstruction.
    last_report = 0.0
    report("final")
    res_q.put(("table", wid, visited))


def _start_on_cpu(procs) -> None:
    """Start spawn-context `procs` with ``JAX_PLATFORMS=cpu`` in their
    environment, which a spawned child inherits before it imports
    anything; the parent's own JAX has read its platform already."""
    prev = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        for p in procs:
            p.start()
    finally:
        if prev is None:
            del os.environ["JAX_PLATFORMS"]
        else:
            os.environ["JAX_PLATFORMS"] = prev


class ParallelBfsChecker(HostEngineBase):
    """Multiprocessing ownership-sharded BFS over any picklable Model."""

    _supports_threads = True

    def __init__(self, builder: CheckerBuilder):
        super().__init__(builder)
        if self._visitor is not None:
            raise ValueError(
                "the parallel host engine does not support visitors"
            )
        # Reference parity: BFS ignores options.symmetry (bfs.rs never
        # reads it); DFS is the symmetry engine.
        self._n = max(2, self._thread_count)
        self._discovery_fps: Dict[str, int] = {}
        self._unique = 0
        self._tables: List[Dict[int, int]] = []
        self._metrics.set_gauge("workers", self._n)
        self._start()

    def _run(self) -> None:
        import cloudpickle

        model = self._model
        # cloudpickle (not plain pickle) ships the model: actor models are
        # typically assembled from closures/lambdas, which pickle rejects.
        model_blob = cloudpickle.dumps(model)
        n = self._n
        # spawn, not fork: the parent typically holds a live JAX runtime
        # (the device, threads) that must not be duplicated into the
        # workers. A worker imports this package, and so JAX: it is pinned
        # to the CPU, since the chip belongs to the parent alone.
        ctx = mp.get_context("spawn")
        in_qs = [ctx.Queue() for _ in range(n)]
        ctl_qs = [ctx.Queue() for _ in range(n)]
        res_q = ctx.Queue()
        procs = [
            ctx.Process(
                target=_worker,
                args=(
                    w,
                    n,
                    model_blob,
                    self._target_max_depth,
                    self._coverage.enabled,
                    in_qs[w],
                    in_qs,
                    ctl_qs[w],
                    res_q,
                ),
                daemon=True,
            )
            for w in range(n)
        ]
        _start_on_cpu(procs)

        # Seed: route init states to their owners.
        seeds: List[List] = [[] for _ in range(n)]
        for state in model.init_states():
            if not model.within_boundary(state):
                continue
            fp = model.fingerprint_state(state)
            seeds[fp % n].append((state, fp, 0, self._init_ebits, 1))
        n_seeded = sum(len(s) for s in seeds)
        for w in range(n):
            if seeds[w]:
                in_qs[w].put(seeds[w])

        stats = {
            w: dict(sc=0, uniq=0, maxd=0, sent=0, recv=0, idle=False, disc={})
            for w in range(n)
        }

        def ingest(msg):
            _, wid, _epoch, sc, uniq, maxd, sent, recv, idle, disc, cov = msg
            stats[wid] = dict(
                sc=sc, uniq=uniq, maxd=maxd, sent=sent, recv=recv,
                idle=idle, disc=disc,
            )
            for name, fp in disc.items():
                self._discovery_fps.setdefault(name, fp)
            if cov:
                # Workers attach their coverage tallies exactly once, on
                # the final report; merge is therefore add-exact.
                self._coverage.merge_counts(**cov)

        # Termination: coordinator-driven polling epochs. Each epoch
        # broadcasts a poll; every worker replies with counts sampled at
        # reply time (after draining its inbox). The run is quiescent when
        # TWO consecutive epochs each show all workers idle with global
        # sent == received (+ seeds) AND identical totals across the two
        # epochs — a message in flight at the first epoch either still
        # shows sent > received at the second, or its delivery changes the
        # totals; either way the pair is rejected. (Unsolicited progress
        # reports feed counters/discoveries only, never this decision:
        # stale-snapshot pairs can momentarily balance — observed as a
        # premature stop in round-5 verification.)
        prev_quiet_totals = None
        epoch = 0
        try:
            while True:
                epoch += 1
                replies = {}
                with self._metrics.phase("poll"):
                    for w in range(n):
                        ctl_qs[w].put(("poll", epoch))
                    deadline = time.monotonic() + 5.0
                    while len(replies) < n and time.monotonic() < deadline:
                        try:
                            msg = res_q.get(timeout=0.05)
                        except queue_mod.Empty:
                            continue
                        if msg[0] in ("progress", "final"):
                            ingest(msg)
                        elif msg[0] == "poll_reply":
                            ingest(msg)
                            if msg[2] == epoch:
                                replies[msg[1]] = msg

                self._state_count = sum(s["sc"] for s in stats.values())
                self._unique = sum(s["uniq"] for s in stats.values())
                self._max_depth = max(
                    [s["maxd"] for s in stats.values()] + [self._max_depth]
                )
                self._metrics.inc("rounds")
                self._obs_event(
                    "round",
                    frontier=sum(0 if s["idle"] else 1 for s in stats.values()),
                    workers=n,
                    epoch=epoch,
                )

                if self._finish_matched(self._discovery_fps):
                    break
                if (
                    self._target_state_count is not None
                    and self._state_count >= self._target_state_count
                ):
                    break
                if self._timed_out():
                    break

                if len(replies) == n:
                    all_idle = all(r[8] for r in replies.values())
                    g_sent = sum(r[6] for r in replies.values()) + n_seeded
                    g_recv = sum(r[7] for r in replies.values())
                    totals = (g_sent, g_recv)
                    if all_idle and g_sent == g_recv:
                        if prev_quiet_totals == totals:
                            break
                        prev_quiet_totals = totals
                    else:
                        prev_quiet_totals = None
                else:
                    prev_quiet_totals = None
        finally:
            for w in range(n):
                ctl_qs[w].put("stop")
            tables: Dict[int, Dict[int, int]] = {}
            # Shard tables cross the result queue as pickled dicts; the
            # collection deadline must scale with their size or large runs
            # time out, lose tables, and later raise "fingerprint missing
            # from shard table" during path reconstruction. Budget ~10µs
            # per visited entry (generous vs measured pickle+pipe cost) on
            # top of the old 30s floor.
            deadline = time.monotonic() + 30 + self._unique * 1e-5
            while len(tables) < n and time.monotonic() < deadline:
                try:
                    msg = res_q.get(timeout=1.0)
                except queue_mod.Empty:
                    continue
                if msg[0] == "table":
                    tables[msg[1]] = msg[2]
                else:
                    ingest(msg)
            self._tables = [tables.get(w, {}) for w in range(n)]
            if self._sampler is not None:
                # Workers are separate processes, so sampling happens at
                # the table merge: one vectorized bottom-k pass over each
                # shard's visited fingerprints (rows/depths resolve
                # lazily through _reconstruct at profile-build time).
                import numpy as np

                for table in self._tables:
                    if table:
                        self._sampler.offer_array(
                            np.fromiter(
                                table.keys(), dtype=np.uint64, count=len(table)
                            )
                        )
            self._state_count = sum(s["sc"] for s in stats.values())
            self._unique = sum(s["uniq"] for s in stats.values())
            self._max_depth = max(
                [s["maxd"] for s in stats.values()] + [self._max_depth]
            )
            for p in procs:
                p.join(timeout=5)
                if p.is_alive():
                    p.terminate()

    # -- accessors ----------------------------------------------------------

    def unique_state_count(self) -> int:
        return self._unique

    def discoveries(self) -> Dict[str, Path]:
        self.join()
        return {
            name: self._reconstruct(fp)
            for name, fp in list(self._discovery_fps.items())
        }

    def _sample_resolver(self):
        return self._path_sample_resolver(self._reconstruct)

    def _reconstruct(self, fp: int) -> Path:
        """Walk parent pointers across the shard tables (owner = fp % N)."""
        chain = [fp]
        cur = fp
        for _ in range(10_000_000):
            parent = self._tables[cur % self._n].get(cur)
            if parent is None:
                raise RuntimeError(
                    f"fingerprint {cur} missing from shard table during "
                    "path reconstruction"
                )
            if parent == 0:
                break
            cur = parent
            chain.append(cur)
        chain.reverse()
        return Path.from_fingerprints(self._model, chain)
