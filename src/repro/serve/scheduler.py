"""DR-based request routing across serving replicas.

Serving-side instance of the paper's mapping: requests carry a *session
key* (user / document / host — the paper's §6 partitions crawl output by
web host); replicas are partitions; the per-session KV cache is operator
state.  Session keys are heavy-tailed (hot documents / hot tenants), so
UHP routing makes some replicas stragglers.  The scheduler runs the same
DRM loop: counter-sketch over observed session keys, KIPUPDATE at decision
points, and session (cache) migration costed against the expected balance
gain.

Replicas here are modeled objects (queue depths), keeping the scheduler
testable without spinning 16 engines; ``ServeEngine`` is the per-replica
execution unit.

``checkpoint`` is a thin control-plane driver: it feeds the window's
telemetry (queue depths, routed records) into ``DRMaster.evaluate`` and
executes whatever typed action the shared policy stack returns — replica
scale-out/in (``Resize``) or session re-routing (``Repartition``) — always
returning the same result schema.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.control import Repartition, Resize, SwitchBackend, Telemetry
from repro.core.drm import DRConfig, DRMaster
from repro.core.hashing import DEFAULT_NUM_HOSTS
from repro.core.partitioner import heavy_capacity_for, uniform_partitioner
from repro.exchange import ExchangeStats

__all__ = ["ReplicaState", "DRScheduler"]


@dataclasses.dataclass
class ReplicaState:
    rid: int
    queued_tokens: float = 0.0      # outstanding work
    sessions: set = dataclasses.field(default_factory=set)


class DRScheduler:
    def __init__(self, num_replicas: int, *, dr: DRConfig | None = None, seed: int = 0,
                 migration_token_cost: float = 64.0,
                 exchange_backend: str | None = None,
                 topology=None):
        self.replicas = [ReplicaState(i) for i in range(num_replicas)]
        cfg = dr or DRConfig(lam=4.0, imbalance_trigger=1.25)
        # the same tile-padded sizing rule the kernels' heavy tables use —
        # a bespoke rounding here once drifted from the kernel tile shape
        heavy_cap = heavy_capacity_for(cfg.lam, num_replicas)
        init = uniform_partitioner(num_replicas, DEFAULT_NUM_HOSTS, seed,
                                   heavy_capacity=heavy_cap)
        # the transport KV-cache migrations would ride; its sizing rule
        # prices session-move plans inside the policy stack.  ``topology``
        # (an ExchangeTopology over the replica set) makes that pricing
        # locality-aware: moving a session's KV cache between replicas on
        # one host is cheaper than shipping it across hosts.
        self.drm = DRMaster(init, cfg, consumer="serve",
                            exchange_backend=exchange_backend or "dense",
                            exchange_topology=topology)
        self.telemetry = Telemetry("serve")
        self.migration_token_cost = migration_token_cost
        self.migrations = 0
        self.routed = 0

    # -- hot path ---------------------------------------------------------
    def route(self, session_key: int, cost_tokens: float) -> int:
        """Assign a request to a replica; account its load."""
        r = int(self.drm.partitioner.lookup_np(np.asarray([session_key], np.int32))[0])
        rep = self.replicas[r]
        rep.queued_tokens += cost_tokens
        rep.sessions.add(session_key)
        self.routed += 1
        return r

    def drain(self, tokens_per_replica: float) -> None:
        """Simulate service: each replica completes up to N tokens."""
        for rep in self.replicas:
            rep.queued_tokens = max(0.0, rep.queued_tokens - tokens_per_replica)

    # -- safe point: feed signals, execute the stack's action --------------
    def checkpoint(self, window_keys: np.ndarray) -> dict:
        """One decision point: telemetry in, typed action out, executed.

        Always returns the same schema — ``repartitioned``, ``resized``,
        ``num_replicas``, ``imbalance``, ``moved_sessions``, ``reason``,
        ``backend`` — whatever the decision was (including declines, whose
        reason comes from the decision log's record).
        """
        window_keys = np.asarray(window_keys, np.int64)
        keys, counts = np.unique(window_keys, return_counts=True)
        self.drm.observe(keys.reshape(1, -1), counts.reshape(1, -1))
        loads = np.array([r.queued_tokens for r in self.replicas])
        self.telemetry.record_batch(float(len(window_keys)))
        self.telemetry.record_queues(loads)
        # replicas are *elastic* partitions, not a fixed physical worker set:
        # num_workers=1 keeps the resize floor at min_partitions (scale-in
        # must stay reachable) and session moves costed replica-to-replica
        signals = self.telemetry.snapshot(loads=loads + 1e-9, num_workers=1)
        action = self.drm.evaluate(signals)
        moved_sessions = 0
        if isinstance(action, Resize):
            # elastic scale-out/in — a resize is this decision point's action
            moved_sessions = self.resize(action.target)
        elif isinstance(action, Repartition):
            # migrate each moved session's KV cache
            moved_sessions = self._reroute_sessions(self.drm.partitioner)
            self.migrations += moved_sessions
        elif isinstance(action, SwitchBackend):
            # the DRM installed the new transport in evaluate
            # (note_backend_switch); session-move pricing follows it from the
            # next decision on — nothing to rebuild here, replicas are
            # modeled objects, not jitted steps.  NOTE: session moves are
            # modeled (not bufferized), so the occupancy below is exact
            # rows with no padding — the BackendPolicy sees fraction 1.0
            # and holds dense; real lane accounting would need bufferized
            # KV migration (ROADMAP open item).
            pass
        if moved_sessions:
            # session (KV-cache) moves are this consumer's exchange traffic;
            # modeled 1 row per session, unpadded.  The move wall counts as
            # hidden behind decision work (the streaming driver's
            # attribution).
            self.telemetry.record_exchange(ExchangeStats(
                rows=moved_sessions,
                padded_rows=moved_sessions,
                occupied_rows=moved_sessions,
                backend=self.drm.exchange_backend.name,
                count_wall_s=0.0,
            ))
        return {
            # a backend switch moves no sessions: taken, but not a repartition
            "repartitioned": action.taken and action.moves_state,
            "resized": isinstance(action, Resize),
            "num_replicas": len(self.replicas),
            "imbalance": float(signals.imbalance),
            "moved_sessions": moved_sessions,
            "reason": action.reason,
            "backend": self.drm.exchange_backend.name,
        }

    def imbalance(self) -> float:
        loads = np.array([r.queued_tokens for r in self.replicas])
        return float(loads.max() / max(loads.mean(), 1e-9))

    # -- elastic scale-out / scale-in -------------------------------------
    def resize(self, num_replicas: int) -> int:
        """Grow or shrink the replica set — the streaming resize one level up.

        The session keyspace is re-planned cross-size with the DRM's sketch
        (``DRMaster.replan_resize``); sessions whose replica changed migrate
        their KV cache (costed like a repartition migration).  Returns the
        number of migrated sessions.  With ``DRConfig(elastic=True)``,
        ``checkpoint`` calls this automatically on sustained queue imbalance.
        """
        n = int(num_replicas)
        if n < 1:
            raise ValueError(f"need at least one replica, got {n}")
        if n == len(self.replicas):
            return 0
        new = self.drm.replan_resize(n)
        if n > len(self.replicas):
            self.replicas += [ReplicaState(i) for i in range(len(self.replicas), n)]
        moved = self._reroute_sessions(new)
        if n < len(self.replicas):
            # scale-in: dying replicas already handed off their sessions;
            # their residual queued work drains onto the folded replica
            for rep in self.replicas[n:]:
                self.replicas[rep.rid % n].queued_tokens += rep.queued_tokens
            self.replicas = self.replicas[:n]
        self.migrations += moved
        return moved

    def _reroute_sessions(self, new) -> int:
        """Move sessions (and their KV-cache cost) to where ``new`` maps them.

        A dying replica (``rid >= new.num_partitions``) can never equal its
        sessions' new destination, so scale-in drains it completely.
        """
        moved = 0
        for rep in self.replicas:
            stay = set()
            for s in rep.sessions:
                dst = int(new.lookup_np(np.asarray([s], np.int32))[0])
                if dst != rep.rid:
                    self.replicas[dst].sessions.add(s)
                    self.replicas[dst].queued_tokens += self.migration_token_cost
                    moved += 1
                else:
                    stay.add(s)
            rep.sessions = stay
        return moved
