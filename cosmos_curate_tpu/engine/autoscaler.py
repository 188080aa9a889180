"""Throughput-based autoscaler.

Equivalent capability of xenna's autoscaler (reference
docs/curator/reference/ARCHITECTURE.md:83-93): measure per-worker throughput
per stage, then solve for the worker allocation that maximizes *balanced*
pipeline throughput under the CPU/TPU budget.

Solver: water-filling. The pipeline rate is min over stages of
(workers_i x rate_i); repeatedly grant a worker to the stage with the lowest
projected stage rate until the budget is exhausted. Stages without
throughput samples yet get their minimum and first claim on resources.

Backpressure signals: the observed input-queue depth *biases* the fill —
between stages with similar projected rates, the one with the deeper backlog
wins — and a drained stage (empty queue, known rate) stops receiving extra
workers beyond its minimum, so budget flows to starved stages after a
throughput shift (reference ARCHITECTURE.md:83-93 solves the same balanced-
throughput-under-backpressure problem).

Cross-host: ``plan_node_allocation`` lifts the same water-fill to **per-node
budgets** (one ``NodeBudget`` per connected agent plus the driver). The
per-stage totals come from the flat solver over the aggregate budget — so a
single-node plan is bit-identical to ``plan_allocation`` — and a placement
pass then pins device stages to TPU-bearing nodes, honors explicit
``Stage.node_affinity`` hints, and fans CPU workers across nodes weighted by
each node's measured per-worker throughput for that stage, with a
co-location bias toward the previous stage's node so inter-stage bytes stay
on-node (the T5X data/model-axis split: data-parallel CPU pools scale out
across hosts, the model mesh stays whole on its host).
"""

from __future__ import annotations

from dataclasses import dataclass, field

from cosmos_curate_tpu.core.stage import StageSpec


@dataclass
class StageScaleState:
    spec: StageSpec
    current_workers: int
    throughput_per_worker: float | None  # batches/s; None = unknown yet
    queued: int
    # node_id -> measured per-worker batches/s ON that node. Empty when the
    # run is single-node or no per-node samples landed yet; the per-node
    # placement pass biases CPU fan-out toward faster nodes with it.
    node_rates: dict[str, float] = field(default_factory=dict)


@dataclass(frozen=True)
class Budget:
    cpus: float
    tpus: float


@dataclass(frozen=True)
class NodeBudget:
    """One schedulable host: the driver (``node_id=""``, matching the
    runner's worker-node convention) or a connected agent from
    ``engine/remote_agent.py``."""

    node_id: str
    cpus: float
    tpu_chips: int = 0
    memory_gb: float = 0.0


@dataclass
class NodeAllocation:
    """``plan_node_allocation`` output.

    ``targets[i]`` is stage i's total worker count (identical to
    ``plan_allocation`` over the aggregate budget); ``per_node[i]`` splits
    it across nodes; ``preferred_node[i]`` is the node holding the
    plurality of stage i's workers — the router's affinity key (stage k's
    outputs should land where stage k+1's workers live)."""

    targets: list[int]
    per_node: list[dict[str, int]]
    preferred_node: list[str]


def discover_tpu_chips(cfg, stage_specs: list[StageSpec]) -> int:
    """Local TPU chip count for the budget, shared by the streaming and
    pipelined runners. Only touches JAX when some stage actually requests
    TPU resources — pure-CPU pipelines never pay the import, and never
    claim the chip. An explicit ``PipelineConfig.num_tpu_chips`` wins
    outright."""
    if cfg.num_tpu_chips is not None:
        return cfg.num_tpu_chips
    if not any(s.stage.resources.uses_tpu for s in stage_specs):
        return 0
    from cosmos_curate_tpu.parallel.mesh import tpu_chip_count

    return tpu_chip_count()


def plan_allocation(stages: list[StageScaleState], budget: Budget) -> list[int]:
    """Target worker counts per stage (same order as input)."""
    n = len(stages)
    alloc = [0] * n
    cpu_left = budget.cpus
    tpu_left = budget.tpus

    def cost(i: int) -> tuple[float, float]:
        r = stages[i].spec.stage.resources
        tpus = r.tpus if not r.entire_tpu_host else budget.tpus
        cpus = r.cpus
        if cpus <= 0 and tpus <= 0:
            # A declared zero-cost stage (pure-IO) must still consume budget,
            # or the water-fill below never terminates (fits() forever true).
            cpus = 0.25
        return (cpus, tpus)

    def fits(i: int) -> bool:
        c, t = cost(i)
        return c <= cpu_left + 1e-9 and t <= tpu_left + 1e-9

    def grant(i: int) -> None:
        nonlocal cpu_left, tpu_left
        c, t = cost(i)
        alloc[i] += 1
        cpu_left -= c
        tpu_left -= t

    # 1. minimum viable allocation: every stage gets >= min_workers (>=1)
    #    even if that oversubscribes the host — a pipeline where some stage
    #    has zero workers can never finish. Only *additional* workers
    #    respect the budget.
    for i, st in enumerate(stages):
        want = max(1, st.spec.min_workers)
        if st.spec.num_workers is not None:
            want = st.spec.num_workers
        if st.spec.stage.resources.uses_tpu:
            want = 1  # one in-process worker per TPU stage (see engine/pool.py)
        grant(i)  # unconditional first worker
        for _ in range(want - 1):
            if fits(i):
                grant(i)

    # 2. water-fill the bottleneck with the remaining budget
    while True:
        best = None
        best_score = None
        for i, st in enumerate(stages):
            if st.spec.num_workers is not None:  # fixed-size pool
                continue
            cap = st.spec.max_workers
            if cap is not None and alloc[i] >= cap:
                continue
            if not fits(i):
                continue
            # TPU in-process pools don't scale by worker count
            if st.spec.stage.resources.uses_tpu and alloc[i] >= 1:
                continue
            rate = st.throughput_per_worker
            if rate is not None and st.queued == 0 and alloc[i] >= max(1, st.spec.min_workers):
                # Drained and measured: no backlog to spend extra workers
                # on; leave the budget for starved stages (scale-down
                # pressure — the runner stops the now-surplus idle workers).
                continue
            projected = (rate if rate is not None else 1.0) * alloc[i]
            # Queue bias: between similar projected rates, the deeper
            # backlog wins. Dimensionless damping keeps rate primary.
            score = projected / (1.0 + float(st.queued))
            if best_score is None or score < best_score:
                best_score = score
                best = i
        if best is None:
            break
        grant(best)
    return alloc


def plan_node_allocation(
    stages: list[StageScaleState], nodes: list[NodeBudget]
) -> NodeAllocation:
    """Per-node × per-stage worker allocation.

    Totals come from ``plan_allocation`` over the aggregate budget (so one
    node reproduces today's plan exactly); placement then assigns each
    worker to a node:

    - TPU stages go to TPU-bearing nodes only (in this engine that is the
      driver — chips belong to the engine process, pool.py invariant).
    - ``Stage.node_affinity`` pins a stage outright (``"driver"`` → the
      driver node).
    - CPU stages water-fill across nodes: each grant goes to the fitting
      node with the best (measured stage rate, co-location with the
      previous stage's preferred node, free CPUs) score — so a
      decode-heavy CPU node systematically feeds a TPU embed node instead
      of competing with it for driver cores.
    """
    if not nodes:
        nodes = [NodeBudget("", cpus=1.0)]
    budget = Budget(
        cpus=sum(n.cpus for n in nodes),
        tpus=float(sum(n.tpu_chips for n in nodes)),
    )
    targets = plan_allocation(stages, budget)
    cpu_left = {n.node_id: n.cpus for n in nodes}
    chips_left = {n.node_id: float(n.tpu_chips) for n in nodes}
    # memory budget participates in the CPU fit check only where BOTH the
    # node declares capacity and the stage declares demand (0 = unknown,
    # fit on CPUs alone — the pre-memory behavior)
    mem_left = {n.node_id: n.memory_gb for n in nodes}
    driver_id = nodes[0].node_id  # runner convention: nodes[0] is the driver
    per_node: list[dict[str, int]] = []
    preferred: list[str] = []
    prev_pref = driver_id
    for i, (st, want) in enumerate(zip(stages, targets)):
        res = st.spec.stage.resources
        affinity = getattr(st.spec.stage, "node_affinity", None)
        counts: dict[str, int] = {}
        for _ in range(want):
            if affinity == "driver":
                chosen = driver_id
            elif res.uses_tpu:
                # device stages pin to TPU-bearing nodes; with none visible
                # (CPU-fallback dev boxes) the driver hosts the in-process
                # worker exactly as the flat path does
                cands = [n.node_id for n in nodes if n.tpu_chips > 0] or [driver_id]
                chosen = max(cands, key=lambda nid: chips_left[nid])
                chips_left[chosen] -= (
                    res.tpus if not res.entire_tpu_host else chips_left[chosen]
                )
            else:
                ccost = res.cpus if res.cpus > 0 else 0.25
                chosen = _best_cpu_node(
                    st, nodes, cpu_left, ccost, prev_pref,
                    mem_left=mem_left, mem_cost=res.memory_gb,
                )
            counts[chosen] = counts.get(chosen, 0) + 1
            cpu_left[chosen] -= res.cpus if res.cpus > 0 else 0.25
            mem_left[chosen] -= res.memory_gb
        per_node.append(counts)
        # plurality node; deterministic tie-break by node order, so the
        # router's affinity key is stable across replans with equal splits
        order = {n.node_id: j for j, n in enumerate(nodes)}
        pref = (
            max(counts, key=lambda nid: (counts[nid], -order.get(nid, 0)))
            if counts
            else prev_pref
        )
        preferred.append(pref)
        prev_pref = pref
    return NodeAllocation(targets=targets, per_node=per_node, preferred_node=preferred)


def _best_cpu_node(
    st: StageScaleState,
    nodes: list[NodeBudget],
    cpu_left: dict[str, float],
    ccost: float,
    prev_pref: str,
    *,
    mem_left: dict[str, float] | None = None,
    mem_cost: float = 0.0,
) -> str:
    """One CPU-worker grant: fitting nodes first, then measured per-worker
    rate on that node (a node that decodes 2× faster per worker earns the
    worker), then co-location with the upstream stage's node (inter-stage
    bytes stay local), then free CPUs (balance). A node with no samples
    yet ranks at the MEAN measured rate — neutral exploration — so an
    unmeasured late joiner neither outranks every measured-but-slow node
    nor starves, and the co-location bias stays decisive between
    rate-equivalent nodes. Nothing fits → least oversubscribed node,
    mirroring the flat planner's unconditional min-viable grant."""
    measured = [r for r in st.node_rates.values() if r > 0]
    neutral = sum(measured) / len(measured) if measured else 1.0

    def key(n: NodeBudget):
        fits = cpu_left[n.node_id] + 1e-9 >= ccost
        if fits and mem_cost > 0 and n.memory_gb > 0 and mem_left is not None:
            fits = mem_left[n.node_id] + 1e-9 >= mem_cost
        rate = st.node_rates.get(n.node_id)
        return (
            fits,
            rate if rate is not None else neutral,
            1 if n.node_id == prev_pref else 0,
            cpu_left[n.node_id],
        )

    return max(nodes, key=key).node_id
