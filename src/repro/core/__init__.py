"""The paper's primary contribution: multiscale gossip for decentralized
averaging (Tsianos & Rabbat, 2010), plus the baselines it is evaluated
against and the failure models of §VI-C.

The production mapping of this algorithm onto TPU meshes (gradient
synchronization) lives in `repro.dist`; the MXU-friendly batched cell
mixing kernel lives in `repro.kernels.cell_mixing`.
"""
from .baselines import (
    BaselineResult,
    geographic_gossip,
    path_averaging,
    standard_gossip,
)
from .engine import EngineResult, execute_plan
from .events import event_totals
from .failures import handshake_cost
from .gossip import (
    GossipResult,
    batched_graphs,
    gossip_core,
    gossip_until,
)
from .medium import (
    CostModel,
    FailureModel,
    MediumCost,
    expected_retransmissions,
    level_edge_messages,
    price_edge_messages,
    price_messages,
    route_edge_transmissions,
)
from .metrics import relative_error, theorem2_bound
from .multiscale import (
    LevelReport,
    MultiscaleResult,
    MultiscaleTrials,
    multiscale_gossip,
)
from .options import ExecOptions
from .partition import Partition, auto_levels, build_partition
from .plan import HierarchyPlan, LevelPlan, build_plan
from .plan_cache import (
    PLAN_CACHE_VERSION,
    load_plan,
    plan_key,
    setup_plan,
    store_plan,
)
from .rgg import (
    RGG_METHODS,
    Graph,
    connectivity_radius,
    grid_graph,
    random_geometric_graph,
)
from .schedule import (
    CsrGraphs,
    ExchangeSchedule,
    dense_to_csr,
    flat_usage_to_dense,
    sample_schedule,
    sample_tick,
)
from .routing import (
    BatchedRoutes,
    Route,
    accumulate_route_sends,
    batched_greedy_routes,
    batched_routes_to_nodes,
    greedy_route,
    route_table,
    route_to_node,
)
from .scenarios import (
    Scenario,
    ScenarioResult,
    run_scenario_matrix,
    scenario_matrix,
)
from .synchronous import SyncMultiscaleResult, synchronous_multiscale

__all__ = [
    "BaselineResult",
    "BatchedRoutes",
    "CostModel",
    "CsrGraphs",
    "EngineResult",
    "ExecOptions",
    "FailureModel",
    "Graph",
    "GossipResult",
    "HierarchyPlan",
    "LevelPlan",
    "LevelReport",
    "MediumCost",
    "MultiscaleResult",
    "MultiscaleTrials",
    "Partition",
    "Route",
    "Scenario",
    "ScenarioResult",
    "accumulate_route_sends",
    "auto_levels",
    "batched_graphs",
    "batched_greedy_routes",
    "batched_routes_to_nodes",
    "build_partition",
    "build_plan",
    "connectivity_radius",
    "dense_to_csr",
    "event_totals",
    "execute_plan",
    "expected_retransmissions",
    "flat_usage_to_dense",
    "geographic_gossip",
    "gossip_core",
    "gossip_until",
    "greedy_route",
    "grid_graph",
    "handshake_cost",
    "load_plan",
    "multiscale_gossip",
    "path_averaging",
    "plan_key",
    "PLAN_CACHE_VERSION",
    "level_edge_messages",
    "price_edge_messages",
    "price_messages",
    "route_edge_transmissions",
    "random_geometric_graph",
    "relative_error",
    "RGG_METHODS",
    "route_table",
    "route_to_node",
    "run_scenario_matrix",
    "scenario_matrix",
    "setup_plan",
    "store_plan",
    "standard_gossip",
    "SyncMultiscaleResult",
    "synchronous_multiscale",
    "theorem2_bound",
]
