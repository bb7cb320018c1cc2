"""Edge-computing runtime: center + edge servers (§4), discrete-event
latency simulator (§5 dynamic scenario), and the districts→devices
shard_map deployment."""
from .topology import LatencyModel, Topology
from .center import ComputingCenter
from .server import EdgeServer
from .router import EdgeSystem, EngineLayout, choose_layout
from .engine import BatchedQueryEngine, ShardedBatchedEngine
from .simulator import (BatchPolicy, MigrationEvent, QueryEvent, SimResult,
                        UpdateSchedule, VariableUpdateSchedule, make_trace,
                        migrations_from_plan, run_update_epochs,
                        simulate_centralized, simulate_edge)
from .traffic import (TRAFFIC_SHAPES, arrival_times, poisson_count,
                      rate_profile)
from .sharded_oracle import (ShardedOracleData, default_edge_mesh,
                             pack_for_mesh, pack_tables, prepare_queries,
                             make_sharded_query_fn, sharded_query)

__all__ = [n for n in dir() if not n.startswith("_")]
