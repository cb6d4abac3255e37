"""Serving: KV-cache autoregressive decode with tp-sharded continuous
batching — the inference half of the sharded-mesh story.

- ``serve.cache``     — the KV cache pytrees: slot-major rings AND the
  paged block-table pool (+ its host PagePool allocator)
- ``serve.engine``    — the jitted (prefill, decode) pair on the tp mesh
- ``serve.prefix``    — host prefix-cache index (trie + refcounted LRU;
  paged entries own refcounted page lists — zero-copy sharing)
- ``serve.scheduler`` — continuous batching over the engine (paged mode
  admits by free pages, pooling capacity across slots); externally
  drivable tick by tick (begin/submit/tick/collect + pressure())
- ``serve.router``    — the multi-tenant front door: SLO-aware routing
  of classed traffic over N scheduler/engine replicas (prefix-affinity
  placement, priority shedding, per-class SLO accounting)
- ``serve.controller`` — the self-healing fleet controller: SLO/
  pressure-driven autoscaling (per-role on disaggregated fleets),
  drain-before-removal, replica-crash recovery and cross-replica
  request preemption on the router's deterministic global clock
- ``serve.disagg``    — disaggregated prefill/decode roles: phase-
  specialized replicas with the first-token page hand-off coordinator
- ``serve.speculate`` — speculative decoding drafts (n-gram / prompt
  lookup) verified bit-identically through free decode-batch lanes
- ``serve.engine_iface`` — the ServeEngine protocol: the narrow engine
  surface the control plane actually calls (ISSUE 18)
- ``serve.host``      — the engines' host half, written once: config
  check, block tables, reservations, prefix paging, bucket ladders
- ``serve.sim``       — the cost-model engine: that host half with no
  arrays under it, per-phase virtual time — the million-request
  digital twin's engine
- ``serve.scenarios`` — the named scenario library (seeded burst,
  diurnal, crash-storm, role-mix, longtail-prefix) shared by the
  pinned tests, the ``ddl_tpu sim`` CLI and the twin bench

Quickstart (also ``python -m ddl_tpu serve --help``)::

    from ddl_tpu.serve import InferenceEngine, Request, Scheduler, ServeConfig

    eng = InferenceEngine(ServeConfig(slots=4, capacity=256))
    eng.load_params("ckpt/ckpt.npz")   # any trained topology, params-only
    done, stats = Scheduler(eng).run([
        Request(id=0, prompt=prompt_ids, max_new_tokens=64),
    ])
"""

from .controller import (  # noqa: F401
    AutoscaleConfig,
    FleetController,
    RoleScale,
    parse_autoscale_spec,
)
from .disagg import (  # noqa: F401
    ROLES,
    DisaggCoordinator,
    parse_roles_spec,
    validate_roles,
)
from .engine import InferenceEngine, ServeConfig  # noqa: F401
from .engine_iface import ServeEngine, engine_kind  # noqa: F401
from .hybrid_engine import HybridEngine, engine_cls  # noqa: F401
from .prefix import PrefixIndex  # noqa: F401
from .scenarios import (  # noqa: F401
    SCENARIOS,
    Scenario,
    SeededRequest,
    get_scenario,
    parse_scenario,
)
from .sim import CostModel, CostModelEngine, sim_engine_factory  # noqa: F401
from .speculate import greedy_accept, propose_draft  # noqa: F401
from .router import (  # noqa: F401
    ClassSpec,
    Router,
    RouterConfig,
    RouterStats,
    parse_slo_spec,
    parse_traffic_spec,
)
from .scheduler import (  # noqa: F401
    Completion,
    PreemptedRequest,
    Pressure,
    Request,
    Scheduler,
    ServeStats,
    derive_request_slo,
    request_slo_samples,
)

__all__ = [
    "AutoscaleConfig",
    "ClassSpec",
    "Completion",
    "CostModel",
    "CostModelEngine",
    "DisaggCoordinator",
    "FleetController",
    "InferenceEngine",
    "PreemptedRequest",
    "PrefixIndex",
    "Pressure",
    "ROLES",
    "Request",
    "RoleScale",
    "Router",
    "RouterConfig",
    "RouterStats",
    "SCENARIOS",
    "Scenario",
    "Scheduler",
    "SeededRequest",
    "ServeConfig",
    "ServeEngine",
    "ServeStats",
    "derive_request_slo",
    "engine_kind",
    "get_scenario",
    "greedy_accept",
    "parse_autoscale_spec",
    "parse_roles_spec",
    "parse_scenario",
    "parse_slo_spec",
    "parse_traffic_spec",
    "propose_draft",
    "request_slo_samples",
    "sim_engine_factory",
    "validate_roles",
]
