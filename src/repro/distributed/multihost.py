"""Multi-host engine bring-up: `jax.distributed` initialization, the
global serve mesh, and the process-sharded SPMD fallback.

One host stops at its own devices; ROADMAP open item 1 is the tier above
— N processes (one per host, or N local processes in CI) serving as one
fleet.  This module owns the bring-up:

  * `initialize()` wraps `jax.distributed.initialize` (coordinator
    address, process count, process id — the same triple a k8s
    StatefulSet derives from its pod ordinal) and returns a
    `MultihostContext`.  After it, `jax.devices()` is the *global* device
    view and the coordination service (barriers + key-value store) is
    live — `barrier()`, `kv_set()`, `kv_get()` below are thin wrappers
    the launch harness (tools/launchgate.py) and the multi-process tests
    use for readiness fan-in and result fan-out.

  * **Global-mesh mode** (`mode_of() == "global"`): build the
    (data, model) mesh over every global device with
    `global_serve_mesh()` and hand it to an engine exactly like a local
    mesh — params shard by the existing FSDP/TP rules and the slot batch
    by the serve rules (`repro.distributed.sharding.param_shardings` /
    `serve_state_shardings` / `cache_shardings`; the engines consume
    them via `mesh=`, unchanged).  This is the real multi-host path on
    TPU/GPU backends.

  * **Process-sharded SPMD mode** (`mode_of() == "spmd"`): the CPU
    backend cannot run multi-process XLA computations (probed:
    `Multiprocess computations aren't implemented on the CPU backend`),
    so CI runs the fleet as N coordinated processes each serving a
    deterministic *request shard* (`shard_requests`) on a local engine.
    The serving stack's core invariant — every result is a pure function
    of (seed, sampler config), slots are independent batch rows — makes
    the union of the per-process results **bitwise identical** to one
    engine serving the whole list (tests/test_multihost.py proves it in
    CI with 2 real `jax.distributed`-initialized processes).  The same
    invariant is exactly why the router tier (serve/router.py) can split
    a trace across replicas bitwise-safely.

Mode selection is a capability gate, not a flag: `mode_of()` returns
"global" only when the backend supports cross-process computations, so
the same launch code runs CI (CPU, spmd) and a real cluster (TPU/GPU,
global) without edits.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Sequence

import jax


@dataclasses.dataclass(frozen=True)
class MultihostContext:
    """The identity of this process in the fleet, post-initialize."""
    process_id: int
    num_processes: int
    coordinator_address: Optional[str] = None

    @property
    def is_coordinator(self) -> bool:
        return self.process_id == 0


def initialize(coordinator_address: Optional[str] = None,
               num_processes: int = 1,
               process_id: int = 0) -> MultihostContext:
    """Join the fleet.  A single-process call is a no-op (local jax is
    already initialized); a multi-process call must happen before any
    device use in the process, mirrors `jax.distributed.initialize`, and
    blocks until all `num_processes` processes connect — the launch
    harness's readiness wait rides on exactly that barrier."""
    if num_processes < 1:
        raise ValueError(f"num_processes must be >= 1, got {num_processes}")
    if not (0 <= process_id < num_processes):
        raise ValueError(f"process_id {process_id} outside "
                         f"[0, {num_processes})")
    if num_processes > 1:
        if coordinator_address is None:
            raise ValueError("multi-process initialize needs a "
                             "coordinator_address (host:port)")
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id)
    return MultihostContext(process_id=process_id,
                            num_processes=num_processes,
                            coordinator_address=coordinator_address)


def multiprocess_jit_supported() -> bool:
    """Whether this backend can run one XLA computation across processes.
    CPU cannot (no cross-process collectives runtime); TPU/GPU can."""
    return jax.default_backend() not in ("cpu",)


def mode_of(ctx: MultihostContext) -> str:
    """'global' (one engine on the global mesh) when the backend supports
    cross-process computations or the fleet is one process; 'spmd'
    (process-sharded requests on local engines) otherwise."""
    if ctx.num_processes == 1 or multiprocess_jit_supported():
        return "global"
    return "spmd"


def global_serve_mesh(data: Optional[int] = None, model: int = 1):
    """The serving (data, model) mesh over every *global* device.  After
    `initialize`, `jax.devices()` spans the fleet, so this is the
    multi-host analogue of `repro.launch.mesh.make_local_mesh` — the
    engines consume it via `mesh=` and the existing sharding rules
    (param FSDP/TP, serve-state and cache data-sharding) apply unchanged.
    """
    n = jax.device_count()
    if data is None:
        if n % model:
            raise ValueError(f"{n} devices not divisible by model={model}")
        data = n // model
    if data * model > n:
        raise ValueError(f"mesh data={data} x model={model} needs "
                         f"{data * model} devices, {n} present globally")
    from ..launch.mesh import make_mesh
    return make_mesh((data, model), ("data", "model"))


def shard_requests(requests: Sequence[Any], num_processes: int,
                   process_id: int) -> List[Any]:
    """This process's deterministic request shard: positions
    `process_id::num_processes` of the (stable-ordered) request list.
    Round-robin, so heterogeneous traffic (mixed NFE budgets, families)
    spreads instead of clumping onto one process.  Union-of-shards is
    bitwise equal to the unsharded serve: results are pure functions of
    (seed, config), never of neighbours or placement."""
    if not (0 <= process_id < num_processes):
        raise ValueError(f"process_id {process_id} outside "
                         f"[0, {num_processes})")
    return list(requests[process_id::num_processes])


# ---------------------------------------------------------------------------
# coordination-service helpers (readiness fan-in, small result fan-out)
# ---------------------------------------------------------------------------
def _client():
    from jax._src import distributed
    client = distributed.global_state.client
    if client is None:
        raise RuntimeError("coordination service not initialized — call "
                           "multihost.initialize(...) with num_processes>1 "
                           "first")
    return client


def barrier(name: str, timeout_s: float = 60.0) -> None:
    """Block until every process reaches `name` (readiness fan-in: the
    launch harness knows the fleet is serving when the 'ready' barrier
    clears on process 0)."""
    _client().wait_at_barrier(name, timeout_in_ms=int(timeout_s * 1000))


def kv_set(key: str, value: str) -> None:
    """Publish a small string (counter JSON, result digest) to the
    fleet-wide key-value store."""
    _client().key_value_set(key, value)


def kv_get(key: str, timeout_s: float = 60.0) -> str:
    """Blocking fetch from the fleet-wide key-value store."""
    return _client().blocking_key_value_get(
        key, timeout_in_ms=int(timeout_s * 1000))
