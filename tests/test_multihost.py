"""Tier: multihost — the fleet bring-up (distributed/multihost.py).

Unit layer: context validation, mode selection (CPU backends cannot run
cross-process XLA computations, so the fleet falls back to process-
sharded SPMD), the global serve mesh's divisibility checks, and the
round-robin request sharding.

Integration layer (the real thing, in the style of test_serve_mesh.py's
spawned subprocesses): TWO processes joined through an actual
`jax.distributed.initialize` coordination service — barrier fan-in, KV
round-trip, and each process serving its request shard on a local
engine with the union of per-process results **bitwise identical** to
one engine serving the whole list.  That equality is the invariant the
router tier and the launchgate harness stand on.
"""
import os
import socket
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.distributed import multihost
from suite_support import LIMIT_S


# ---------------------------------------------------------------- unit

class TestContext:
    def test_single_process_is_noop(self):
        ctx = multihost.initialize()
        assert (ctx.process_id, ctx.num_processes) == (0, 1)
        assert ctx.is_coordinator

    def test_validation(self):
        with pytest.raises(ValueError, match="num_processes"):
            multihost.initialize(num_processes=0)
        with pytest.raises(ValueError, match="process_id"):
            multihost.initialize(coordinator_address="h:1", num_processes=2,
                                 process_id=2)
        with pytest.raises(ValueError, match="coordinator_address"):
            multihost.initialize(num_processes=2, process_id=0)

    def test_mode_on_this_backend(self):
        one = multihost.MultihostContext(0, 1)
        two = multihost.MultihostContext(0, 2, "h:1")
        assert multihost.mode_of(one) == "global"
        if jax.default_backend() == "cpu":
            assert not multihost.multiprocess_jit_supported()
            assert multihost.mode_of(two) == "spmd"
        else:
            assert multihost.mode_of(two) == "global"

    def test_coordination_requires_initialize(self):
        with pytest.raises(RuntimeError, match="initialize"):
            multihost.barrier("nope")


class TestGlobalServeMesh:
    def test_defaults_to_all_devices_on_data(self):
        mesh = multihost.global_serve_mesh()
        assert dict(mesh.shape) == {"data": jax.device_count(), "model": 1}

    def test_divisibility_checked(self):
        n = jax.device_count()
        with pytest.raises(ValueError):
            multihost.global_serve_mesh(model=n + 1)
        with pytest.raises(ValueError):
            multihost.global_serve_mesh(data=n + 1, model=1)


class TestShardRequests:
    def test_round_robin_partition(self):
        reqs = list(range(10))
        shards = [multihost.shard_requests(reqs, 3, p) for p in range(3)]
        assert shards[0] == [0, 3, 6, 9]
        assert shards[1] == [1, 4, 7]
        assert shards[2] == [2, 5, 8]
        assert sorted(sum(shards, [])) == reqs

    def test_validation(self):
        with pytest.raises(ValueError, match="process_id"):
            multihost.shard_requests([1], 2, 2)


# ---------------------------------------------------------- integration

_WORKER = """
    import hashlib, json, os, sys
    sys.path.insert(0, "src")
    import jax
    from repro.configs import get_diffusion
    from repro.distributed import multihost
    from repro.serve import DiffusionEngine, SampleRequest

    pid = int(sys.argv[1]); nproc = int(sys.argv[2]); coord = sys.argv[3]
    ctx = multihost.initialize(coordinator_address=coord,
                               num_processes=nproc, process_id=pid)
    assert multihost.mode_of(ctx) in ("global", "spmd")

    # KV round-trip: every process publishes, process 0 reads all back
    multihost.kv_set(f"mh-test/hello/{pid}", f"from-{pid}")
    multihost.barrier("mh-test-kv")
    if ctx.is_coordinator:
        got = [multihost.kv_get(f"mh-test/hello/{p}") for p in range(nproc)]
        assert got == [f"from-{p}" for p in range(nproc)], got
        print("KV-OK", flush=True)

    # SPMD serve: this process's request shard on a local engine
    requests = [SampleRequest(rid=i, seed=i, nfe=5) for i in range(6)]
    mine = multihost.shard_requests(requests, nproc, pid)
    spec = get_diffusion("cifar10-ddpm", reduced=True)
    params = spec.init(jax.random.PRNGKey(0))
    engine = DiffusionEngine(spec, params, batch_size=2, nfe=5)
    results = engine.serve(mine)
    digests = {r.rid: hashlib.sha256(results[r.rid].tobytes()).hexdigest()
               for r in mine}
    multihost.kv_set(f"mh-test/digests/{pid}", json.dumps(digests))
    multihost.barrier("mh-test-served")
    if ctx.is_coordinator:
        union = {}
        for p in range(nproc):
            union.update(json.loads(
                multihost.kv_get(f"mh-test/digests/{p}")))
        assert sorted(union) == [str(i) for i in range(6)], sorted(union)
        solo = DiffusionEngine(spec, spec.init(jax.random.PRNGKey(0)),
                               batch_size=2, nfe=5)
        want = solo.serve(requests)
        for i in range(6):
            w = hashlib.sha256(want[i].tobytes()).hexdigest()
            assert union[str(i)] == w, f"rid {i} diverged across the fleet"
        print("UNION-BITWISE-OK", flush=True)
    multihost.barrier("mh-test-done")
    print(f"DONE-{pid}", flush=True)
"""


@pytest.mark.slow
def test_two_process_fleet_kv_barrier_and_bitwise_union(tmp_path):
    """2 real processes through jax.distributed: coordination-service KV
    and barriers work, and the union of the per-process SPMD serves is
    bitwise equal to the single-host serve."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coord = f"127.0.0.1:{port}"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH="src")

    procs = [subprocess.Popen(
        [sys.executable, "-c", textwrap.dedent(_WORKER),
         str(p), "2", coord],
        cwd=repo, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True) for p in range(2)]
    outs = []
    try:
        for p, proc in enumerate(procs):
            out, _ = proc.communicate(timeout=LIMIT_S)
            outs.append(out)
            assert proc.returncode == 0, f"process {p}:\n{out}"
    finally:
        for proc in procs:
            proc.kill()
            proc.wait()
    assert "KV-OK" in outs[0]
    assert "UNION-BITWISE-OK" in outs[0]
    for p in range(2):
        assert f"DONE-{p}" in outs[p]
