"""Distribution layer: sharding rules (divisibility fallback), cache specs,
and multi-device behaviours (pipeline, FSDP) via subprocesses with forced
host device counts — the main test process keeps the real 1-device view."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.distributed.sharding import (ShardCfg, bank_shardings, param_spec,
                                        batch_spec, kv_cache_spec)

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
CFG = ShardCfg()


class TestParamRules:
    def test_column_parallel_qkv(self):
        s = param_spec("layer_stacks/0/attn/wq", (4, 7168, 7168), MESH, CFG)
        assert s[2] == "model"          # TP on output dim
        assert s[0] is None             # stack axis never sharded

    def test_row_parallel_wo(self):
        s = param_spec("layer_stacks/0/attn/wo", (4, 7168, 7168), MESH, CFG)
        assert s[1] == "model"

    def test_divisibility_fallback(self):
        # a TP dim that does not divide the 16-way model axis falls back to
        # replication on that axis (FSDP may still claim another dim)
        s = param_spec("layer_stacks/0/attn/wq", (2, 896, 904), MESH, CFG)
        assert "model" not in [a for a in s if isinstance(a, str)]

    def test_vocab_tp_and_fallback(self):
        ok = param_spec("embed", (32000, 4096), MESH, CFG)
        assert ok[0] == "model"
        bad = param_spec("embed", (51865, 512), MESH, CFG)   # whisper vocab
        assert bad[0] != "model"

    def test_moe_expert_parallel(self):
        s = param_spec("layer_stacks/0/moe/w_gate", (3, 128, 2048, 768), MESH, CFG)
        assert s[1] == "model"          # expert axis

    def test_norms_replicated(self):
        s = param_spec("layer_stacks/0/ln_attn", (4, 4096), MESH, CFG)
        assert all(a is None for a in s)

    def test_fsdp_on_largest_free_dim(self):
        s = param_spec("layer_stacks/0/mlp/w_up", (4, 1024, 4096), MESH, CFG)
        assert s[2] == "model" and s[1] == "data"

    def test_multipod_params_not_sharded_over_pod(self):
        s = param_spec("layer_stacks/0/mlp/w_up", (4, 1024, 4096), MESH3, CFG)
        assert "pod" not in [a for a in s if isinstance(a, str)]


class TestActivationRules:
    def test_batch_spec_single_pod(self):
        s = batch_spec(MESH, CFG, 2, 256)
        assert s[0] == "data"

    def test_batch_spec_multi_pod(self):
        s = batch_spec(MESH3, CFG, 2, 256)
        assert s[0] == ("pod", "data")

    def test_batch_one_unsharded(self):
        s = batch_spec(MESH, CFG, 2, 1)
        assert s[0] is None

    def test_bank_shardings_replicate_with_optional_diag_split(self):
        """FactoredBank placement: every factor/index leaf replicates; the
        (P, D) diag pool — the only D-scaled leaf — replicates by default
        and D-shards over the tp axis only on opt-in when divisible."""
        from repro.core import CoeffCache, SamplerConfig
        from repro.sde import VPSDE
        cache = CoeffCache(VPSDE(), data_shape=(8, 8, 3))   # D=192
        cache.index_of(SamplerConfig(nfe=4))
        bank = cache.factored_bank
        sh = bank_shardings(MESH, CFG, bank)
        assert all(getattr(sh, f).spec == P() for f in bank._fields)
        sh = bank_shardings(MESH, CFG, bank, shard_diag=True)
        assert sh.diag.spec == P(None, "model")             # 192 % 16 == 0
        assert sh.psi_blk.spec == P()
        # indivisible D falls back to replication
        odd = bank._replace(diag=jnp.zeros((1, 7), jnp.float32))
        sh = bank_shardings(MESH, CFG, odd, shard_diag=True)
        assert sh.diag.spec == P()

    def test_kv_cache_heads_or_seq(self):
        # enough heads: shard heads over model
        s = kv_cache_spec(MESH, CFG, (4, 128, 32768, 16, 128), 128, 16)
        assert s[3] == "model"
        # MQA baseline: replicate over model (no seq sharding by default)
        s = kv_cache_spec(MESH, CFG, (4, 1, 524288, 1, 256), 1, 1)
        assert s[2] is None and s[3] is None
        # opt-in SP cache for the shard_map flash-decode path
        s = kv_cache_spec(MESH, CFG, (4, 1, 524288, 1, 256), 1, 1,
                          seq_fallback=True)
        assert s[2] == "model"


# ---------------------------------------------------------------------------
# multi-device behaviours in subprocesses
# ---------------------------------------------------------------------------
def run_with_devices(n, code):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


def test_pipeline_matches_sequential():
    out = run_with_devices(4, """
        import numpy as np, jax, jax.numpy as jnp
        from repro.distributed.pipeline import pipeline_apply, make_stage_mesh
        mesh = make_stage_mesh(4)
        def stage_fn(w, x):
            return jnp.tanh(x @ w)
        ws = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 16)) * 0.5
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 4, 16))  # 8 micro x 4
        out = pipeline_apply(stage_fn, ws, x, mesh=mesh)
        ref = x
        for i in range(4):
            ref = jnp.tanh(ref @ ws[i])
        err = float(jnp.abs(out - ref).max())
        assert err < 1e-5, err
        print("PIPELINE_OK", err)
    """)
    assert "PIPELINE_OK" in out


def test_fsdp_train_step_multi_device():
    """2x2 mesh: sharded params + batch, one train step runs and agrees with
    the single-device result."""
    out = run_with_devices(4, """
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_arch
        from repro.models.registry import Arch
        from repro.launch.steps import make_train_step, shardings_for
        from repro.optim.adamw import AdamWCfg, adamw_init
        from repro.distributed.sharding import ShardCfg, param_shardings, batch_spec
        from repro.launch.mesh import make_mesh

        spec = get_arch("gemma3-1b", reduced=True)
        arch = Arch(spec)
        key = jax.random.PRNGKey(0)
        params = arch.init(key)
        opt_cfg = AdamWCfg(warmup_steps=1, total_steps=4)
        batch = {"tokens": jax.random.randint(key, (4, 32), 0, 100),
                 "labels": jax.random.randint(key, (4, 32), 0, 100)}
        step = make_train_step(arch, opt_cfg)

        # single-device reference
        opt0 = adamw_init(params, opt_cfg)
        p_ref, _, m_ref = jax.jit(step)(params, opt0, batch)

        mesh = make_mesh((2, 2), ("data", "model"))
        cfg = ShardCfg()
        psh = param_shardings(params, mesh, cfg)
        params_d = jax.device_put(params, psh)
        opt_d = adamw_init(params_d, opt_cfg)
        bsh = {k: NamedSharding(mesh, batch_spec(mesh, cfg, v.ndim, 4))
               for k, v in batch.items()}
        batch_d = jax.device_put(batch, bsh)
        with mesh:
            p_new, o_new, m = jax.jit(step)(params_d, opt_d, batch_d)
        l1, l2 = float(m_ref["loss"]), float(m["loss"])
        assert abs(l1 - l2) < 1e-4, (l1, l2)
        d = max(float(jnp.abs(a - b).max()) for a, b in
                zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_new)))
        assert d < 1e-4, d
        print("FSDP_OK", l1, l2, d)
    """)
    assert "FSDP_OK" in out


def test_elastic_remesh_restart():
    """The same checkpoint restores under a different device count/mesh —
    elastic re-meshing (DESIGN.md §4)."""
    out = run_with_devices(8, """
        import numpy as np, jax, jax.numpy as jnp, tempfile, os
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_arch
        from repro.models.registry import Arch
        from repro.distributed.sharding import ShardCfg, param_shardings
        from repro.ckpt.store import CheckpointStore
        from repro.launch.mesh import make_mesh

        spec = get_arch("gemma3-1b", reduced=True)
        arch = Arch(spec)
        params = arch.init(jax.random.PRNGKey(0))
        d = tempfile.mkdtemp()
        store = CheckpointStore(d)
        mesh1 = make_mesh((4, 2), ("data", "model"))
        p1 = jax.device_put(params, param_shardings(params, mesh1, ShardCfg()))
        store.save(1, p1, blocking=True)
        # "restart" on a different mesh shape
        mesh2 = make_mesh((2, 4), ("data", "model"))
        like = jax.tree.map(
            lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
            params, param_shardings(params, mesh2, ShardCfg()))
        step, restored = store.restore_latest(like)
        assert step == 1
        err = max(float(jnp.abs(a - b).max()) for a, b in
                  zip(jax.tree.leaves(params), jax.tree.leaves(restored)))
        assert err == 0.0, err
        print("REMESH_OK")
    """)
    assert "REMESH_OK" in out
