"""Sequence-parallel flash-decode (shard_map) vs the replicated reference —
run on a forced 4-device host in a subprocess."""
import os
import subprocess
import sys
import textwrap

from suite_support import LIMIT_S


def run_with_devices(n, code):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       timeout=LIMIT_S)
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


def test_sp_decode_matches_ref_and_update_is_local():
    out = run_with_devices(4, """
        import numpy as np, jax, jax.numpy as jnp
        from repro.kernels.decode_attention.sp import sp_decode_attention, sp_cache_update
        from repro.kernels.decode_attention.ref import decode_attention_ref
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((1, 4), ("data", "model"))
        B, Hq, Hkv, Dh, S = 2, 8, 2, 16, 64
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, Hq, Dh))
        k = jax.random.normal(ks[1], (B, S, Hkv, Dh))
        v = jax.random.normal(ks[2], (B, S, Hkv, Dh))
        for clen in (1, 17, 48, 64):
            ref = decode_attention_ref(q, k, v, jnp.int32(clen))
            with mesh:
                out = jax.jit(lambda q,k,v,c: sp_decode_attention(
                    q, k, v, c, mesh=mesh))(q, k, v, jnp.int32(clen))
            err = float(jnp.abs(out - ref).max())
            assert err < 1e-5, (clen, err)

        # cache update: write at position 17, verify only that slot changed
        kn = jax.random.normal(jax.random.PRNGKey(5), (B, Hkv, Dh))
        vn = jax.random.normal(jax.random.PRNGKey(6), (B, Hkv, Dh))
        with mesh:
            k2, v2 = jax.jit(lambda kc,vc,kn,vn,c: sp_cache_update(
                kc, vc, kn, vn, c, mesh=mesh))(k, v, kn, vn, jnp.int32(17))
        assert float(jnp.abs(k2[:, 17] - kn).max()) < 1e-6
        mask = jnp.arange(S) != 17
        assert float(jnp.abs(k2[:, mask] - k[:, mask]).max()) == 0.0
        print("SP_DECODE_OK")
    """)
    assert "SP_DECODE_OK" in out
