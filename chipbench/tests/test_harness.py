"""Whole runs of tiny cells on the CPU (the look for a chip skipped): the
sound program is correct, and each fault a serving cell can have under
the timed path makes `correct` false, with one family and with two
co-resident ones.  The exchange between chips has no fault here: every
cell runs on one chip."""
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from chipbench.harness import Abort, family_views, run_cell
from chipbench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]


def run(cell, seed=11, **kw):
    return run_cell(cell, seed, 2.0, False, t_process=time.perf_counter(),
                    require_tpu=False, **kw)


def test_sound_run_is_correct():
    res = run(tiny.cell("vpsde"))
    assert res["correct"], res
    assert res["failed"] == 0 and res["attempted"] == 12
    assert res["checks"]["sample_rel_l2_max"]["value"] < 1e-5
    assert set(res["metrics"]) == {"samples_per_s", "latency_p50_s",
                                   "latency_p95_s", "setup_s"}
    assert list(res)[-1] == "checks"


def _jit_step(fn):
    import jax
    return jax.jit(fn, static_argnames=("with_corrector",))


def _unchanged_state(engine):
    for key in engine._steps:
        engine._steps[key] = _jit_step(
            lambda params, state, bank, with_corrector=False: state)


def _half_batch(engine):
    import jax
    import jax.numpy as jnp
    for key, step in list(engine._steps.items()):
        def half(params, state, bank, with_corrector=False, step=step):
            old = jax.tree.map(jnp.copy, state)
            new = step(params, old, bank, with_corrector=with_corrector)
            B = new.u.shape[0]
            keep = (jnp.arange(B) < B // 2)
            pick = lambda a, b: jnp.where(
                keep.reshape((-1,) + (1,) * (a.ndim - 1)), a, b)
            return new._replace(u=pick(new.u, old.u),
                                hist=pick(new.hist, old.hist))
        engine._steps[key] = _jit_step(half)


def _neighbour_answer(engine):
    for fam, project in list(engine._project_row.items()):
        B = engine.batch_size
        engine._project_row[fam] = \
            lambda u, i, project=project: project(u, (i + 1) % B)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _neighbour_answer])
def test_fault_makes_the_run_incorrect(fault):
    res = run(tiny.cell("vpsde"), engine_hook=fault)
    assert not res["correct"]
    assert res["checks"]["sample_rel_l2_max"]["value"] > 1e-1


TWO = ("sample_rel_l2_max.vpsde", "sample_rel_l2_max.cld")


def test_two_family_run_is_correct():
    """VPSDE and CLD co-resident, mixed traffic: each family's samples are
    held to its own reference and limit."""
    res = run(tiny.cell("vpsde+cld"))
    assert res["correct"], res
    assert res["failed"] == 0 and res["attempted"] == 12
    assert list(res["checks"]) == [*TWO, "unfinished"]
    for name in TWO:
        assert res["checks"][name]["value"] < 1e-5


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _neighbour_answer])
def test_fault_makes_the_two_family_run_incorrect(fault):
    res = run(tiny.cell("vpsde+cld"), engine_hook=fault)
    assert not res["correct"]
    assert max(res["checks"][n]["value"] for n in TWO) > 1e-1


def test_a_single_family_config_is_its_own_view():
    cfg = tiny.config("cld")
    views = family_views(cfg)
    assert list(views) == ["cld"] and views["cld"] is cfg
    cell = tiny.cell("cld")
    assert {m["family"] for m in cell.traffic["mix"]} == {"cld"}


def test_cell_gives_each_family_its_own_view():
    cell = tiny.cell("vpsde+cld")
    v, c = cell.families["vpsde"], cell.families["cld"]
    assert (v["in_channels"], c["in_channels"]) == (3, 6)
    assert v["sde"]["kind"] == "vpsde" and c["sde"]["kind"] == "cld"
    assert v["hidden_size"] == c["hidden_size"] == 64
    assert "families" not in v
    with pytest.raises(Abort, match="listed twice"):
        family_views(tiny.co_resident(tiny.config("cld"),
                                      tiny.config("cld")))
    with pytest.raises(Abort, match="bdm"):
        tiny.cell("vpsde", mix=[dict(tiny.MIX[0], family="bdm")])


def test_each_family_draws_its_own_net():
    """The first family draws from the seed's own key, as a single-family
    config does; the next from a key of its own."""
    import numpy as np
    from chipbench.weights import make_weights
    cfg = tiny.config("vpsde")
    w0, again, w1 = (make_weights(cfg, 2**31 + 9, family_index=j)
                     for j in (0, 0, 1))
    assert np.array_equal(w0["t_mlp2"], again["t_mlp2"])
    assert not np.array_equal(w0["t_mlp2"], w1["t_mlp2"])


@pytest.mark.parametrize("family", ["vpsde", "cld"])
def test_control_is_incorrect(family):
    """The control, the reference with fp8 matmul inputs in the program's
    place, reads far above the sound program (readings printed)."""
    sound = run(tiny.cell(family), seed=12)
    ctrl = run(tiny.cell(family), seed=12, control=True)
    s = sound["checks"]["sample_rel_l2_max"]["value"]
    c = ctrl["checks"]["sample_rel_l2_max"]["value"]
    print(f"sound {s:.3e} control {c:.3e}")
    assert sound["correct"] and not ctrl["correct"] and c > 3 * s


def test_run_py_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "cld-backlog-b64-nfe50", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2 and p.stdout == ""
    assert "TPU" in p.stderr


def test_run_py_refuses_outside_a_checkout(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "cld-backlog-b64-nfe50", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 2 and p.stdout == ""
