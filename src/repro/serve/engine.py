"""Continuous-batching engines: token decoding and gDDIM sampling.

Both engines are specializations of one `ServeLoop` core (loop.py): a fixed
device batch of `batch_size` slots, FIFO admission through a `Scheduler`,
host shadow bookkeeping in a `SlotTable`, and — since the `EngineState`
refactor — *device-resident* per-slot state updated inside a donated,
jitted round step (state.py, `make_token_round_step` /
`make_diffusion_round_step` in launch/steps.py).  What a "round" is differs:

  * `TokenEngine`  — one greedy decode token for every active slot.
    Admission runs a *batched* prefill through `make_prefill_step` (width-
    bucketed to the group's power-of-two size, so a 2-request wave on a
    16-slot engine pays 2 rows of FLOPs, not 16) and scatters the resulting
    cache rows slot-wise.  The round step decodes at the per-slot position
    `state.pos`, appends to the per-slot output ring, and retires on
    eos/budget — all on device.
  * `DiffusionEngine` — one gDDIM update for every active slot, each at its
    own step index k *and* its own sampler config (SDE family, NFE,
    multistep order q, corrector, stochasticity lambda); per-slot
    Psi/pC/cC/B/P_chol factor pairs are gathered from a stacked
    multi-family `FactoredBank` by (state.cfg[b], state.k[b]), slots live
    in the canonical packed (K, D) layout shared by every family, and a
    round dispatches one compiled variant per (family, corrector) class
    present in the batch.

Steady-state data flow: the round step consumes and returns the EngineState
(donated, so u/hist/caches update in place with no per-step copy) and the
host transfers *nothing* to the device per round — no slot metadata, no
token ids, no step indices.  The host polls a small done/progress mask at
most every `sync_every` rounds (exactly at the next possible retirement
when that is predictable) and fetches outputs only for retiring slots.
`tests/test_serve_engine.py` locks this in with a `jax.transfer_guard`.

Mesh mode: pass `mesh=` (e.g. `launch.mesh.make_local_mesh(data=2)`) and
the engine places params via the `distributed.sharding` param rules and the
slot batch — EngineState, caches, encoder memory — sharded over the `data`
axes (`serve_state_shardings` / `cache_shardings`).  Admission targets
free slots round-robin across shards.  Outputs are bitwise identical to
the single-device engine (per-row computation is row-independent), which
`tests/test_serve_mesh.py` asserts on a forced 2-device host.

Compile behaviour: after warmup the round programs are reused for every
round regardless of which slots retire or refill, and — for the diffusion
engine — regardless of which sampler configs the traffic mixes, because
the coefficient bank is a bucket-padded *argument* of the step
(`compile_stats()` exposes the jit cache sizes; the sampler step has at
most two entries, the predictor-only and with-corrector variants).
Prefill compiles once per (prompt length, width bucket) actually seen.

Determinism: slots are batch rows and every per-row computation in the
model stack is row-independent, so a request's output stream is bitwise
identical whether it runs alone or interleaved with arbitrary neighbours.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..launch import steps as steps_lib
from ..models.registry import Arch
from ..models import quantize as qtz
from ..core import CoeffCache, SamplerConfig
from ..sde.base import family_name
from ..distributed import sharding as shd
from .loop import ServeLoop, bucket_pow2
from .parking import row_fetch, row_restore
from .api import ServeRequest
from .scheduler import Scheduler
from .state import (DiffusionState, TokenState, diffusion_state_init,
                    token_state_init)

Array = jax.Array


def _cache_size(jitted) -> int:
    """Compiled programs in a `jax.jit` function's cache."""
    return int(jitted._cache_size())


def _make_row_scatter(batch_axes: List[int], out_shardings=None):
    """jitted (dst_tree, src_tree, slot_ids) -> dst_tree with src's batch
    rows written at `slot_ids`.  `slot_ids` is padded to the source batch
    size with an out-of-range sentinel; those rows are dropped, so one
    compilation serves every admission-wave width bucket.  The destination
    is donated: the scatter updates the engine cache in place.  In mesh
    mode `out_shardings` pins the result to the engine's canonical cache
    layout so the downstream round step never sees a second sharding."""

    def scatter(dst_tree, src_tree, slot_ids):
        dst_leaves, treedef = jax.tree.flatten(dst_tree)
        src_leaves, _ = jax.tree.flatten(src_tree)
        out = []
        for d, s, ax in zip(dst_leaves, src_leaves, batch_axes):
            dm = jnp.moveaxis(d, ax, 0)
            sm = jnp.moveaxis(s, ax, 0).astype(d.dtype)
            dm = dm.at[slot_ids].set(sm, mode="drop")
            out.append(jnp.moveaxis(dm, 0, ax))
        return jax.tree.unflatten(treedef, out)

    if out_shardings is None:
        return jax.jit(scatter, donate_argnums=(0,))
    return jax.jit(scatter, donate_argnums=(0,), out_shardings=out_shardings)


def _jit_state_update(fn, donate, out_shardings=None, **kw):
    """jit with the state donated and (mesh mode) the output pinned to the
    engine's canonical shardings — sharding stability is what keeps the
    round program's jit cache at one entry per variant."""
    if out_shardings is None:
        return jax.jit(fn, donate_argnums=donate, **kw)
    return jax.jit(fn, donate_argnums=donate, out_shardings=out_shardings,
                   **kw)


def _make_token_admit(out_shardings=None):
    """jitted admission scatter into a TokenState: writes the prefill token
    and per-slot counters for one wave.  Rows whose `slot_ids` carry the
    out-of-range sentinel are dropped.  A slot born done (budget 1, or the
    prefill token is already eos) starts inactive; the first poll retires
    it without a decode round.  The state is donated."""

    def admit(state, logits_last, slot_ids, budgets, pos, eos):
        first = jnp.argmax(logits_last, axis=-1).astype(jnp.int32)   # (G,)
        born_active = (budgets > 1) & (first != eos)
        return TokenState(
            last=state.last.at[slot_ids, 0].set(first, mode="drop"),
            pos=state.pos.at[slot_ids].set(pos, mode="drop"),
            n_out=state.n_out.at[slot_ids].set(1, mode="drop"),
            budget=state.budget.at[slot_ids].set(budgets, mode="drop"),
            out=state.out.at[slot_ids, 0].set(first, mode="drop"),
            active=state.active.at[slot_ids].set(born_active, mode="drop"))

    return _jit_state_update(admit, (0,), out_shardings)


def _make_row_gather(batch_axes: List[int]):
    """jitted (tree, i) -> row `i` of every leaf, each taken along its own
    batch axis (the cache twin of parking.row_fetch, whose leaves are all
    batch-leading).  `i` is a traced argument: one compiled gather serves
    every slot index, so repeated preemptions never recompile."""

    def gather(tree, i):
        leaves, treedef = jax.tree.flatten(tree)
        rows = [jnp.moveaxis(x, ax, 0)[i]
                for x, ax in zip(leaves, batch_axes)]
        return jax.tree.unflatten(treedef, rows)

    return jax.jit(gather)


def _make_deactivate(out_shardings=None):
    """jitted (state, i) -> state with slot `i`'s active flag cleared: a
    suspended slot's device row must stop advancing the moment its payload
    is parked (the frozen row is overwritten at re-admission either way —
    this just stops the round step from burning FLOPs on a parked row).
    The state is donated, like every state update."""

    def deactivate(state, i):
        return state._replace(active=state.active.at[i].set(False))

    return _jit_state_update(deactivate, (0,), out_shardings)


def _make_diffusion_admit(out_shardings=None):
    """jitted admission scatter into a DiffusionState: one slot row —
    packed prior sample, zeroed eps history, k=0, config index, family id,
    precision class, PRNG key.  The state is donated."""

    def admit(state, u_row, key_row, i, ci, fi, pi):
        return DiffusionState(
            u=state.u.at[i].set(u_row[0]),
            hist=state.hist.at[i].set(0.0),
            k=state.k.at[i].set(0),
            cfg=state.cfg.at[i].set(ci),
            fam=state.fam.at[i].set(fi),
            prec=state.prec.at[i].set(pi),
            keys=state.keys.at[i].set(key_row),
            active=state.active.at[i].set(True))

    return _jit_state_update(admit, (0,), out_shardings)


# ===========================================================================
# Token decoding
# ===========================================================================
class TokenEngine(ServeLoop):
    """Continuous-batching greedy decode over any `Arch` family.

    Usage:
        engine = TokenEngine(arch, params, batch_size=8, max_len=256)
        results = engine.serve([ServeRequest(rid=0, workload="token",
                                   tokens=prompt, max_new=32), ...])
        # results[rid] -> np.ndarray of generated token ids

    The engine is persistent: repeated `serve()` calls reuse the allocated
    cache and the compiled steps (retire-and-refill, no recompilation).
    Pass `mesh=` to shard the slot batch over the mesh's data axes (see the
    module docstring).
    """

    def __init__(self, arch: Arch, params: Any, batch_size: int, max_len: int,
                 eos_id: int = 1, mesh: Any = None,
                 shard_cfg: Optional[shd.ShardCfg] = None,
                 sync_every: int = 8):
        super().__init__(batch_size,
                         Scheduler(group_key=lambda r: r.prompt_len),
                         mesh=mesh, shard_cfg=shard_cfg,
                         sync_every=sync_every)
        self.arch = arch
        self.max_len = max_len

        caches = arch.init_cache(batch_size, max_len)
        axes_tree = arch.cache_batch_axes(max_len)
        state = token_state_init(batch_size, max_len)
        memory = None
        if arch.spec.family == "encdec":
            ctx, d = arch.spec.frontend_ctx, arch.cfg.d_model
            memory = jnp.zeros((batch_size, ctx, d), jnp.float32)

        caches_sh = state_sh = memory_sh = None
        if mesh is not None:
            scfg = self.shard_cfg
            params = jax.device_put(params,
                                    shd.param_shardings(params, mesh, scfg))
            caches_sh = shd.cache_shardings(
                caches, axes_tree, mesh, scfg, batch_size,
                getattr(arch.cfg, "n_kv_heads", 0),
                getattr(arch.cfg, "d_head", -1))
            caches = jax.device_put(caches, caches_sh)
            state_sh = shd.serve_state_shardings(state, mesh, scfg)
            state = jax.device_put(state, state_sh)
            if memory is not None:
                memory_sh = shd.logical_to_sharding(
                    mesh, shd.batch_spec(mesh, scfg, memory.ndim, batch_size))
                memory = jax.device_put(memory, memory_sh)
        self.params = params
        self.caches = caches
        self.state = state
        self.memory = memory

        self._merge = _make_row_scatter(jax.tree.leaves(axes_tree),
                                        out_shardings=caches_sh)
        self._admit_state = _make_token_admit(out_shardings=state_sh)
        self._cache_axes = jax.tree.leaves(axes_tree)
        # preemption machinery (serve_stream): gather a slot's state +
        # cache rows for parking, deactivate the parked device row, and
        # restore the parked bits into a free row on resume.  All take the
        # slot index as a traced argument — one compile each, warmed by
        # the first preemption
        self._fetch_row = jax.jit(row_fetch)
        self._fetch_cache_row = _make_row_gather(self._cache_axes)
        self._deactivate = _make_deactivate(out_shardings=state_sh)
        self._restore = _jit_state_update(row_restore, (0,), state_sh)
        self._snapshot = jax.jit(steps_lib.make_mask_snapshot())
        # the round step is donated on (state, caches): in-place at the XLA
        # level, no per-step copy of the KV/recurrent cache.  Output
        # shardings are pinned in mesh mode so retire-and-refill cycles
        # keep one compiled program
        self._decode = _jit_state_update(
            steps_lib.make_token_round_step(arch), (1, 2),
            None if mesh is None else (state_sh, caches_sh))
        self._prefill = jax.jit(steps_lib.make_prefill_step(arch, max_len))
        self._encode = None
        if arch.spec.family == "encdec":
            self._encode = jax.jit(arch.encode_memory)
            self._merge_memory = _make_row_scatter([0],
                                                   out_shardings=memory_sh)

        self.eos_id = eos_id

        # throughput counters (benchmarks read these)
        self.n_decode_steps = 0
        self.n_prefill_calls = 0
        self.n_tokens_out = 0
        # recent admission-wave widths (bounded: the engine is persistent)
        self.prefill_widths: deque = deque(maxlen=256)

    # eos is a *device* scalar argument of the round step (not a closure
    # constant), so changing it never recompiles and never transfers
    # per-round; the setter keeps the device copy in sync
    @property
    def eos_id(self) -> int:
        return self._eos_id

    @eos_id.setter
    def eos_id(self, v: int) -> None:
        self._eos_id = int(v)
        eos = jnp.int32(v)
        if self.mesh is not None:
            eos = jax.device_put(eos, shd.replicated(self.mesh))
        self._eos = eos

    def compile_stats(self) -> Dict[str, int]:
        stats = {"decode": _cache_size(self._decode),
                 "prefill": _cache_size(self._prefill),
                 "merge": _cache_size(self._merge),
                 "park": _cache_size(self._fetch_row)
                 + _cache_size(self._fetch_cache_row)
                 + _cache_size(self._deactivate),
                 "resume": _cache_size(self._restore),
                 "snapshot": _cache_size(self._snapshot)}
        if self._encode is not None:
            stats["encode"] = _cache_size(self._encode)
        return stats

    # ---- ServeLoop hooks ----------------------------------------------------
    def _validate(self, r: ServeRequest) -> None:
        if r.prompt_len < 1:
            raise ValueError(f"request {r.rid}: empty prompt")
        if r.max_new < 1:
            raise ValueError(f"request {r.rid}: max_new must be >= 1 "
                             f"(got {r.max_new})")
        if r.prompt_len + r.max_new > self.max_len:
            raise ValueError(
                f"request {r.rid}: prompt_len {r.prompt_len} + max_new "
                f"{r.max_new} exceeds max_len {self.max_len}")
        if self._encode is not None and r.frames is None:
            raise ValueError(f"request {r.rid}: encdec arch needs frames")

    def _admit_wave(self, group: List[ServeRequest], free: List[int]) -> None:
        # prefill width-bucketed to the group's power-of-two size: a small
        # admission wave no longer pays full-batch prefill FLOPs
        L = group[0].prompt_len
        G = bucket_pow2(len(group), self.batch_size)
        toks = np.zeros((G, L), np.int32)
        for g, req in enumerate(group):
            toks[g] = req.tokens
        batch = {"tokens": jnp.asarray(toks)}
        mem_g = None
        if self._encode is not None:
            shape = (G,) + self.memory.shape[1:]
            frames = np.zeros(shape, np.float32)
            for g, req in enumerate(group):
                frames[g] = req.frames
            with self._ctx():
                mem_g = self._encode(self.params, jnp.asarray(frames))
            batch["memory"] = mem_g

        with self._ctx():
            logits_last, caches_g = self._prefill(self.params, batch)
        self.n_prefill_calls += 1
        self.prefill_widths.append(G)

        # slot-wise scatter: row g of the wave -> free[g]; padded rows carry
        # the batch-size sentinel and are dropped (never touch a live slot)
        slot_ids = np.full((G,), self.batch_size, np.int32)
        budgets = np.ones((G,), np.int32)
        for g, req in enumerate(group):
            slot_ids[g] = free[g]
            budgets[g] = req.max_new
        ids = jnp.asarray(slot_ids)
        with self._ctx():
            self.caches = self._merge(self.caches, caches_g, ids)
            if mem_g is not None:
                self.memory = self._merge_memory(self.memory, mem_g, ids)
            self.state = self._admit_state(
                self.state, logits_last, ids, jnp.asarray(budgets),
                jnp.full((G,), L, jnp.int32), self._eos)
        for g, req in enumerate(group):
            # host shadow: n_out paces polls (it may overshoot the device
            # count after an early eos — resynced at every poll, and an
            # overshoot only makes the next poll earlier, never later)
            self.slots.assign(free[g], req, n_out=1, budget=req.max_new)

    def _round(self) -> None:
        with self._ctx():
            self.state, self.caches = self._decode(
                self.params, self.state, self.caches, self._eos, self.memory)
        self.n_decode_steps += 1
        for s in self.slots.active():
            s.data["n_out"] += 1

    def _poll(self, results: Dict[int, np.ndarray], snap=None,
              lag: int = 0) -> int:
        busy = self.slots.active()
        if not busy:
            return 0
        if snap is None:
            snap = (self.state.active, self.state.n_out)
        # the one steady-state device fetch: the done/progress mask (in
        # the double-buffered online poll, a snapshot taken before the
        # look-ahead round — blocking here overlaps that round's compute)
        active, n_out = jax.device_get(snap)  # staticcheck: disable=SC103 (the one sanctioned steady-state fetch: done/progress mask, once per poll)
        finished = [s for s in busy if not active[s.index]]
        if finished:
            # retired rows are frozen, so reading the *live* out buffer is
            # exact even with a look-ahead round in flight
            out = jax.device_get(self.state.out)  # staticcheck: disable=SC103 (terminal drain: runs only when a request finished, not steady-state)
            for s in finished:
                n = int(n_out[s.index])
                results[s.request.rid] = out[s.index, :n].astype(np.int32)
                self.n_tokens_out += n
                self.slots.release(s.index)
        for s in self.slots.active():
            # resync the shadow from the snapshot, plus the rounds
            # dispatched after it (`lag`: the online look-ahead)
            s.data["n_out"] = int(n_out[s.index]) + lag
        return len(finished)

    def _poll_snapshot(self):
        with self._ctx():
            return self._snapshot(self.state.active, self.state.n_out)

    def _suspend_slot(self, slot):
        i = np.int32(slot.index)
        with self._ctx():
            state_row = self._fetch_row(self.state, i)
            cache_row = self._fetch_cache_row(self.caches, i)
            mem_row = None if self.memory is None \
                else self._fetch_row(self.memory, i)
            self.state = self._deactivate(self.state, i)
        return (state_row, cache_row, mem_row)

    def _resume_slot(self, request: ServeRequest, shadow: dict, payload,
                     index: int) -> None:
        state_row, cache_row, mem_row = payload
        ids = jnp.asarray([index], np.int32)
        # the cache scatter expects source rows in the caches' own layout
        # (batch axis in place, size 1) — the merge is the same program
        # width-1 admission waves warm
        leaves, treedef = jax.tree.flatten(cache_row)
        src = jax.tree.unflatten(treedef, [
            np.expand_dims(x, ax)
            for x, ax in zip(leaves, self._cache_axes)])
        with self._ctx():
            self.caches = self._merge(self.caches, src, ids)
            if mem_row is not None:
                self.memory = self._merge_memory(
                    self.memory, mem_row[None], ids)
            self.state = self._restore(self.state, state_row,
                                       np.int32(index))

    def _remaining_lb(self, slot) -> int:
        return slot.data["budget"] - slot.data["n_out"]


# ===========================================================================
# gDDIM sampling service
# ===========================================================================
class DiffusionEngine(ServeLoop):
    """Continuous-batching gDDIM sampling over a *heterogeneous* sampler
    family: slots are samples, the per-slot position is the sampler step
    index k, and every slot additionally carries its own sampler config —
    SDE family, NFE budget, multistep order q, Eq. 45 corrector toggle,
    and Eq. 22 stochasticity lambda.  One resident engine, a handful of
    compiled step variants, many scenarios: a 10-NFE VPSDE preview batches
    with a 50-NFE CLD predictor-corrector render and a BDM sample.

    Usage (single family — the historical surface):
        engine = DiffusionEngine(spec, params, batch_size=16, nfe=50)
        results = engine.serve([
            SampleRequest(rid=0, seed=0),                    # engine default
            SampleRequest(rid=1, seed=1, nfe=10),            # fast preview
            SampleRequest(rid=2, seed=2, nfe=50, q=2, corrector=True),
            SampleRequest(rid=3, seed=3, nfe=20, lam=0.5),   # stochastic
        ])
        # results[rid] -> np.ndarray sample in data space

    Multi-family: pass ordered mappings `{family_name: spec}` /
    `{family_name: params}` (names per `repro.sde.base.family_name`; the
    first entry is the default family) and requests pick their family:

        engine = DiffusionEngine({"vpsde": spec_v, "cld": spec_c,
                                  "bdm": spec_b},
                                 {"vpsde": pv, "cld": pc, "bdm": pb},
                                 batch_size=16, nfe=20)
        engine.serve([SampleRequest(rid=0, seed=0),              # vpsde
                      SampleRequest(rid=1, seed=1, family="cld"),
                      SampleRequest(rid=2, seed=2, family="bdm", nfe=10)])

    All families must share one `data_shape`; every slot lives in the
    canonical packed (K, D) layout of `kernels/ei_update/ops.py`
    (K = max family channel width: VPSDE/BDM 1, CLD 2; BDM slots hold DCT
    coefficients and ride the dct2 kernel path), so one slot pool, one
    mesh, one `DiffusionState` serve the whole mix.  Each family's
    score-net params are placed on device once at construction and stay
    resident; a serving round dispatches one jitted round-step variant per
    (family, corrector) cost class *present among active slots* — each
    variant evaluates its family's score net over the packed batch and
    commits updates only to its own slots — so homogeneous traffic pays
    exactly the single-family cost and a mixed batch pays one model
    evaluation per resident family per round.

    Coefficients come from a host-side `CoeffCache` (Stage-I quadrature run
    once per distinct config) whose stacked multi-family `FactoredBank` —
    (K, K) block factors plus a deduplicated (D,) diagonal pool, ~D-fold
    smaller device-resident than the dense layout it replaced — is padded
    to bucketed shapes and passed to the jitted step as an argument, so
    admitting a config the engine has never seen refreshes the bank
    *contents* without recompiling, as long as the new config fits the
    warmed buckets (`FactoredBank.shape_key`; a bucket overflow — incl.
    the diag pool's, which only first-seen BDM-family configs can grow —
    costs one recompile, then the doubled bucket absorbs further growth;
    registration appends factored rows instead of restacking the bank).
    The
    corrector needs a second model evaluation per step, so each family has
    two jit variants (static `with_corrector`); each round dispatches per
    family on whether any of *its* active slots wants the corrector —
    known host-side from the admission shadow, so dispatch costs no device
    fetch.  The scheduler keeps admission waves homogeneous in the
    (family, corrector) cost class, which biases runs of same-class
    traffic into sharing rounds — it cannot prevent classes from
    co-residing after retire-and-refill, so a VPSDE slot admitted next to
    a mid-flight CLD render shares its rounds with both models' dispatches
    (correct, just not cheaper) until the render retires.

    A sampler slot's retirement round is *exactly* predictable (a slot
    admitted at k=0 with NFE n retires after n rounds), so the loop's
    host shadow paces polls with zero device fetches for metadata; the only
    device->host traffic is the finished sample itself.

    Samples are a pure function of (request seed, sampler config): the
    stochastic branch keys its per-step noise by fold_in(seed-derived key,
    k), so admission order and neighbouring slots — whatever their family —
    cannot change a result (per-row independence plus static per-family
    sub-block arithmetic, locked in bitwise by tests/test_serve_engine.py).
    """

    _NOISE_SALT = 0x5EED              # separates step noise from the prior
    greedy_admit = False              # one cost-class wave per admission
                                      # cycle (see ServeLoop.greedy_admit)

    def __init__(self, spec: Any, params: Any, batch_size: int,
                 nfe: Optional[int] = None, grid: Optional[str] = None,
                 default_config: Optional[SamplerConfig] = None,
                 precision: str = "f32",
                 mesh: Any = None,
                 shard_cfg: Optional[shd.ShardCfg] = None,
                 sync_every: int = 8):
        if isinstance(spec, dict):
            specs = dict(spec)
            if not isinstance(params, dict) or set(params) != set(specs):
                raise ValueError("multi-family DiffusionEngine needs params "
                                 "as a dict with the same family names as "
                                 "spec")
            params = {n: params[n] for n in specs}     # align orders
        else:
            name = family_name(spec.sde)
            specs, params = {name: spec}, {name: params}
        shapes = {n: tuple(s.data_shape) for n, s in specs.items()}
        if len(set(shapes.values())) != 1:
            raise ValueError("all families of one engine must share a "
                             f"data_shape; got {shapes}")

        if default_config is None:
            default_config = SamplerConfig(
                nfe=20 if nfe is None else nfe,
                grid="quadratic" if grid is None else grid)
        elif nfe is not None or grid is not None:
            raise ValueError("pass either nfe/grid or default_config, "
                             "not both")
        self.default_config = default_config
        self.nfe = default_config.nfe
        super().__init__(
            batch_size,
            Scheduler(group_key=lambda r: self._class_of(r)),
            mesh=mesh, shard_cfg=shard_cfg, sync_every=sync_every)
        self.specs = specs
        self.spec = next(iter(specs.values()))         # default family spec
        self._data_shape = next(iter(shapes.values()))

        self.cache = CoeffCache({n: s.sde for n, s in specs.items()},
                                kt={n: s.kt for n, s in specs.items()},
                                data_shape=self._data_shape)
        if default_config.family is not None \
                and default_config.family not in specs:
            raise ValueError(f"default_config.family "
                             f"{default_config.family!r} is not resident; "
                             f"families: {list(specs)}")
        if default_config.family is None:
            default_config = dataclasses.replace(
                default_config, family=self.cache.default_family)
            self.default_config = default_config
        self.cache.index_of(default_config)
        # single-config Stage-I bank of the default config (reference /
        # introspection surface; the serve loop reads the stacked bank)
        self.coeffs = self.cache.get(default_config)

        k_max = self.cache.k_max
        data_dim = int(np.prod(self._data_shape))
        state = diffusion_state_init(batch_size, k_max, data_dim,
                                     self.cache.factored_bank.pC_blk.shape[2])
        state_sh = None
        if mesh is not None:
            params = {n: jax.device_put(
                p, shd.param_shardings(p, mesh, self.shard_cfg))
                for n, p in params.items()}
            state_sh = shd.serve_state_shardings(state, mesh, self.shard_cfg)
            state = jax.device_put(state, state_sh)
        self.params = params
        self.state = state
        self._state_sh = state_sh       # NamedShardings are shape-free:
                                        # still valid after hist regrowth
        self._bank_src = None
        self._bank = None
        self._refresh_bank()

        # low-precision serving: requests pick a score-net precision class
        # (engine default `precision`); each class keeps its own lazily-
        # quantized device-resident copy of the family's params
        # (models/quantize — bf16 cast / int8 QTensor residency) and its
        # own compiled round variants, masked per-slot by `state.prec`
        self.precision = qtz.check_precision(precision)
        self._params_prec: Dict[Any, Any] = {
            (n, "f32"): p for n, p in params.items()}

        # one round-step program per (family, precision) class (x2
        # with_corrector variants), donated on the state: u/hist update in
        # place.  The family index and precision class baked into each
        # variant are the closure constants that keep the steady-state
        # round transfer-free; unused precision classes never trace, so
        # they cost nothing until traffic asks for them
        self._steps = {
            (n, prec): _jit_state_update(
                steps_lib.make_diffusion_round_step(
                    s, fam_index=self.cache.fam_index(n),
                    prec_index=pi,
                    eps_model=qtz.wrap_eps_model(s.eps_model, prec)),
                (1,), state_sh, static_argnames=("with_corrector",))
            for n, s in specs.items()
            for pi, prec in enumerate(qtz.PRECISIONS)}
        self._admit_state = _make_diffusion_admit(out_shardings=state_sh)
        # preemption machinery (serve_stream): every DiffusionState leaf is
        # batch-leading, so the generic parking row fetch/restore covers the
        # whole per-slot row (u, hist, k, cfg, fam, keys, active) — a
        # resumed slot continues mid-trajectory, mid-multistep-history, on
        # exactly the bits it was suspended with
        self._fetch_row = jax.jit(row_fetch)
        self._deactivate = _make_deactivate(out_shardings=state_sh)
        self._restore = _jit_state_update(row_restore, (0,), state_sh)

        def make_prior(s):
            from ..kernels.ei_update.ops import pad_channels
            sde, dshape = s.sde, tuple(s.data_shape)
            kf = sde.packed_k

            def prior(key):                       # (1, K, D) packed row
                u = sde.canonicalize(sde.prior_sample(key, 1, dshape))
                return pad_channels(u, k_max)

            def project(u, i):                    # packed row -> data space
                return sde.project_data(
                    sde.decanonicalize(u[i][None, :kf], dshape))[0]

            return jax.jit(prior), jax.jit(project)

        self._prior1, self._project_row = {}, {}
        for n, s in specs.items():
            self._prior1[n], self._project_row[n] = make_prior(s)

        self.n_steps = 0                # step-program dispatches
        self.n_rounds = 0               # serving rounds (>= 1 dispatch each)
        self.n_samples_out = 0

    @property
    def families(self) -> List[str]:
        return list(self.specs)

    def compile_stats(self) -> Dict[str, int]:
        # step counts every (family, corrector) jit variant; after warmup
        # it stays put across any traffic mix whose configs fit the warmed
        # coefficient buckets
        return {"step": sum(_cache_size(s) for s in self._steps.values()),
                "prior": sum(_cache_size(p) for p in self._prior1.values()),
                "park": _cache_size(self._fetch_row)
                + _cache_size(self._deactivate),
                "resume": _cache_size(self._restore)}

    def config_of(self, req: ServeRequest) -> SamplerConfig:
        d = self.default_config
        pick = lambda v, dv: dv if v is None else v
        fam = pick(req.family, pick(d.family, self.cache.default_family))
        if fam not in self.specs:
            raise ValueError(f"unknown SDE family {fam!r}; resident "
                             f"families: {list(self.specs)}")
        return SamplerConfig(
            nfe=pick(req.nfe, d.nfe), q=pick(req.q, d.q),
            corrector=pick(req.corrector, d.corrector),
            lam=pick(req.lam, d.lam), grid=pick(req.grid, d.grid),
            family=fam, algorithm=pick(req.algorithm, d.algorithm))

    def precision_of(self, req: ServeRequest) -> str:
        """The request's score-net precision class (engine default when
        unset) — never part of the SamplerConfig: coefficients stay f32
        and bitwise at every precision (models/quantize docstring)."""
        return qtz.check_precision(
            self.precision if req.precision is None else req.precision)

    def _class_of(self, req: ServeRequest):
        """The admission-wave cost class: (family, corrector, precision)."""
        cfg = self.config_of(req)
        return (cfg.family, cfg.corrector, self.precision_of(req))

    def _params_for(self, fam: str, prec: str):
        """This (family, precision) class's device-resident params —
        quantized from the placed f32 copy on first use, then cached
        (resident next to the f32 copy; the round program reads the
        low-precision buffers directly)."""
        key = (fam, prec)
        if key not in self._params_prec:
            self._params_prec[key] = qtz.quantize_tree(self.params[fam],
                                                       prec)
        return self._params_prec[key]

    # ---- coefficient-bank placement ----------------------------------------
    def _refresh_bank(self) -> None:
        """Re-place the factored bank on device when the CoeffCache grew it
        (a new config appended rows / pool entries), and grow the state's
        eps-history bucket if the bank's Qb bucket grew (one-time warmup
        shape change)."""
        bank = self.cache.factored_bank
        if bank is self._bank_src:
            return
        self._bank_src = bank
        if self.mesh is not None:
            bank = jax.device_put(
                bank, shd.bank_shardings(self.mesh, self.shard_cfg, bank))
        self._bank = bank
        qb = bank.pC_blk.shape[2]
        hist = self.state.hist
        if hist.shape[1] < qb:
            pad = jnp.zeros((self.batch_size, qb - hist.shape[1])
                            + hist.shape[2:], jnp.float32)
            hist = jnp.concatenate([hist, pad], axis=1)
            if self._state_sh is not None:
                hist = jax.device_put(hist, self._state_sh.hist)
            self.state = self.state._replace(hist=hist)

    # ---- ServeLoop hooks ----------------------------------------------------
    def _validate(self, r: ServeRequest) -> None:
        try:
            self.config_of(r)           # fail fast, before any device work
            self.precision_of(r)
        except ValueError as e:
            raise ValueError(f"request {r.rid}: {e}") from None

    def _prepare(self, requests: List[ServeRequest]) -> None:
        """Register every request's config before anything is admitted, so
        the bank restacks (and, if the call introduces a bucket overflow,
        re-buckets) exactly once up front — a warmup call that covers the
        deployment's config menu then compiles every (family, corrector)
        variant at the final bank shapes, and later traffic inside those
        buckets never recompiles."""
        for r in requests:
            self.cache.index_of(self.config_of(r))

    def _admit_wave(self, group: List[ServeRequest], free: List[int]) -> None:
        # register the whole wave's configs before touching the bank, so it
        # restacks at most once per wave (not once per new config; mid-call
        # this is a no-op after `_prepare`, but direct scheduler submits —
        # tests, streaming admission — still land here first)
        cfgs = [self.config_of(req) for req in group]
        idx = [self.cache.index_of(cfg) for cfg in cfgs]
        self._refresh_bank()
        for req, cfg, ci in zip(group, cfgs, idx):
            i = free.pop(0)
            fi = self.cache.fam_index(cfg.family)
            prec = self.precision_of(req)
            pi = qtz.prec_index(prec)
            base = jax.random.PRNGKey(req.seed)
            with self._ctx():
                row = self._prior1[cfg.family](base)
                key_row = jax.random.fold_in(base, self._NOISE_SALT)
                self.state = self._admit_state(self.state, row, key_row,
                                               np.int32(i), np.int32(ci),
                                               np.int32(fi), np.int32(pi))
            self.slots.assign(i, req, k=0, cfg=ci, nfe=cfg.nfe,
                              family=cfg.family, pc=cfg.corrector, prec=prec)

    def _round(self) -> None:
        # dispatch one variant per (family, precision, corrector) class
        # present among active slots — a host-shadow read, no device fetch.
        # Iteration follows (family registration order) x (PRECISIONS
        # order) so a round's dispatch sequence is deterministic
        want: Dict[Tuple[str, str], bool] = {}
        for s in self.slots.active():
            cls = (s.data["family"], s.data["prec"])
            want[cls] = want.get(cls, False) or s.data["pc"]
        for fam in self.families:
            for prec in qtz.PRECISIONS:
                cls = (fam, prec)
                if cls not in want:
                    continue
                with self._ctx():
                    self.state = self._steps[cls](
                        self._params_for(fam, prec), self.state, self._bank,
                        with_corrector=want[cls])
                self.n_steps += 1
        self.n_rounds += 1
        for s in self.slots.active():
            s.data["k"] += 1

    def _poll(self, results: Dict[int, np.ndarray], snap=None,
              lag: int = 0) -> int:
        # retirement is exactly predictable from the host shadow (k reaches
        # the config's NFE after exactly NFE rounds): no device fetch at
        # all for metadata, only the finished samples themselves.  There is
        # no device mask to snapshot, so the online poll's observation
        # point is reconstructed by discounting `lag` (rounds dispatched
        # after it — the look-ahead): a slot that finishes *inside* the
        # look-ahead round retires at the next poll, exactly like the
        # token engine's snapshot semantics, so its completion stamp never
        # predates the round that produced it.  The round step freezes a
        # finished row on device (active = k < nfe), so reading the live
        # `state.u` under a look-ahead round in flight is bitwise exact
        done = [s for s in self.slots.active()
                if s.data["k"] - lag >= s.data["nfe"]]
        for s in done:
            with self._ctx():
                row = self._project_row[s.data["family"]](self.state.u,
                                                          s.index)
            results[s.request.rid] = np.asarray(row)  # staticcheck: disable=SC103 (terminal result materialization at slot release, not steady-state)
            self.n_samples_out += 1
            self.slots.release(s.index)
        return len(done)

    def _suspend_slot(self, slot):
        i = np.int32(slot.index)
        with self._ctx():
            row = self._fetch_row(self.state, i)    # before the deactivate:
            self.state = self._deactivate(self.state, i)  # parked active=True
        return row

    def _resume_slot(self, request: ServeRequest, shadow: dict, payload,
                     index: int) -> None:
        qb = self.state.hist.shape[1]
        hist = payload.hist
        if hist.shape[0] < qb:
            # the bank's Qb bucket grew while the row was parked (a first-
            # seen higher-q config arrived): pad with zeros — exactly what
            # `_refresh_bank` padded every *resident* row with, so resumed
            # == never-suspended, bitwise, across the regrowth
            pad = np.zeros((qb - hist.shape[0],) + hist.shape[1:],
                           hist.dtype)
            payload = payload._replace(
                hist=np.concatenate([hist, pad], axis=0))
        with self._ctx():
            self.state = self._restore(self.state, payload, np.int32(index))

    def _remaining_lb(self, slot) -> int:
        return slot.data["nfe"] - slot.data["k"]
