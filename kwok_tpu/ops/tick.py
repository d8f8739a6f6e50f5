"""The vectorized stage-transition tick kernel.

This single jitted step replaces the reference's entire hot loop —
informer event -> preprocess -> Lifecycle.Match -> WeightDelayingQueue
-> playStageWorker -> patch (reference: pkg/kwok/controllers/
pod_controller.go:196-360 and pkg/utils/queue/weight_delaying_queue.go)
— with one batched pass over the struct-of-arrays:

1. **fire**: rows whose timer elapsed (the delay-queue pop);
2. **effects**: feature-column updates gathered from the compiled
   effect tables (the rendered patch, pre-lowered by the compiler);
3. **rematch**: masked predicate tests over all stages (Lifecycle.Match);
4. **choice**: weighted sampling by cumulative-sum inversion, with the
   reference's zero-total fallback to uniform-among-matched
   (lifecycle.go:125-191 — the device path has no weight errors, so the
   error rungs of the ladder collapse);
5. **timers**: delay + jitter (uniform in [duration, jitter)), with
   per-object annotation overrides and deletionTimestamp deadlines
   (lifecycle.go:313-341), producing the next fire time.

Everything is int32 (virtual milliseconds) and bfloat16/float32-free on
purpose: the FSM is integer-exact, which keeps device/host parity
bit-stable. All shapes are static; control flow is mask arithmetic, and
the stage tables are read by selects, not gathers, wherever they are
small (``_lookup``), so XLA fuses the whole tick into a handful of
elementwise kernels — MXU is not the bottleneck here, HBM bandwidth is,
and the layout is one contiguous [N, C] features array.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kwok_tpu.engine.compiler import IDLE, NEVER, SENTINEL, CompiledStageSet


class TickParams(NamedTuple):
    """Compiled stage-set tensors (static per stage set / signatures)."""

    cond_col: jax.Array  # [S, K] int32
    cond_mask: jax.Array  # [S, K] int32
    cond_neg: jax.Array  # [S, K] bool
    cond_valid: jax.Array  # [S, K] bool
    w_static: jax.Array  # [S] int32
    d_static: jax.Array  # [S] int32 ms
    j_static: jax.Array  # [S] int32 ms (SENTINEL = none)
    has_jitter: jax.Array  # [S] bool
    d_from_del_ts: jax.Array  # [S] bool
    j_from_del_ts: jax.Array  # [S] bool
    stage_delete: jax.Array  # [S] bool
    eff_mode: jax.Array  # [SIG, S, C] int32 (0 keep / 1 set)
    eff_val: jax.Array  # [SIG, S, C] int32
    ov_w: jax.Array  # [OVC, S] int32 (SENTINEL = no override)
    ov_d: jax.Array  # [OVC, S] int32
    ov_j: jax.Array  # [OVC, S] int32


class SoA(NamedTuple):
    """Device-resident simulation state: one row per object."""

    features: jax.Array  # [N, C] int32 bitmask columns
    sig: jax.Array  # [N] int32 signature id
    ovc: jax.Array  # [N] int32 override-class id
    stage: jax.Array  # [N] int32 current stage (IDLE = none)
    fire_at: jax.Array  # [N] int32 virtual ms (NEVER = idle)
    active: jax.Array  # [N] bool (admitted and not deleted)
    rematch: jax.Array  # [N] bool (host-forced re-evaluation)
    del_ts: jax.Array  # [N] int32 deletionTimestamp virtual ms (SENTINEL = absent)
    now: jax.Array  # [] int32 virtual ms
    key: jax.Array  # PRNG key


class TickOut(NamedTuple):
    fired: jax.Array  # [N] bool — rows that transitioned this tick
    fired_stage: jax.Array  # [N] int32 — stage that fired (IDLE otherwise)
    deleted: jax.Array  # [N] bool — rows deleted this tick
    fired_count: jax.Array  # [] int32


def params_from_compiled(cset: CompiledStageSet) -> TickParams:
    eff_mode, eff_val = cset.effect_tables()
    if (eff_mode == eff_mode[:1]).all() and (eff_val == eff_val[:1]).all():
        # every signature lowers every stage alike (no effect reads the
        # object): one row serves them all, the tick reads it by stage
        # alone, and a new signature changes no shape the tick compiles for
        eff_mode, eff_val = eff_mode[:1], eff_val[:1]
    ov_w, ov_d, ov_j = cset.override_tables()
    return TickParams(
        cond_col=jnp.asarray(cset.cond_col),
        cond_mask=jnp.asarray(cset.cond_mask),
        cond_neg=jnp.asarray(cset.cond_neg),
        cond_valid=jnp.asarray(cset.cond_valid),
        w_static=jnp.asarray(cset.w_static),
        d_static=jnp.asarray(cset.d_static),
        j_static=jnp.asarray(cset.j_static),
        has_jitter=jnp.asarray(cset.has_jitter),
        d_from_del_ts=jnp.asarray(cset.d_from_del_ts),
        j_from_del_ts=jnp.asarray(cset.j_from_del_ts),
        stage_delete=jnp.asarray(cset.stage_delete),
        eff_mode=jnp.asarray(eff_mode),
        eff_val=jnp.asarray(eff_val),
        ov_w=jnp.asarray(ov_w),
        ov_d=jnp.asarray(ov_d),
        ov_j=jnp.asarray(ov_j),
    )


#: a table of at most this many entries is read by one select an entry,
#: elementwise and fused with the rest of the tick, and not by a gather,
#: which the TPU runs row by row (a 1,048,576-row SoA spent most of its
#: tick in the effect gathers)
_SELECT_MAX = 32


def _lookup(table: jax.Array, idx: jax.Array) -> jax.Array:
    """``table[idx]`` along axis 0 for in-range indices ``idx`` [N]."""
    n = table.shape[0]
    if n > _SELECT_MAX:
        return table[idx]
    tail = (1,) * (table.ndim - 1)
    out = jnp.broadcast_to(table[0], idx.shape + table.shape[1:])
    for i in range(1, n):
        out = jnp.where((idx == i).reshape(idx.shape + tail), table[i], out)
    return out


def match_stages(params: TickParams, features: jax.Array) -> list:
    """S [N] bool arrays: selector match per stage per row (Lifecycle.match)."""
    S, K = params.cond_col.shape
    C = features.shape[1]
    cols = [features[:, c] for c in range(C)]
    outs = []
    for s in range(S):  # S, K and C are small & static: unrolled, elementwise
        m = jnp.ones(features.shape[0], dtype=bool)
        for k in range(K):
            col = params.cond_col[s, k]
            picked = cols[0]
            for c in range(1, C):
                picked = jnp.where(col == c, cols[c], picked)
            test = (picked & params.cond_mask[s, k]) != 0
            test = jnp.where(params.cond_neg[s, k], ~test, test)
            m = m & jnp.where(params.cond_valid[s, k], test, True)
        outs.append(m)
    return outs


def _weighted_choice(
    match: list, weights: list, u: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Reference fallback ladder, vectorized (no weight-error rungs on
    device): weighted among matched with weight>0 when total>0, else
    uniform among matched.  ``match`` and ``weights`` hold one [N] array
    a stage.  Returns (stage_idx, any_match)."""
    wm = [jnp.where(m & (w > 0), w, 0) for m, w in zip(match, weights)]
    total = functools.reduce(jnp.add, wm)
    probs = [jnp.where(total > 0, x, m.astype(jnp.int32)) for x, m in zip(wm, match)]
    ptot = functools.reduce(jnp.add, probs)
    any_match = ptot > 0
    # sample by cumulative-sum inversion: first index with cum > r
    r = (u * ptot.astype(jnp.float32)).astype(jnp.int32)  # r in [0, ptot)
    r = jnp.minimum(r, jnp.maximum(ptot - 1, 0))
    cum = jnp.zeros_like(ptot)
    choice = jnp.zeros_like(ptot)
    found = jnp.zeros(ptot.shape, bool)
    for s, p in enumerate(probs):
        cum = cum + p
        hit = ~found & (cum > r)
        choice = jnp.where(hit, s, choice)
        found = found | hit
    return jnp.where(any_match, choice, IDLE), any_match


def _tick_impl(params: TickParams, soa: SoA, dt_ms: int) -> Tuple[SoA, TickOut]:
    """Advance virtual time by dt_ms and run one transition pass."""
    now = soa.now + jnp.int32(dt_ms)
    key, k_choice, k_jitter = jax.random.split(soa.key, 3)
    N, C = soa.features.shape
    S = params.w_static.shape[0]

    # 1. fire: delay elapsed (the WeightDelayingQueue pop)
    fired = soa.active & (soa.stage >= 0) & (soa.fire_at <= now)
    stage_c = jnp.clip(soa.stage, 0, S - 1)

    # 2. effects: the compiled patch lowering for (sig, stage), mode and
    # value in one read; one table row where every signature's effects
    # are the same (params_from_compiled)
    eff = jnp.concatenate([params.eff_mode, params.eff_val], axis=-1)
    if eff.shape[0] == 1:
        eff = _lookup(eff[0], stage_c)  # [N, 2C]
    else:
        eff = _lookup(eff.reshape(-1, 2 * C), soa.sig * S + stage_c)
    mode, val = eff[:, :C], eff[:, C:]
    apply_mask = fired[:, None] & (mode == 1)
    features = jnp.where(apply_mask, val, soa.features)

    deleted_now = fired & _lookup(params.stage_delete, stage_c)
    active = soa.active & ~deleted_now

    # 3. rematch rows: fresh transitions + host-forced
    rematch = (fired & active) | (soa.rematch & active)

    # 4. match + weighted choice
    match = match_stages(params, features)
    w_over = _lookup(params.ov_w, soa.ovc)  # [N, S]
    weights = [
        jnp.where(w_over[:, s] != SENTINEL, w_over[:, s], params.w_static[s])
        for s in range(S)
    ]
    u = jax.random.uniform(k_choice, (N,))
    new_stage, any_match = _weighted_choice(match, weights, u)

    # 5. timers: delay + jitter for the chosen stage
    ns_c = jnp.clip(new_stage, 0, S - 1)
    ov_at = soa.ovc * S + ns_c
    d_over = _lookup(params.ov_d.reshape(-1), ov_at)
    j_over = _lookup(params.ov_j.reshape(-1), ov_at)
    d = jnp.where(d_over != SENTINEL, d_over, _lookup(params.d_static, ns_c))
    # deletionTimestamp deadline: duration = deadline - now
    has_dl = soa.del_ts != SENTINEL
    d = jnp.where(_lookup(params.d_from_del_ts, ns_c) & has_dl, soa.del_ts - now, d)

    j = jnp.where(j_over != SENTINEL, j_over, _lookup(params.j_static, ns_c))
    j = jnp.where(_lookup(params.j_from_del_ts, ns_c) & has_dl, soa.del_ts - now, j)
    has_j = _lookup(params.has_jitter, ns_c) & (j != SENTINEL)

    uj = jax.random.uniform(k_jitter, (N,))
    span = jnp.maximum(j - d, 0)
    jittered = d + (uj * span.astype(jnp.float32)).astype(jnp.int32)
    delay = jnp.where(has_j, jnp.where(j < d, j, jittered), d)
    delay = jnp.maximum(delay, 0)

    stage = jnp.where(rematch, new_stage, soa.stage)
    fire_at = jnp.where(
        rematch, jnp.where(any_match, now + delay, NEVER), soa.fire_at
    )
    # deleted/idle rows never fire
    fire_at = jnp.where(active, fire_at, NEVER)

    out = TickOut(
        fired=fired,
        fired_stage=jnp.where(fired, soa.stage, IDLE),
        deleted=deleted_now,
        fired_count=fired.sum().astype(jnp.int32),
    )
    new_soa = SoA(
        features=features,
        sig=soa.sig,
        ovc=soa.ovc,
        stage=stage,
        fire_at=fire_at,
        active=active,
        rematch=jnp.zeros_like(soa.rematch),
        del_ts=soa.del_ts,
        now=now,
        key=key,
    )
    return new_soa, out


tick = functools.partial(jax.jit, static_argnames=("dt_ms",), donate_argnums=(1,))(
    _tick_impl
)


def _run_ticks_collect_impl(
    params: TickParams, soa: SoA, count: jax.Array, dt_ms: int, num_ticks: int
) -> Tuple[SoA, jax.Array]:
    """Macro-tick: advance ``count`` ticks on device (a traced int of at
    most ``num_ticks``), collecting the per-tick fired stage as one
    compact [num_ticks, N] int8 array whose rows past ``count`` are IDLE.
    One dispatch + ONE device->host transfer replaces 4 blocking reads
    per tick: each round-trip stalls the host, and the tick itself is
    short.  The count is not part of the program, so a loop whose count
    follows its timing meets no new shape.  ``deleted`` is recomputed on
    host from stage_delete[stage]; sub-tick virtual times are
    now0 + (k+1)*dt."""

    def body(carry):
        k, soa, stages = carry
        soa, out = _tick_impl(params, soa, dt_ms)
        stages = jax.lax.dynamic_update_index_in_dim(
            stages, out.fired_stage.astype(jnp.int8), k, 0
        )
        return k + 1, soa, stages

    stages = jnp.full((num_ticks, soa.stage.shape[0]), IDLE, jnp.int8)
    _, soa, stages = jax.lax.while_loop(
        lambda carry: carry[0] < count, body, (jnp.int32(0), soa, stages)
    )
    return soa, stages


run_ticks_collect = functools.partial(
    jax.jit, static_argnames=("dt_ms", "num_ticks"), donate_argnums=(1,)
)(_run_ticks_collect_impl)


def _run_node_ticks_collect_impl(
    params: TickParams, soa: SoA, count: jax.Array, dt_ms: int, num_ticks: int
) -> Tuple[SoA, jax.Array]:
    """The Node player's macro-tick: ``_run_ticks_collect_impl`` under a
    jit name of its own (``jit__run_node_ticks_collect_impl``), so that a
    profile tells a Node tick from a Pod tick.  The two SoAs may differ
    in rows by a hundredfold (the Node player is sized to the nodes)."""
    return _run_ticks_collect_impl(params, soa, count, dt_ms, num_ticks)


run_node_ticks_collect = functools.partial(
    jax.jit, static_argnames=("dt_ms", "num_ticks"), donate_argnums=(1,)
)(_run_node_ticks_collect_impl)


def collect_program(kind: str) -> Tuple[str, Any]:
    """(name, jitted function) of the macro-tick that plays ``kind``."""
    if kind == "Node":
        return "run_node_ticks_collect", run_node_ticks_collect
    return "run_ticks_collect", run_ticks_collect


def _scatter_rows_impl(
    soa: SoA,
    rows: jax.Array,
    features: jax.Array,
    sig: jax.Array,
    ovc: jax.Array,
    stage: jax.Array,
    fire_at: jax.Array,
    active: jax.Array,
    rematch: jax.Array,
    del_ts: jax.Array,
) -> SoA:
    """Write a batch of host-mutated rows into the device SoA in place
    (donated).  This is the host->device half of the "only dirty rows
    cross the boundary" contract: admit/refresh/release used to force a
    full SoA re-upload (capacity x C ints both ways per firing tick at
    worst); now they scatter just the touched rows."""
    return soa._replace(
        features=soa.features.at[rows].set(features),
        sig=soa.sig.at[rows].set(sig),
        ovc=soa.ovc.at[rows].set(ovc),
        stage=soa.stage.at[rows].set(stage),
        fire_at=soa.fire_at.at[rows].set(fire_at),
        active=soa.active.at[rows].set(active),
        rematch=soa.rematch.at[rows].set(rematch),
        del_ts=soa.del_ts.at[rows].set(del_ts),
    )


scatter_rows = functools.partial(jax.jit, donate_argnums=(0,))(_scatter_rows_impl)


class LeaseLane(NamedTuple):
    """Device-resident lease-renewal timers: one slot per held node
    (SURVEY §7 step 5 / §2.9 lease-renewal lanes).  Replaces the host
    DelayingQueue cadence of the reference's NodeLeaseController
    syncWorkers (node_lease_controller.go:108-143) with a vectorized
    fire-time column ticked alongside the stage SoA; all due leases in
    a tick drain as ONE batched write-back."""

    fire_at: jax.Array  # [N] int32 virtual ms; NEVER = empty slot
    key: jax.Array  # PRNG key (renewal jitter)


def _lease_tick_impl(
    lane: LeaseLane, now: jax.Array, renew_ms: jax.Array, jitter_ms: jax.Array
) -> Tuple[LeaseLane, jax.Array, jax.Array]:
    """One pass: rows whose renewal is due, their lag, and rescheduled
    fire times (renew interval + one-sided jitter — the reference's
    duration/4 + 4% cadence, controller.go:245-249)."""
    key, k = jax.random.split(lane.key)
    due = lane.fire_at <= now
    u = jax.random.uniform(k, lane.fire_at.shape)
    nxt = now + renew_ms + (u * jitter_ms.astype(jnp.float32)).astype(jnp.int32)
    lag = jnp.where(due, now - lane.fire_at, 0)
    fire_at = jnp.where(due, nxt, lane.fire_at)
    return LeaseLane(fire_at=fire_at, key=key), due, lag


lease_tick = functools.partial(jax.jit, donate_argnums=(0,))(_lease_tick_impl)


def _run_ticks_impl(
    params: TickParams, soa: SoA, dt_ms: int, num_ticks: int
) -> Tuple[SoA, jax.Array]:
    """Device-side multi-tick loop (bench path): returns total fires.
    Host drain is skipped; use tick() when transitions must stream out."""

    def body(_, carry):
        soa, count = carry
        soa, out = _tick_impl(params, soa, dt_ms)
        return soa, count + out.fired_count

    soa, count = jax.lax.fori_loop(0, num_ticks, body, (soa, jnp.int32(0)))
    return soa, count


run_ticks = functools.partial(
    jax.jit, static_argnames=("dt_ms", "num_ticks"), donate_argnums=(1,)
)(_run_ticks_impl)
