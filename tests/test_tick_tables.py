"""The tick reads its stage tables by selects where they are small and by
gathers where they are not, and keeps one effect row where every
signature lowers every stage alike: each way gives the same ticks, bit for
bit, as the plain gather over one effect row a signature."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from kwok_tpu.engine.compiler import SENTINEL
from kwok_tpu.engine.simulator import DeviceSimulator
from kwok_tpu.ops import tick
from kwok_tpu.stages import load_builtin

TICKS = 5


def pod(name, node, finalizer=False):
    meta = {"name": name, "namespace": "default"}
    if finalizer:
        meta["finalizers"] = ["kwok.x-k8s.io/fake"]
    return {"apiVersion": "v1", "kind": "Pod", "metadata": meta,
            "spec": {"nodeName": node, "containers": [{"name": "app", "image": "img"}]}}


def random_case(rng, n, stages, cols, signatures, classes, uniform):
    """Random stage tables and a random SoA of ``n`` rows."""

    def sentinel(a, share):
        return np.where(rng.random(a.shape) < share, SENTINEL, a).astype(np.int32)

    def ints(lo, hi, shape):
        return rng.integers(lo, hi, shape, dtype=np.int64).astype(np.int32)

    mode = (rng.random((signatures, stages, cols)) < 0.5).astype(np.int32)
    val = ints(0, 2**31 - 1, (signatures, stages, cols))
    if uniform:
        mode[:], val[:] = mode[:1], val[:1]
    k = 3
    params = tick.TickParams(
        cond_col=jnp.asarray(ints(0, cols, (stages, k))),
        cond_mask=jnp.asarray(ints(1, 2**31 - 1, (stages, k))),
        cond_neg=jnp.asarray(rng.random((stages, k)) < 0.3),
        cond_valid=jnp.asarray(rng.random((stages, k)) < 0.6),
        w_static=jnp.asarray(ints(0, 4, stages)),
        d_static=jnp.asarray(ints(0, 500, stages)),
        j_static=jnp.asarray(sentinel(ints(0, 900, stages), 0.5)),
        has_jitter=jnp.asarray(rng.random(stages) < 0.5),
        d_from_del_ts=jnp.asarray(rng.random(stages) < 0.3),
        j_from_del_ts=jnp.asarray(rng.random(stages) < 0.3),
        stage_delete=jnp.asarray(rng.random(stages) < 0.3),
        eff_mode=jnp.asarray(mode),
        eff_val=jnp.asarray(val),
        ov_w=jnp.asarray(sentinel(ints(0, 4, (classes, stages)), 0.7)),
        ov_d=jnp.asarray(sentinel(ints(0, 500, (classes, stages)), 0.7)),
        ov_j=jnp.asarray(sentinel(ints(0, 900, (classes, stages)), 0.7)),
    )
    soa = tick.SoA(
        features=jnp.asarray(ints(-2**31, 2**31 - 1, (n, cols))),
        sig=jnp.asarray(ints(0, signatures, n)),
        ovc=jnp.asarray(ints(0, classes, n)),
        stage=jnp.asarray(ints(-1, stages, n)),
        fire_at=jnp.asarray(ints(0, 600, n)),
        active=jnp.asarray(rng.random(n) < 0.8),
        rematch=jnp.asarray(rng.random(n) < 0.3),
        del_ts=jnp.asarray(sentinel(ints(0, 2000, n), 0.5)),
        now=jnp.int32(100),
        key=jax.random.PRNGKey(int(rng.integers(0, 2**31))),
    )
    return params, soa


def reference_tick(params, soa, dt_ms=100):
    """The tick as plain gathers: an effect row a signature, every table
    indexed, the choice by cumulative sum and argmax."""
    S = params.w_static.shape[0]
    now = soa.now + jnp.int32(dt_ms)
    key, k_choice, k_jitter = jax.random.split(soa.key, 3)
    n = soa.features.shape[0]
    fired = soa.active & (soa.stage >= 0) & (soa.fire_at <= now)
    stage_c = jnp.clip(soa.stage, 0, S - 1)
    sig = soa.sig if params.eff_mode.shape[0] > 1 else jnp.zeros_like(soa.sig)
    mode = params.eff_mode[sig, stage_c]
    val = params.eff_val[sig, stage_c]
    features = jnp.where(fired[:, None] & (mode == 1), val, soa.features)
    deleted = fired & params.stage_delete[stage_c]
    active = soa.active & ~deleted
    rematch = (fired & active) | (soa.rematch & active)
    match = []
    for s in range(S):
        m = jnp.ones(n, dtype=bool)
        for k in range(params.cond_col.shape[1]):
            test = (features[:, params.cond_col[s, k]] & params.cond_mask[s, k]) != 0
            test = jnp.where(params.cond_neg[s, k], ~test, test)
            m = m & jnp.where(params.cond_valid[s, k], test, True)
        match.append(m)
    match = jnp.stack(match, axis=1)
    w_over = params.ov_w[soa.ovc]
    weights = jnp.where(w_over != SENTINEL, w_over, params.w_static[None, :])
    wm = jnp.where(match & (weights > 0), weights, 0)
    probs = jnp.where((wm.sum(1) > 0)[:, None], wm, match.astype(jnp.int32))
    ptot = probs.sum(1)
    any_match = ptot > 0
    u = jax.random.uniform(k_choice, (n,))
    r = jnp.minimum((u * ptot.astype(jnp.float32)).astype(jnp.int32), jnp.maximum(ptot - 1, 0))
    choice = jnp.argmax(jnp.cumsum(probs, 1) > r[:, None], 1).astype(jnp.int32)
    new_stage = jnp.where(any_match, choice, -1)
    ns_c = jnp.clip(new_stage, 0, S - 1)
    d_over = jnp.take_along_axis(params.ov_d[soa.ovc], ns_c[:, None], 1)[:, 0]
    j_over = jnp.take_along_axis(params.ov_j[soa.ovc], ns_c[:, None], 1)[:, 0]
    has_dl = soa.del_ts != SENTINEL
    d = jnp.where(d_over != SENTINEL, d_over, params.d_static[ns_c])
    d = jnp.where(params.d_from_del_ts[ns_c] & has_dl, soa.del_ts - now, d)
    j = jnp.where(j_over != SENTINEL, j_over, params.j_static[ns_c])
    j = jnp.where(params.j_from_del_ts[ns_c] & has_dl, soa.del_ts - now, j)
    has_j = params.has_jitter[ns_c] & (j != SENTINEL)
    uj = jax.random.uniform(k_jitter, (n,))
    jittered = d + (uj * jnp.maximum(j - d, 0).astype(jnp.float32)).astype(jnp.int32)
    delay = jnp.maximum(jnp.where(has_j, jnp.where(j < d, j, jittered), d), 0)
    stage = jnp.where(rematch, new_stage, soa.stage)
    fire_at = jnp.where(rematch, jnp.where(any_match, now + delay, tick.NEVER), soa.fire_at)
    fire_at = jnp.where(active, fire_at, tick.NEVER)
    out = tick.TickOut(fired=fired, fired_stage=jnp.where(fired, soa.stage, -1),
                       deleted=deleted, fired_count=fired.sum().astype(jnp.int32))
    return tick.SoA(features, soa.sig, soa.ovc, stage, fire_at, active,
                    jnp.zeros_like(soa.rematch), soa.del_ts, now, key), out


def play(params, soa, ticks=TICKS, impl=None):
    """The states and outputs of ``ticks`` ticks, traced anew (so that a
    patched ``_SELECT_MAX`` is what the trace reads)."""
    impl = impl or (lambda p, s: tick._tick_impl(p, s, 100))
    step = jax.jit(impl)
    seen = []
    for _ in range(ticks):
        soa, out = step(params, soa)
        seen.append([np.asarray(x) for x in (*soa, *out)])
    return seen


def assert_same(a, b):
    assert len(a) == len(b)
    for ta, tb in zip(a, b):
        for xa, xb in zip(ta, tb):
            assert xa.shape == xb.shape and (xa == xb).all()


@pytest.mark.parametrize("entries", [1, 3, 32, 33, 200])
@pytest.mark.parametrize("width", [None, 5])
def test_a_lookup_reads_what_indexing_reads(entries, width):
    rng = np.random.default_rng(entries)
    shape = (entries,) if width is None else (entries, width)
    table = jnp.asarray(rng.integers(-50, 50, shape).astype(np.int32))
    idx = jnp.asarray(rng.integers(0, entries, 777).astype(np.int32))
    got = jax.jit(tick._lookup)(table, idx)
    assert (np.asarray(got) == np.asarray(table)[np.asarray(idx)]).all()


@pytest.mark.parametrize("stages,cols,signatures,classes,uniform", [
    (3, 4, 1, 1, True),      # pod-fast's shape
    (3, 4, 60, 1, False),    # an effect gather over 180 rows
    (3, 2, 5, 2, False),     # 15 effect rows: selects by signature and stage
    (9, 11, 40, 3, False),   # pod-general's width: the effect rows a gather
    (40, 6, 2, 1, False),    # more stages than a select takes
    (5, 3, 2, 9, False),     # 45 override rows: delays and jitters a gather
])
def test_selects_and_gathers_tick_alike(monkeypatch, stages, cols, signatures, classes,
                                        uniform):
    rng = np.random.default_rng(stages * 1000 + signatures)
    params, soa = random_case(rng, 1500, stages, cols, signatures, classes, uniform)
    selected = play(params, soa)
    assert_same(selected, play(params, soa, impl=reference_tick))
    monkeypatch.setattr(tick, "_SELECT_MAX", 0)  # every table read by a gather
    assert_same(selected, play(params, soa))


def test_weighted_choice_is_the_first_stage_past_the_draw():
    """The unrolled choice against the cumulative sum and argmax it stands for."""
    rng = np.random.default_rng(7)
    n, s = 4000, 6
    match = rng.random((n, s)) < 0.4
    weights = rng.integers(0, 3, (n, s)).astype(np.int32)
    u = rng.random(n).astype(np.float32)
    got, any_match = tick._weighted_choice(
        [jnp.asarray(match[:, i]) for i in range(s)],
        [jnp.asarray(weights[:, i]) for i in range(s)], jnp.asarray(u))
    wm = np.where(match & (weights > 0), weights, 0)
    probs = np.where((wm.sum(1) > 0)[:, None], wm, match.astype(np.int32))
    ptot = probs.sum(1)
    r = np.minimum((u * ptot.astype(np.float32)).astype(np.int32), np.maximum(ptot - 1, 0))
    want = np.where(ptot > 0, np.argmax(np.cumsum(probs, 1) > r[:, None], 1), -1)
    assert (np.asarray(any_match) == (ptot > 0)).all()
    assert (np.asarray(got) == want).all()


def test_one_effect_row_serves_every_signature_and_no_signature_grows_it():
    """Pods on 40 nodes are 40 signatures whose pod-fast effects are the
    same: the tick gets one effect row, a pod on a new node leaves the
    tables' shapes as they were, and the ticks equal those over the table
    of a row a signature."""
    sim = DeviceSimulator(load_builtin("pod-fast"), capacity=512, kind="Pod")
    for i in range(200):
        sim.admit(pod(f"p-{i}", f"node-{i % 40}", finalizer=i % 3 == 0))
    params, soa = sim.to_device()
    mode, val = sim.cset.effect_tables()
    assert mode.shape[0] == 40 and params.eff_mode.shape[0] == 1
    full = params._replace(eff_mode=jnp.asarray(mode), eff_val=jnp.asarray(val))
    copy = jax.tree.map(jnp.copy, soa)
    assert_same(play(params, soa, 8), play(full, copy, 8, impl=reference_tick))

    shapes = (params.eff_mode.shape, params.ov_w.shape)
    sim.admit(pod("p-new", "node-new"))
    params, _ = sim.to_device()
    assert sim.cset.effect_tables()[0].shape[0] == 41
    assert (params.eff_mode.shape, params.ov_w.shape) == shapes
