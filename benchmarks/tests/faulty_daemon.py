#!/usr/bin/env python3
"""The kwok daemon with its timed path broken underneath, for
``test_faults.py``: started by the harness in the daemon's place.  The
fault named by ``KWOK_BENCH_FAULT`` switches on when the file ``fault_on``
appears in ``KWOK_BENCH_CONTROL_DIR`` (the test drops it as the window
opens, so set-up is sound)."""

import os
import sys

FAULT = os.environ["KWOK_BENCH_FAULT"]
_FLAG = os.path.join(os.environ["KWOK_BENCH_CONTROL_DIR"], "fault_on")


def armed() -> bool:
    return os.path.exists(_FLAG)


def break_tick() -> None:
    import jax.numpy as jnp

    from kwok_tpu.engine.simulator import DeviceSimulator

    real = DeviceSimulator.tick_many_async

    def tick_many_async(self, dt_ms, n_ticks):
        if not armed() or len(self.cset.compiled) > 3:  # the pod player's stage set only
            return real(self, dt_ms, n_ticks)
        if FAULT == "frozen_state":
            # a step that returns its state unchanged: nothing advances, nothing fires
            self.to_device()
            return jnp.full((n_ticks, self.capacity), -1, jnp.int8), self._now_host
        stages, t0_ms = real(self, dt_ms, n_ticks)
        # half of the batch left out: every other row's firing never reaches the host
        return stages.at[:, 1::2].set(-1), t0_ms

    DeviceSimulator.tick_many_async = tick_many_async


def break_lease_tick() -> None:
    """The same faults in the lease lane, and a lane that runs at twice
    the configured pace."""
    import jax.numpy as jnp

    from kwok_tpu.controllers import device_lease

    real = device_lease.lease_tick

    def lease_tick(lane, now, renew_ms, jitter_ms):
        if not armed():
            return real(lane, now, renew_ms, jitter_ms)
        if FAULT == "lease_frozen_state":
            none = jnp.zeros(lane.fire_at.shape, bool)
            return lane, none, jnp.zeros(lane.fire_at.shape, jnp.int32)
        if FAULT == "lease_hasty":
            return real(lane, now, renew_ms // 2, jitter_ms)
        lane, due, lag = real(lane, now, renew_ms, jitter_ms)
        return lane, due.at[1::2].set(False), lag

    device_lease.lease_tick = lease_tick


def _alter(x) -> None:
    if isinstance(x, dict):
        for k, v in x.items():
            if k == "hostIP":
                x[k] = "10.9.9.9"
            else:
                _alter(v)
    elif isinstance(x, list):
        for v in x:
            _alter(v)


def break_answer() -> None:
    """An answer altered where it is produced: the status the daemon
    writes names another host address."""
    from kwok_tpu.cluster.client import ClusterClient

    real = ClusterClient.bulk

    def bulk(self, ops, as_user=None):
        ops = list(ops)
        if armed():
            _alter(ops)
        return real(self, ops, as_user=as_user)

    ClusterClient.bulk = bulk


if __name__ == "__main__":
    import threading

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                                    "harness"))
    import traced_daemon

    threading.Thread(target=traced_daemon._serve, daemon=True,
                     args=(os.environ["KWOK_BENCH_CONTROL_DIR"],)).start()
    {"frozen_state": break_tick, "half_batch": break_tick, "altered_answer": break_answer,
     "lease_frozen_state": break_lease_tick, "lease_half_batch": break_lease_tick,
     "lease_hasty": break_lease_tick}[FAULT]()
    from kwok_tpu.cmd.kwok import main

    sys.exit(main(sys.argv[1:]))
