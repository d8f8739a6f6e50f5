"""The benchmark's harness: cluster driver, watching client, metric
arithmetic, prom-text reader, trace control and the check of
``correct``.  Nothing here imports ``jax``: the only process of a run
that holds the chip is the kwok daemon."""
