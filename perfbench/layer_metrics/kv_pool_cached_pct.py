"""See ``kv_pool_live_pct``: one reader for both shares of the pool."""

from perfbench.layer_metrics.kv_pool_live_pct import read  # noqa: F401
