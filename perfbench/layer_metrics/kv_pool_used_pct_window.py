"""``kv.pool_used_pct.window``: see ``kv_pool_used_pct_full.py``, whose reader serves both."""

from perfbench.layer_metrics.kv_pool_used_pct_full import read  # noqa: F401
