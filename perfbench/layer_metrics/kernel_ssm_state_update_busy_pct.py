"""See ``kernel_ssm_state_update_roofline_pct``: one reader for both shares
of the kernel."""

from perfbench.layer_metrics.kernel_ssm_state_update_roofline_pct import read  # noqa: F401
