"""Kernels: the latent-attention kernel's share of the device's busy time
(its reader is the roofline share's)."""

from perfbench.layer_metrics.kernel_latent_attention_roofline_pct import read  # noqa: F401
