"""Big-model-inference benchmark: load time + s/token for a disk-offloaded
model, the measurement behind the reference's published table
(``/root/reference/benchmarks/big_model_inference/README.md:27-37``; the
OPT-30B fp32 + disk row is 112.3 s load / 33.9 s/token on 2× Titan RTX).

The chip here can't hold OPT-30B, so the comparison is made on the
*bandwidth-normalised* metric the disk-offload regime is governed by:

    effective_stream_bandwidth = model_bytes_streamed_per_token / s_per_token

The reference row moves ~120 GB (fp32 30B) per generated token at
33.9 s/token → **3.54 GB/s** effective. Any configuration whose pipeline
sustains a higher effective bandwidth beats that row shape-for-shape; int8
quantized loading additionally divides the bytes per token by 4.

Run: ``python benchmarks/big_model_inference/bench_offload.py [--layers N]``
Prints one JSON line per configuration (fp32 disk, int8 disk).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def _drop_page_cache() -> bool:
    """Cold-cache the disk tier so s/token includes the real read (the
    reference's 120 GB model couldn't fit its 32 GB page cache either)."""
    try:
        subprocess.run(["sync"], check=True)
        with open("/proc/sys/vm/drop_caches", "w") as f:
            f.write("3\n")
        return True
    except (OSError, subprocess.CalledProcessError):
        return False


def _build_config(tag: str, quantize, layers: int, hidden: int):
    import time as _time

    from accelerate_tpu.big_modeling import dispatch_model
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM
    from accelerate_tpu.utils.quantization import BnbQuantizationConfig, quantize_model_params

    config = LlamaConfig(
        vocab_size=32000, hidden_size=hidden, intermediate_size=hidden * 4,
        num_hidden_layers=layers, num_attention_heads=16, num_key_value_heads=16,
        max_position_embeddings=256, remat=False,
    )
    t0 = _time.perf_counter()
    model = LlamaForCausalLM.from_config(config, seed=0)
    if quantize == "nf4":
        model = quantize_model_params(
            model,
            BnbQuantizationConfig(load_in_4bit=True, quantize_embeddings=True),
        )
    elif quantize:  # int8 (True kept for backward compat)
        model = quantize_model_params(
            model, BnbQuantizationConfig(quantize_embeddings=True)
        )
    offload_dir = tempfile.mkdtemp(prefix=f"bench_offload_{tag}_")
    dispatched = dispatch_model(model, {"": "disk"}, offload_dir=offload_dir)
    load_s = _time.perf_counter() - t0
    bytes_on_disk = sum(
        os.path.getsize(os.path.join(offload_dir, f))
        for f in os.listdir(offload_dir)
        if f.endswith(".dat")
    )
    return {
        "tag": tag, "dispatched": dispatched, "dir": offload_dir,
        "load_s": load_s, "bytes": bytes_on_disk, "per_token": [],
    }


def run_configs(config_list, layers: int, hidden: int, tokens: int) -> list[dict]:
    """Measure every configuration INTERLEAVED per token (fp32 token,
    int8 token, nf4 token, repeat): on a shared 1-core host any ambient
    CPU load then hits each configuration nearly equally instead of
    poisoning whichever ran while the neighbour was busy."""
    import numpy as np

    from accelerate_tpu.generation import generate

    # short prompt: the reference's s/token regime (OPT-30B decode,
    # README.md:36-37) is WEIGHT-MOVEMENT-bound — 120 GB per token
    # against a trivial prompt's matmuls. A long prompt on this 1-core
    # measurement host would instead measure prefill compute, which the
    # effective-stream metric deliberately excludes.
    ids = np.random.default_rng(0).integers(0, 32000, size=(1, 8)).astype(np.int32)
    built = [_build_config(tag, quantize, layers, hidden) for tag, quantize in config_list]
    try:
        for b in built:  # warmup: one token (compiles every segment fn)
            generate(b["dispatched"], ids, max_new_tokens=1)
        cold = True
        for _ in range(tokens):
            for b in built:
                # each measured token starts cold-cache so its disk read
                # is real (same input → identical shapes, compile cached)
                cold = _drop_page_cache() and cold
                t0 = time.perf_counter()
                generate(b["dispatched"], ids, max_new_tokens=1)
                # generate()'s full-forward path device_gets the logits
                # every token, so it host-syncs before returning and the
                # elapsed read measures real compute, not dispatch:
                # tpu-lint: ignore[TPU008] — generate() host-syncs internally
                b["per_token"].append(time.perf_counter() - t0)
        results = []
        for b in built:
            # median, not mean: one ambient-load spike shouldn't own a row
            s_per_token = float(np.median(b["per_token"]))
            bw = b["bytes"] / s_per_token
            results.append(
                {
                    "config": b["tag"],
                    "load_s": round(b["load_s"], 2),
                    "model_bytes": b["bytes"],
                    "cold_cache": cold,
                    "s_per_token": round(s_per_token, 4),
                    "effective_stream_gb_per_s": round(bw / 1e9, 3),
                    "reference_opt30b_row_gb_per_s": 3.54,
                    "beats_reference_row": bw / 1e9 > 3.54,
                }
            )
        return results
    finally:
        for b in built:
            shutil.rmtree(b["dir"], ignore_errors=True)


def run_config(tag: str, quantize, layers: int, hidden: int, tokens: int) -> dict:
    """Single-configuration entry kept for direct CLI use."""
    return run_configs([(tag, quantize)], layers, hidden, tokens)[0]


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--layers", type=int, default=12)
    parser.add_argument("--hidden", type=int, default=1024)
    parser.add_argument("--tokens", type=int, default=4)
    parser.add_argument(
        "--platform", default="cpu", choices=("cpu", "tpu"),
        help="cpu (default) measures the streaming pipeline against local "
        "disk+RAM; tpu uses the attached chip",
    )
    args = parser.parse_args()
    if args.platform == "cpu":
        # the platform is an argument here, so it is set in code, before
        # any backend starts
        import jax

        jax.config.update("jax_platforms", "cpu")

    for result in run_configs(
        [("fp32_disk", False), ("int8_disk", True), ("nf4_disk", "nf4")],
        args.layers, args.hidden, args.tokens,
    ):
        print(json.dumps(result))


if __name__ == "__main__":
    main()
