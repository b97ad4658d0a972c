"""Operations and bytes an algorithm needs, from shapes alone. The
yardstick's arithmetic: nothing here reads the program or a trace."""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The table's row for ``device_kind``; a device that is not in the
    table is an error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"device kind {device_kind!r} is not in perfbench/peaks.json "
            f"(known: {[k for k in table if not k.startswith('_')]})"
        )
    return table[device_kind]


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_params(cfg: dict) -> int:
    """Matrix parameters of one block (norm vectors left out)."""
    h, ff = cfg["hidden_size"], cfg["intermediate_size"]
    hd = head_dim(cfg)
    q = h * cfg["num_attention_heads"] * hd
    kv = h * cfg["num_key_value_heads"] * hd
    return 2 * q + 2 * kv + 3 * h * ff


def non_embedding_params(cfg: dict) -> int:
    """Block matrices plus the output head (a matrix product per token);
    the embedding is a gather and costs no product."""
    return cfg["num_hidden_layers"] * layer_params(cfg) + cfg["hidden_size"] * cfg["vocab_size"]


def causal_attention_flops(cfg: dict, seq: int, backward: bool) -> float:
    """QK^T and PV of ONE sequence in ONE layer, causal (half the square).
    Forward 2 products of 2*s*s*hd*heads/2; backward twice the forward
    (no recomputation counted)."""
    fwd = 2 * 2 * seq * seq * head_dim(cfg) * cfg["num_attention_heads"] / 2
    return fwd * (3 if backward else 1)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """6 x non-embedding parameters + causal attention, forward and
    backward, recomputation not counted."""
    attn = cfg["num_hidden_layers"] * causal_attention_flops(cfg, seq, backward=True) / seq
    return 6.0 * non_embedding_params(cfg) + attn


def flash_attention_cost(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """All three flash kernels (fwd, bwd dq, bwd dkv) of ONE layer over a
    local batch: FLOPs as above; bytes the least they must move (q, k, v, o
    read/written once forward; q, k, v, o, do read and dq, dk, dv written
    backward)."""
    nh, nkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    q_b = batch * seq * nh * hd * itemsize
    kv_b = batch * seq * nkv * hd * itemsize
    fwd_bytes = 2 * q_b + 2 * kv_b
    bwd_bytes = 4 * q_b + 4 * kv_b
    return {
        "flops": batch * causal_attention_flops(cfg, seq, backward=True),
        "bytes": fwd_bytes + bwd_bytes,
    }


def paged_attention_cost(cfg: dict, context_lens, q_lens, kv_itemsize: int = 2,
                         act_itemsize: int = 2) -> dict:
    """ONE layer's paged-attention call: each row ``i`` has ``q_lens[i]``
    queries against ``context_lens[i]`` valid cached positions. Bytes: the
    valid K and V once, q read and the output written. FLOPs: QK^T and PV
    over valid positions."""
    nh, nkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    flops = 0.0
    nbytes = 0.0
    for ctx, ql in zip(context_lens, q_lens):
        flops += 2 * 2 * ql * ctx * hd * nh
        nbytes += 2 * ctx * nkv * hd * kv_itemsize + 2 * ql * nh * hd * act_itemsize
    return {"flops": flops, "bytes": nbytes}


def roofline(cost: dict, peak: dict) -> dict:
    """Least seconds the chip could take and which bound sets it."""
    t_flops = cost["flops"] / peak["bf16_flops_per_s"]
    t_bytes = cost["bytes"] / peak["hbm_bytes_per_s"]
    return {
        "least_s": max(t_flops, t_bytes),
        "bound": "compute" if t_flops >= t_bytes else "memory",
    }
