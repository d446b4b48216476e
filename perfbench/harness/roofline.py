"""The table of peaks and the byte/operation counts of a decode tick and of
the int8 kernel's call sites. Kept with the benchmark so that no later PR
can move the yardstick. Every count is computed from the PUBLISHED config
keys in ``configs/<name>.json`` — nothing is read from the program."""

from __future__ import annotations

from typing import Dict, List, Tuple

# Published peaks of one chip, keyed by jax's ``device_kind``. Source:
# Google Cloud documentation, "TPU v5e" system architecture (197 TFLOP/s
# bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip). A device that is
# not in the table is an error, never a default.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops": 197e12, "int8_ops": 393e12,
                "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device_kind {device_kind!r}: add it "
                       "to perfbench/harness/roofline.py with its source")
    return PEAKS[device_kind]


def shape_of(cfg: dict) -> dict:
    """The sizes the counts need, from a published HF config (gpt2 or a
    llama-family one such as qwen2)."""
    if cfg["model_type"] == "gpt2":
        d, h = cfg["n_embd"], cfg["n_head"]
        return {"layers": cfg["n_layer"], "hidden": d, "heads": h,
                "kv_heads": h, "head_dim": d // h, "vocab": cfg["vocab_size"],
                "ffn": cfg.get("n_inner") or 4 * d, "gated": False,
                "tied": True}
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"layers": cfg["num_hidden_layers"], "hidden": d, "heads": h,
            "kv_heads": cfg["num_key_value_heads"], "head_dim": d // h,
            "vocab": cfg["vocab_size"], "ffn": cfg["intermediate_size"],
            "gated": True, "tied": bool(cfg.get("tie_word_embeddings"))}


def matmul_sites(cfg: dict) -> List[Tuple[str, int, int]]:
    """(site, K, N) of one layer's weight matmuls, q|k|v and gate|up each
    as the one fused product the engine runs."""
    s = shape_of(cfg)
    d, dh = s["hidden"], s["head_dim"]
    qkv = (s["heads"] + 2 * s["kv_heads"]) * dh
    sites = [("wqkv", d, qkv), ("wo", s["heads"] * dh, d)]
    if s["gated"]:
        sites += [("wgu", d, 2 * s["ffn"]), ("wd", s["ffn"], d)]
    else:
        sites += [("wi", d, s["ffn"]), ("wo_mlp", s["ffn"], d)]
    return sites


def tick_cost(cfg: dict, *, layers: int, sessions: float, kv_rows: float,
              weight_bytes: float, act_bytes: int = 2) -> Dict[str, float]:
    """Bytes and operations ONE decode tick needs: every layer weight once
    (at ``weight_bytes`` per element, +4 bytes per output column of scale
    when quantised), the head once, the K and V rows IN USE of the
    sessions in the tick, and the matmul + attention arithmetic."""
    s = shape_of(cfg)
    per_layer = sum(k * n for _, k, n in matmul_sites(cfg))
    scales = (sum(n for _, _, n in matmul_sites(cfg)) * 4
              if weight_bytes < act_bytes else 0)
    head = s["vocab"] * s["hidden"]
    kv_elems = sessions * kv_rows * 2 * layers * s["kv_heads"] * s["head_dim"]
    weights = layers * (per_layer * weight_bytes + scales)
    nbytes = weights + head * act_bytes + kv_elems * act_bytes
    flops = (2.0 * sessions * (layers * per_layer + head)
             + 4.0 * sessions * kv_rows * s["heads"] * s["head_dim"] * layers)
    return {"bytes": nbytes, "flops": flops, "weight_bytes": weights,
            "head_bytes": head * act_bytes, "kv_bytes": kv_elems * act_bytes}


def roofline_s(cost: Dict[str, float], device_kind: str,
               int8_operands: bool = False) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    p = peaks(device_kind)
    t_mem = cost["bytes"] / p["hbm_bytes_per_s"]
    t_ops = cost["flops"] / (p["int8_ops"] if int8_operands
                             else p["bf16_flops"])
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")


def int8_site_cost(m: int, k: int, n: int, act_bytes: int = 2
                   ) -> Dict[str, float]:
    """One call of the int8 kernel at x[m,k] @ q[k,n]: the int8 weight
    once, its float32 scale row, x in and y out in the activation type."""
    return {"bytes": k * n + 4 * n + m * k * act_bytes + m * n * act_bytes,
            "flops": 2.0 * m * k * n}
