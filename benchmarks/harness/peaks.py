"""The table of peaks and the functions that count what a served LM needs (a
training configuration's counts come from its reference module:
``train_flops_per_sample``, ``train_min_bytes_per_step``).

Peaks of one chip, keyed by a substring of ``device_kind``. A device that is
not in the table is an error, never a default: a chip measured against another
chip's peak is a wrong number.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s a
chip. (Copied from ``distributed_pytorch_tpu/obs/goodput.py`` and
``obs/roofline.py``, which keep the same figures.)
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
    "v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    kind = device_kind.lower()
    for key, row in PEAKS.items():
        if key in kind:
            return row
    raise KeyError(
        f"no peaks recorded for device kind {device_kind!r}; add a sourced "
        f"row to benchmarks/harness/peaks.py (known: {sorted(PEAKS)})")


# ------------------------------------------------------------- transformer


def lm_matmul_params(cfg: dict) -> int:
    """Parameters every token multiplies against: all layers' matrices plus
    the (tied) output head. The embedding lookup is a gather, not a matmul."""
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    per_layer = d * d + 2 * d * kv + d * d + 2 * d * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * per_layer


def lm_forward_flops(cfg: dict, new_tokens: int, context_tokens: float,
                     logits_rows: int) -> float:
    """FLOPs to push ``new_tokens`` positions through the model when they
    attend to ``context_tokens`` keys in all (summed over the new positions),
    and ``logits_rows`` of them go through the output head."""
    d = cfg["hidden_size"]
    dense = 2.0 * lm_matmul_params(cfg) * new_tokens
    attn = 4.0 * cfg["num_hidden_layers"] * d * context_tokens
    head = 2.0 * d * cfg["vocab_size"] * logits_rows
    return dense + attn + head


def lm_weight_bytes(cfg: dict, bytes_per_param: int = 2) -> float:
    d = cfg["hidden_size"]
    return bytes_per_param * (
        lm_matmul_params(cfg) + d * cfg["vocab_size"])


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> float:
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return (2.0 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * hd * bytes_per_value)


def roofline_seconds(flops: float, nbytes: float, device_kind: str) -> tuple:
    """``(seconds, bound)``: the least time the chip could take, and which
    of the two peaks sets it."""
    p = peaks_for(device_kind)
    t_flops = flops / p["bf16_flops"]
    t_bytes = nbytes / p["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
