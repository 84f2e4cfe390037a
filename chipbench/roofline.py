"""Peaks of the chip and the work of the model, counted from its shapes.

The peak table is keyed by JAX's ``device_kind``; a device missing from
it is an error, never a default.  Source of the v5e row: Google Cloud
documentation, "TPU v5e" (per chip: 197 TFLOP/s bf16, 393 TOP/s int8,
16 GB of HBM at 819 GB/s).

The work is the algorithm's, from the model's shapes and each call's row
count ``M`` as the engine shapes it: never from a kernel's blocking,
padding or even/odd split, so a kernel written anew is read against the
same work.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"device kind {device_kind!r} is not in the peak "
                       f"table ({sorted(PEAKS)})")
    return PEAKS[device_kind]


def head_dim(model: dict) -> int:
    return model.get("head_dim") or model["d_model"] // model["n_heads"]


def layer_linears(model: dict) -> list[tuple[str, int, int]]:
    """(name, K, N) of every matmul weight in one decoder layer."""
    d, hd = model["d_model"], head_dim(model)
    q, kv = model["n_heads"] * hd, model["n_kv_heads"] * hd
    ff = model["d_ff"]
    out = [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wo", q, d)]
    if model.get("mlp_variant", "swiglu") == "gelu":
        out += [("up", d, ff), ("down", ff, d)]
    else:
        out += [("up", d, ff), ("gate", d, ff), ("down", ff, d)]
    return out


def int4_linear_cost(m: int, k: int, n: int) -> tuple[int, int]:
    """(operations, bytes) of one int8-activation x int4-weight linear:
    2·M·K·N operations; K·N/2 bytes of nibbles, 4·N of f32 scales, M·K of
    int8 activations in and 4·M·N of int32 results out."""
    return 2 * m * k * n, k * n // 2 + 4 * n + m * k + 4 * m * n


def min_time_s(ops: float, nbytes: float, peak_ops: float,
               bytes_per_s: float) -> float:
    """The least time the chip could take: the larger of its two bounds."""
    return max(ops / peak_ops, nbytes / bytes_per_s)


def forward_min_time_s(model: dict, m: int, peak: dict,
                       lm_head: bool) -> float:
    """Least time of the packed linears of one forward over ``m`` rows:
    every layer's linears, plus the LM head when the call runs it."""
    shapes = layer_linears(model) * model["n_layers"]
    if lm_head:
        shapes = shapes + [("lm_head", model["d_model"], model["vocab_size"])]
    return sum(
        min_time_s(*int4_linear_cost(m, k, n), peak["int8_ops"],
                   peak["hbm_bytes_per_s"])
        for _, k, n in shapes
    )


def matmul_params(model: dict) -> tuple[int, int]:
    """(parameters of the decoder layers' matmuls, of the LM head)."""
    per_layer = sum(k * n for _, k, n in layer_linears(model))
    return per_layer * model["n_layers"], model["d_model"] * model["vocab_size"]


def attended(model: dict, pos: int) -> int:
    """Keys a query at position ``pos`` attends to (causal, windowed)."""
    w = model.get("sliding_window")
    return min(pos + 1, w) if w else pos + 1


def position_flops(model: dict, pos: int) -> int:
    """Model FLOPs of one token position through the decoder layers:
    2 per matmul parameter plus QK and PV over the attended keys."""
    layers, _ = matmul_params(model)
    attn = 4 * model["n_heads"] * head_dim(model) * attended(model, pos)
    return 2 * layers + attn * model["n_layers"]


def prompt_flops(model: dict, length: int) -> int:
    """FLOPs of prefilling ``length`` prompt positions (no LM head)."""
    layers, _ = matmul_params(model)
    w = model.get("sliding_window") or length
    att = sum(min(p + 1, w) for p in range(length))
    return (2 * layers * length
            + 4 * model["n_heads"] * head_dim(model) * att * model["n_layers"])


def lm_head_flops(model: dict) -> int:
    return 2 * matmul_params(model)[1]
