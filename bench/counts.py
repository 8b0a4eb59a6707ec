"""Operations and bytes the algorithms need, from their shapes.

These are the numerators of the roofline shares and of ``batch_mfu``:
what the work requires, not what a program happens to do.  A program
that does more (attends over its whole cache, copies a table it could
update in place) reads a lower share.

Dense decoder (Qwen3 layout; sizes from a configuration file):

    per-layer matrix weights  P = D*H*dh + 2*D*K*dh + H*dh*D + 3*D*F
    prefill of B rows of S tokens
        flops = 2*B*S*L*P                       (projections and MLP)
              + 4*B*L*H*dh * S*(S+1)/2          (causal scores and values)
              + 2*B*D*V                         (logits of the last token)
        bytes = all weights once + K and V written for B*S tokens
    decode step of B rows at position p (p tokens already cached)
        flops = 2*B*L*P + 4*B*L*H*dh*(p+1) + 2*B*D*V
        bytes = all weights once + K and V read for B*(p+1) tokens

Weights are bf16 (2 bytes), as are K and V.  Norm weights count as
weights; the embedding counts once (it is also the output head).

Hash map (one round of n operations; node fields int32 key, val, nxt
and bool live; int32 bucket heads):

    a chain walk reads the bucket head (4) and, per node visited, its
    key and link (8); ``visits`` is half the mean chain length measured
    before the window (a hit stops half way on average, so this is a
    lower bound)
    lookup: per op  4 (key in) + 4 + 8*visits + 1 (live) + 4 (val)
                    + 1 (found out) + 4 (val out)
    update: per op  12 (op, key, val in) + 4 + 8*visits + 1 + 1 (ok out)
            plus    17 per fresh node (key, val, nxt, live, head),
                    5 per other successful insert (val, live),
                    1 per successful delete (live)
"""
from __future__ import annotations

BF16 = 2


def _dims(cfg: dict):
    return (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["vocab_size"], cfg["num_hidden_layers"])


def layer_matrix_params(cfg: dict) -> int:
    D, F, H, K, dh, _, _ = _dims(cfg)
    return D * H * dh + 2 * D * K * dh + H * dh * D + 3 * D * F


def weight_bytes(cfg: dict) -> int:
    D, F, H, K, dh, V, L = _dims(cfg)
    per_layer = layer_matrix_params(cfg) + 2 * D + 2 * dh
    return BF16 * (L * per_layer + V * D + D)


def _kv_bytes_per_token(cfg: dict) -> int:
    _, _, _, K, dh, _, L = _dims(cfg)
    return 2 * L * K * dh * BF16


def prefill_flops(cfg: dict, B: int, S: int) -> float:
    D, F, H, K, dh, V, L = _dims(cfg)
    return (2.0 * B * S * L * layer_matrix_params(cfg)
            + 4.0 * B * L * H * dh * S * (S + 1) / 2
            + 2.0 * B * D * V)


def prefill_bytes(cfg: dict, B: int, S: int) -> float:
    return float(weight_bytes(cfg) + B * S * _kv_bytes_per_token(cfg))


def decode_flops(cfg: dict, B: int, p: int) -> float:
    D, F, H, K, dh, V, L = _dims(cfg)
    return (2.0 * B * L * layer_matrix_params(cfg)
            + 4.0 * B * L * H * dh * (p + 1) + 2.0 * B * D * V)


def decode_bytes(cfg: dict, B: int, p: int) -> float:
    return float(weight_bytes(cfg) + B * (p + 1) * _kv_bytes_per_token(cfg))


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def lookup_bytes(n: int, visits: float) -> float:
    return n * (4 + 4 + 8 * visits + 1 + 4 + 1 + 4)


def update_bytes(n: int, visits: float, fresh: int, ins_ok: int,
                 del_ok: int) -> float:
    """``ins_ok`` counts every successful insert, fresh ones included."""
    return (n * (12 + 4 + 8 * visits + 1 + 1) + 17 * fresh
            + 5 * (ins_ok - fresh) + del_ok)
