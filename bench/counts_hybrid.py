"""Operations that the Granite 4.0-H hybrid's work needs, from its widths.

Model FLOPs, as in ``counts.py``: the multiplications the mathematics
requires (2 per multiply-add), not what an implementation computes.
Per token in the forward pass:

* a Mamba-2 + MLP layer: the in-projection ``2 D (2 H P + 2 N + H)``, the
  out-projection ``2 H P D``, the MLP ``6 D F``, and the SSD: ``C B^T``
  within the chunk (``2 Q N``, the group's, shared by the heads), its
  product with ``x`` (``2 H Q P``), the chunk states and the state to
  output (``2 H P N`` each);
* the attention + MLP layer: the projections ``2 D (2 D + 2 KV)``, the MLP,
  and ``q k^T`` and ``p v`` counted causally (``2 D S`` together);
* the head: ``2 D V``.

Training is three times the forward; recomputation does not count.
"""

from __future__ import annotations


def mamba_layer_flops_per_token(cfg: dict) -> int:
    D, F = cfg["hidden_size"], cfg["shared_intermediate_size"]
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    in_proj = 2 * D * (2 * H * P + 2 * N + H)
    return in_proj + 2 * H * P * D + 6 * D * F + ssd_flops_per_token(cfg)


def ssd_flops_per_token(cfg: dict) -> int:
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    Q = cfg["mamba_chunk_size"]
    return 2 * Q * N + 2 * H * Q * P + 2 * 2 * H * P * N


def attention_layer_flops_per_token(cfg: dict, seq: int) -> int:
    D, F = cfg["hidden_size"], cfg["shared_intermediate_size"]
    kv = cfg["num_key_value_heads"] * (D // cfg["num_attention_heads"])
    return 2 * D * (2 * D + 2 * kv) + 6 * D * F + 2 * D * seq


def head_flops_per_token(cfg: dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def granite4h_forward_flops_per_token(cfg: dict, seq: int) -> int:
    types = cfg["layer_types"][: cfg["num_hidden_layers"]]
    return (
        types.count("mamba") * mamba_layer_flops_per_token(cfg)
        + types.count("attention") * attention_layer_flops_per_token(cfg, seq)
        + head_flops_per_token(cfg)
    )


def granite4h_train_flops_per_token(cfg: dict, seq: int) -> int:
    """Forward and backward of the hybrid at rows of ``seq`` tokens."""
    return 3 * granite4h_forward_flops_per_token(cfg, seq)
