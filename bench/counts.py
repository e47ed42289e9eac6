"""Operations that the model's work needs, from its widths.

Model FLOPs count the matrix multiplications the mathematics requires
(2 per multiply-add), not what an implementation happens to compute:
padding and recomputation do not count.
"""

from __future__ import annotations


def _widths(cfg: dict) -> tuple[int, int, int]:
    return cfg["vocab_size"], cfg["hidden_size"], cfg["intermediate_size"]


def tanhlm_train_flops_per_token(cfg: dict) -> int:
    """Forward and backward of the tanh-MLP LM: 6 (D H + H D + D V)."""
    V, D, H = _widths(cfg)
    return 6 * (D * H + H * D + D * V)
