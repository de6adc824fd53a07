"""Causal grouped-query attention: the operations that scores and values
require, forward and backward, from shapes only."""

from __future__ import annotations


def _heads(cfg):
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    return h, cfg["num_key_value_heads"], hd


def causal_flops(cfg, seq: int, backward: bool = False) -> float:
    """All layers, one sequence.  Forward: q.k and p.v over the causal half,
    2 * 2 * h * hd * seq^2 / 2.  Backward: twice that (dq, dk, dv, dp)."""
    h, _, hd = _heads(cfg)
    fwd = 2.0 * h * hd * seq * seq
    return cfg["num_hidden_layers"] * fwd * (3.0 if backward else 1.0)
