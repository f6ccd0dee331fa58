"""Operations and bytes the algorithm needs, computed from shapes.

These are the yardstick's: they follow the configuration and the equations,
never how a kernel tiles the work.
"""
from __future__ import annotations


def llama_block_params(d: int, heads: int, kv_heads: int, head_dim: int,
                       d_ff: int) -> dict:
    """Parameters of one pre-norm decoder block with grouped-query attention
    and a gated MLP, by kind."""
    return {"attn": d * heads * head_dim + 2 * d * kv_heads * head_dim
            + heads * head_dim * d,
            "mlp": 3 * d * d_ff,
            "norm": 2 * d}


def lm_param_count(m: dict) -> int:
    """All parameters of a decoder-only LM with a tied output head."""
    blk = llama_block_params(m["hidden_size"], m["num_attention_heads"],
                             m["num_key_value_heads"], m["head_dim"],
                             m["intermediate_size"])
    embed = m["vocab_size"] * m["hidden_size"]
    if not m.get("tie_word_embeddings", True):
        embed *= 2
    return (embed + m["num_hidden_layers"] * sum(blk.values())
            + m["hidden_size"])


def lm_train_flops_per_token(m: dict, seq: int) -> float:
    """Model FLOPs of one trained token: every matmul forward (2 per
    multiply-add) and backward (twice the forward), with the output head and
    the attention scores and values over the whole sequence; no recompute."""
    blk = llama_block_params(m["hidden_size"], m["num_attention_heads"],
                             m["num_key_value_heads"], m["head_dim"],
                             m["intermediate_size"])
    matmul = (m["num_hidden_layers"] * (blk["attn"] + blk["mlp"])
              + m["vocab_size"] * m["hidden_size"])
    attn = (m["num_hidden_layers"] * 2 * 2 * seq * m["num_attention_heads"]
            * m["head_dim"])
    return 3.0 * (2.0 * matmul + attn)


def eq4_panel_work(k: int, u: int, p: int) -> tuple:
    """Eq. 4 over one round: k mixed rows from u source rows of p f32
    parameters.  Returns (FLOPs, bytes read and written)."""
    return 2.0 * k * u * p, 4.0 * (u + k) * p


def roofline_s(flops: float, nbytes: float, peaks: dict) -> tuple:
    """The least time the chip could take, and which bound sets it."""
    t_c = flops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
