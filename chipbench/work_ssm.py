"""Operations and bytes of a Mamba-2 model, computed from shapes.

These are the yardstick's: they follow the configuration and the SSD
algorithm (arXiv 2405.21060, one B/C group), never how a kernel tiles the
work.  ``m`` is a configuration's ``model`` block.
"""
from __future__ import annotations


def dims(m: dict) -> tuple:
    """(d_model, d_inner, heads, head_dim, state, conv width, chunk)."""
    d, p = m["hidden_size"], m["head_dim"]
    d_in = m["expand"] * d
    return (d, d_in, d_in // p, p, m["state_size"], m["conv_kernel"],
            m["chunk_size"])


def ssm_block_params(m: dict) -> dict:
    """Parameters of one pre-norm Mamba-2 block, by kind."""
    d, d_in, h, _, n, w, _ = dims(m)
    conv_dim = d_in + 2 * n
    return {"in_proj": d * (2 * d_in + 2 * n + h),
            "conv": w * conv_dim + conv_dim,
            "heads": 3 * h,                    # A_log, D, dt_bias
            "out_proj": d_in * d,
            "norm": d_in + d}                  # gated norm, pre-norm


def ssm_param_count(m: dict) -> int:
    """All parameters of the model, with a tied head and the embedding
    table at the rows the configuration holds (``embedding_rows``)."""
    rows = m.get("embedding_rows", m["vocab_size"])
    return (rows * m["hidden_size"]
            + m["num_hidden_layers"] * sum(ssm_block_params(m).values())
            + m["hidden_size"])


def f32_param_count(m: dict) -> int:
    """Parameters kept in float32 (norms and the per-head A_log, D,
    dt_bias); the rest are the configuration's bfloat16."""
    blk = ssm_block_params(m)
    return m["num_hidden_layers"] * (blk["heads"] + blk["norm"]) + m[
        "hidden_size"]


def ssd_chunk_work(m: dict) -> tuple:
    """The intra-chunk dual form of one chunk of Q tokens, every head:
    C B^T once (2 Q^2 N, the heads share one group), the decayed scores
    times x for each head (2 Q^2 P a head).  Bytes: B, C, the cumulative
    log-decay and x read, y written, in float32.  Returns (FLOPs, bytes)."""
    _, _, h, p, n, _, q = dims(m)
    flops = 2.0 * q * q * n + h * 2.0 * q * q * p
    nbytes = 4.0 * (2 * q * n + h * q + 2 * h * q * p)
    return flops, nbytes


def ssm_train_flops_per_token(m: dict) -> float:
    """Model FLOPs of one trained token: the in and out projections, the
    depthwise conv, SSD's intra-chunk term (``ssd_chunk_work`` over the
    chunk's tokens) and its inter-chunk terms (the chunk state each token
    adds, the state each token reads), the skip D x, and the tied head;
    the backward is twice the forward; no recompute."""
    d, d_in, h, p, n, w, q = dims(m)
    blk = ssm_block_params(m)
    matmul = 2.0 * (blk["in_proj"] + blk["out_proj"])
    conv = 2.0 * w * (d_in + 2 * n)
    intra = ssd_chunk_work(m)[0] / q
    inter = 2.0 * 2.0 * h * p * n
    layer = matmul + conv + intra + inter + 2.0 * d_in
    head = 2.0 * m["vocab_size"] * d
    return 3.0 * (m["num_hidden_layers"] * layer + head)
