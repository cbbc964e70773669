"""Operations and bytes of the split fine-tune step, from shapes alone.

Model FLOPs per sample of sequence length S (a multiply-add is 2):

- the frozen prefix, forward: ``split`` blocks;
- the trained suffix, forward and backward: 3x its blocks' forward,
  less the gradient into the boundary activations (nothing upstream is
  trained, so the first trained block's input projections need none);
- the head: forward and backward (weights and input), 3 x 2 S d V over
  the published (unpadded) vocabulary.

Recomputation under remat is not counted. Attention is causal: a query
at position t reads t + 1 keys, so the score and value products cost
2 S^2 H hd in all (each half of 4 S^2 H hd). The SSD mixer is counted as
the chunked algorithm of arXiv:2405.21060 with chunk Q: within each
chunk the causal half of C B^T and of its product with x, and per token
one state update and one state read (2 N H P each).
"""
from __future__ import annotations


def _mamba2(c: dict, s: int):
    """(projection FLOPs, mixer FLOPs, input-projection FLOPs) of one block."""
    d, n, p = c["d_model"], c["ssm_state"], c["ssm_headdim"]
    di = c["ssm_expand"] * d
    h = di // p
    q = min(c["ssm_chunk"], s)
    w = c["conv_width"]
    in_proj = 2 * d * (2 * di + 2 * n + h) * s
    out_proj = 2 * di * d * s
    conv = 2 * w * (di + 2 * n) * s
    intra = (2 * n + 2 * h * p) * (q + 1) / 2 * s     # causal half, per chunk
    states = 2 * (2 * n * h * p) * s
    return in_proj + out_proj, conv + intra + states, in_proj


def _dense(c: dict, s: int):
    d, hq, hkv, hd, f = (c["d_model"], c["n_heads"], c["n_kv_heads"],
                         c["head_dim"], c["d_ff"])
    qkv = 2 * d * (hq + 2 * hkv) * hd * s
    out = 2 * hq * hd * d * s
    mlp = 3 * 2 * d * f * s
    attn = 2 * s * s * hq * hd                          # causal QK^T and PV
    return qkv + out + mlp, attn, qkv


BLOCKS = {"ssm": _mamba2, "dense": _dense}


def block_flops(c: dict, s: int) -> float:
    proj, mix, _ = BLOCKS[c["family"]](c, s)
    return proj + mix


def finetune_flops_per_sample(c: dict, s: int, split: int) -> dict:
    """Model FLOPs of one sample through the split fine-tune step."""
    proj, mix, in_proj = BLOCKS[c["family"]](c, s)
    fwd = proj + mix
    n_suffix = c["n_layers"] - split
    head = 2 * s * c["d_model"] * c["vocab_size"]
    out = {
        "prefix": split * fwd,
        "suffix": 3 * n_suffix * fwd - in_proj,
        "head": 3 * head,
    }
    out["total"] = sum(out.values())
    return out


def boundary_elements(batch: int, s: int, d: int) -> int:
    return batch * s * d


def quantize_bytes(elements: int, tile: int = 128, act_bytes: int = 2) -> float:
    """bf16 in, int8 plus one float32 scale per ``tile`` out."""
    return elements * (act_bytes + 1 + 4 / tile)


def dequantize_bytes(elements: int, tile: int = 128, act_bytes: int = 2) -> float:
    """int8 plus scales in, bf16 out."""
    return elements * (1 + 4 / tile + act_bytes)
