"""Operations and bytes of the split fine-tune step, from shapes alone.

Model FLOPs per sample of sequence length S (a multiply-add is 2), in
scanned blocks, as the program splits the model (``split`` counts
blocks, not layers):

- the frozen prefix, forward: ``split`` blocks;
- the trained suffix, forward and backward: 3x its blocks' forward,
  less the gradient into the boundary activations (nothing upstream is
  trained, so the first trained block's input projections need none);
- the head: forward and backward (weights and input), 3 x 2 S d V over
  the published (unpadded) vocabulary.

Recomputation under remat is not counted. One block is counted by the
configuration's plain reference module (``reference`` in its file,
``chipbench/reference/<name>.py``), found by name:

- ``block_flops(c, s) -> (proj, mix, in_proj)``: one block's forward
  over S tokens; ``proj`` the products with weights, ``mix`` the rest
  (convolution, scan, attention scores and values), ``in_proj`` the part
  of ``proj`` that reads the block's input (the projections of its first
  layer), whose input gradient the first trained block does not need;
- ``layers_per_block(c)``: how many of the configuration's ``n_layers``
  one block spans, so that there are ``n_layers // layers_per_block``
  blocks.

A block of several layers (a hybrid's period) sums its layers. A layer
with routed experts (MoE) is counted as balanced routing runs it on the
experts this chip holds: the router and the shared experts in full, and
the routed experts as ``top_k x E_held / E_routed`` expert MLPs per
token, each at its published width.
"""
from __future__ import annotations

from chipbench import cells


def block_flops(c: dict, s: int) -> float:
    """Forward FLOPs of one block of configuration ``c`` over S tokens."""
    proj, mix, _ = cells.reference_module(c).block_flops(c, s)
    return proj + mix


def finetune_flops_per_sample(c: dict, s: int, split: int) -> dict:
    """Model FLOPs of one sample through the split fine-tune step; the
    first ``split`` blocks are frozen."""
    arch = cells.reference_module(c)
    proj, mix, in_proj = arch.block_flops(c, s)
    fwd = proj + mix
    n_blocks = c["n_layers"] // arch.layers_per_block(c)
    head = 2 * s * c["d_model"] * c["vocab_size"]
    out = {
        "prefix": split * fwd,
        "suffix": 3 * (n_blocks - split) * fwd - in_proj,
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
