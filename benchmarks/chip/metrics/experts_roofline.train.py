"""Roofline share, in %, of the grouped matmuls of the held experts: the
balanced FLOPs of their up and down products (``chipbench/counts.py``'s
rule: top_k x E_held / E_routed expert MLPs a token; the frozen prefix
forward, the trained suffix 3x) times the steps of the traced window,
over the device time of the ops under the expert layer's ``moe.experts``
scope (the matmuls, forward and backward, and the relayout of their
operands) times the chip's bf16 peak. The reader context does not name
the cell, so the cell is the ``--workload`` of the command line that
runs ``run.py``; a process whose command line names none is an error,
never a silent metric. Nothing where no op ran under that scope."""
import argparse
import sys

from chipbench import cells, layer_scopes


def expert_flops_per_step(cell) -> float:
    """Balanced FLOPs of the held experts' products in one split step."""
    from chipbench.kinds import finetune

    c, t = cell.config, cell.traffic
    ref = cells.reference_module(c)
    split = finetune.run_config(cell)[1].split
    n_blocks = c["n_layers"] // ref.layers_per_block(c)
    per_block = ref.expert_matmul_flops(c, t["seq_len"])
    return t["batch"] * per_block * (split + 3 * (n_blocks - split))


def read(ctx):
    secs = layer_scopes.layer_seconds_per_step(ctx, ("moe.experts",))
    if secs is None:
        return None
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload")
    name = ap.parse_known_args(sys.argv[1:])[0].workload
    if name is None:
        raise RuntimeError("experts_roofline.train: the command line names no --workload")
    cell = cells.resolve(name)
    if not hasattr(cells.reference_module(cell.config), "expert_matmul_flops"):
        return None
    flops = expert_flops_per_step(cell)
    return 100.0 * flops / (secs * ctx["peaks"]["bf16_flops_per_s"])
