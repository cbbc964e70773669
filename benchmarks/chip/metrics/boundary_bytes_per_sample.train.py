"""Bytes per sample that cross the tier boundary: the leaves of the
program's extract output (int8 plus scales with the boundary on, bf16
without), from the shapes of ``make_extract_fn`` for the step's plan."""


def read(ctx):
    return ctx.get("boundary_bytes_per_sample") or None
